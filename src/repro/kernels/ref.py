"""Pure-jnp oracles for every Pallas kernel (correctness ground truth).

These are intentionally naive (full score matrices, sequential recurrences):
slow, obviously-correct implementations that per-kernel sweep tests compare
against in ``interpret=True`` mode.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["attention_ref", "ssd_ref", "ssd_chunked_ref"]


def attention_ref(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    prefix_len: int = 0,  # prefix-LM: bidirectional over the first N positions
    kv_len: Optional[jax.Array] = None,  # per-batch valid cache length
) -> jax.Array:
    """Full-softmax GQA attention, fp32 accumulation."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale

    neg = jnp.finfo(jnp.float32).min
    if causal:
        qi = jnp.arange(Sq)[:, None] + (Sk - Sq)  # align last q with last k
        ki = jnp.arange(Sk)[None, :]
        mask = qi >= ki
        if prefix_len > 0:
            mask = mask | (ki < prefix_len)
        s = jnp.where(mask[None, None, None], s, neg)
    if kv_len is not None:
        valid = jnp.arange(Sk)[None, :] < kv_len[:, None]  # (B, Sk)
        s = jnp.where(valid[:, None, None, None], s, neg)

    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return o.reshape(B, Sq, Hq, -1).astype(q.dtype)


def ssd_ref(
    x: jax.Array,  # (B, S, H, P)   — per-head inputs
    dt: jax.Array,  # (B, S, H)      — positive step sizes
    A: jax.Array,  # (H,)           — negative decay rates
    Bm: jax.Array,  # (B, S, G, N)   — input matrices (G groups)
    Cm: jax.Array,  # (B, S, G, N)   — output matrices
    D: Optional[jax.Array] = None,  # (H,) skip gain
    h0: Optional[jax.Array] = None,  # (B, H, P, N) initial state
    return_state: bool = False,
):
    """Sequential Mamba-2 SSD recurrence (the exact semantics):

        h_t = exp(A·dt_t) · h_{t-1} + dt_t · (x_t ⊗ B_t)
        y_t = (h_t · C_t) + D · x_t
    """
    Bsz, S, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)  # (B,S,H,N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)

    h = (
        h0.astype(jnp.float32)
        if h0 is not None
        else jnp.zeros((Bsz, H, P, Bm.shape[-1]), jnp.float32)
    )

    def step(h, t):
        decay = jnp.exp(Af * dtf[:, t])  # (B,H)
        upd = dtf[:, t, :, None, None] * (xf[:, t, :, :, None] * Bf[:, t, :, None, :])
        h = h * decay[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", h, Cf[:, t])
        return h, y

    h, ys = jax.lax.scan(step, h, jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1)  # (B,S,H,P)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * xf
    y = y.astype(x.dtype)
    if return_state:
        return y, h.astype(jnp.float32)
    return y


def ssd_chunked_ref(
    x, dt, A, Bm, Cm, D=None, h0=None, chunk: int = 64, return_state: bool = False
):
    """Chunked (parallel-form) SSD — same math as :func:`ssd_ref`, organised
    as the Mamba-2 block decomposition.  Used to cross-check the chunked
    algorithm itself before it is ported to Pallas."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    G = Bm.shape[2]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nC = S // chunk

    xf = x.astype(jnp.float32).reshape(Bsz, nC, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nC, chunk, H)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2).reshape(Bsz, nC, chunk, H, N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2).reshape(Bsz, nC, chunk, H, N)

    a = Af[None, None, None, :] * dtf  # (B,nC,L,H) log-decay per step
    cum = jnp.cumsum(a, axis=2)  # s_t = Σ_{u<=t} a_u

    # intra-chunk: M[t,s] = (C_t·B_s) · exp(s_t − s_s) · dt_s   for s ≤ t
    CB = jnp.einsum("bclhn,bcmhn->bchlm", Cf, Bf)  # (B,nC,H,L,L)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # s_t - s_s → (B,nC,L,L,H)
    diff = jnp.moveaxis(diff, -1, 2)  # (B,nC,H,L,L)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # clamp masked (s > t) entries BEFORE exp: their diff is positive and can
    # overflow, and `where` would still backprop NaN through the dead branch
    diff = jnp.where(tri[None, None, None], diff, -jnp.inf)
    M = jnp.where(tri[None, None, None], CB, 0.0) * jnp.exp(diff)
    M = M * jnp.moveaxis(dtf, -1, 2)[:, :, :, None, :]  # × dt_s
    y_intra = jnp.einsum("bchlm,bcmhp->bclhp", M, xf)

    # chunk summaries: state contribution of each chunk
    seg = jnp.exp(cum[:, :, -1:, :] - cum)  # exp(s_L − s_s)
    states = jnp.einsum("bclh,bclhn,bclhp->bchpn", seg * dtf, Bf, xf)

    # inter-chunk recurrence over chunk summaries
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nC,H)
    h = (
        h0.astype(jnp.float32)
        if h0 is not None
        else jnp.zeros((Bsz, H, P, N), jnp.float32)
    )

    def step(h, inputs):
        dec, st = inputs  # dec (B,H), st (B,H,P,N)
        h_out = h  # state *entering* the chunk
        h = h * dec[:, :, None, None] + st
        return h, h_out

    h, h_prevs = jax.lax.scan(
        step, h, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0))
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (B,nC,H,P,N) state entering each chunk

    # inter-chunk output: y_t += C_t · (exp(s_t) · h_prev)
    y_inter = jnp.einsum("bclhn,bchpn->bclhp", Cf * jnp.exp(cum)[..., None], h_prevs)

    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    y = y.astype(x.dtype)
    if return_state:
        return y, h
    return y
