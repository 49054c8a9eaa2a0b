"""Jit'd kernel wrappers with backend dispatch.

``impl`` semantics (both ops):

* ``"auto"``   — Pallas on TPU, XLA elsewhere (CPU container → XLA, so the
  512-device dry-run lowers clean HLO whose cost analysis reflects the real
  matmul/scan structure; the Pallas kernels are the TPU target).
* ``"pallas"`` — the Pallas kernel (``interpret=True`` off-TPU).
* ``"xla"``    — blocked online-softmax / chunked-scan pure-jnp
  implementations: same FLOPs and memory-traffic *structure* as the kernels
  (causal block skipping included), so roofline terms are honest.
* ``"ref"``    — the naive oracles (tests only).

Layouts: models pass batch-major tensors (B, S, H, D); wrappers transpose to
the kernels' head-major layout.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref
from .flash_attention import flash_attention_pallas
from .ssd_scan import ssd_scan_pallas

__all__ = ["flash_attention", "ssd_scan", "decode_attention"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------- attention
def _xla_flash(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float,
    prefix_len: int,
    q_block: int,
    kv_block: int,
    n_causal_chunks: int = 8,
) -> jax.Array:
    """Blocked online-softmax attention in pure jnp, compile-size bounded.

    Structure: a *python* loop over at most ``n_causal_chunks`` q
    super-chunks (each with a static kv extent — so fully-masked kv blocks
    beyond the diagonal are never computed), and ``lax.scan`` over kv blocks
    inside each super-chunk (HLO size is O(chunks), not O(seq²/block²)).
    Masked-flop waste is bounded by ~``1/(2·n_causal_chunks)`` ≈ 6%, keeping
    the roofline compute term honest at 32k+ sequence lengths.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]  # MLA: v_head_dim may differ from the qk dim
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min

    def chunk_attn(q0: int, qc: jax.Array, k_end: int):
        """Online softmax of one q chunk against kv[:k_end] via kv-scan."""
        nb = max(1, (k_end + kv_block - 1) // kv_block)
        pad_k = nb * kv_block - k_end
        kc = jax.lax.dynamic_slice_in_dim(jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else kf, 0, nb * kv_block, 1)
        vc = jax.lax.dynamic_slice_in_dim(jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else vf, 0, nb * kv_block, 1)
        kb = kc.reshape(B, nb, kv_block, Hkv, D)
        vb = vc.reshape(B, nb, kv_block, Hkv, Dv)
        rows = q0 + jnp.arange(qc.shape[1]) + (Sk - Sq)  # global row ids

        m0 = jnp.full(qc.shape[:-1], neg, jnp.float32)
        l0 = jnp.zeros(qc.shape[:-1], jnp.float32)
        a0 = jnp.zeros(qc.shape[:-1] + (Dv,), jnp.float32)

        def body(carry, inputs):
            m, l, acc = carry
            ki, kblk, vblk = inputs
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qc, kblk) * scale
            cols = ki * kv_block + jnp.arange(kv_block)
            mask = cols[None, :] < k_end  # padded kv tail
            if causal:
                cmask = rows[:, None] >= cols[None, :]
                if prefix_len > 0:
                    cmask = cmask | (cols[None, :] < prefix_len)
                mask = mask & cmask
            s = jnp.where(mask[None, :, None, None, :], s, neg)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd", p, vblk)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.arange(nb), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)),
        )
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]

    if not causal:
        o = chunk_attn(0, qf, Sk)
        return o.reshape(B, Sq, Hq, Dv).astype(q.dtype)

    # causal: ≤ n_causal_chunks q super-chunks, each with a static kv extent
    n_chunks = min(n_causal_chunks, max(1, (Sq + q_block - 1) // q_block))
    qc_size = -(-Sq // n_chunks)  # ceil
    outs = []
    for i in range(n_chunks):
        q0, q1 = i * qc_size, min((i + 1) * qc_size, Sq)
        if q0 >= q1:
            break
        k_end = min(Sk, q1 + (Sk - Sq))
        outs.append(chunk_attn(q0, qf[:, q0:q1], max(1, k_end)))
    o = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return o.reshape(B, Sq, Hq, Dv).astype(q.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    prefix_len: int = 0,
    impl: str = "auto",
    # 256×1024 tiles: 8× fewer q re-reads per kv pass than 128×128 while the
    # per-step working set (q+k+v+acc+s ≈ 2.4 MB at D=128) still fits VMEM
    # with headroom to double-buffer (§Perf iteration A4)
    q_block: int = 256,
    kv_block: int = 1024,
) -> jax.Array:
    D = q.shape[-1]
    scale = float(scale if scale is not None else D ** -0.5)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "pallas" and (prefix_len > 0 or v.shape[-1] != q.shape[-1]):
        impl = "xla"  # prefix-LM masking / MLA's v_dim≠qk_dim: blocked-jnp path
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, scale=scale, prefix_len=prefix_len)
    if impl == "xla":
        return _xla_flash(
            q, k, v, causal=causal, scale=scale, prefix_len=prefix_len,
            q_block=q_block, kv_block=kv_block,
        )
    if impl == "pallas":
        qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        o = flash_attention_pallas(
            qh, kh, vh, causal=causal, scale=scale,
            q_block=q_block, kv_block=kv_block, interpret=not _on_tpu(),
        )
        return jnp.swapaxes(o, 1, 2)
    raise ValueError(f"unknown impl {impl!r}")


def decode_attention(
    q: jax.Array,  # (B, Hq, D) — one new token
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,  # (B, S, Hkv, D)
    cache_len: jax.Array,  # (B,) or scalar — valid prefix length (inclusive of new token)
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention over a KV cache (bandwidth-bound; pure jnp)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = float(scale if scale is not None else D ** -0.5)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kf) * scale
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len)
    valid = jnp.arange(S)[None, :] < cache_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------- SSD
def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    D: Optional[jax.Array] = None,
    h0: Optional[jax.Array] = None,
    *,
    chunk: int = 128,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y (B,S,H,P), final state (B,H,P,N)).

    A ragged sequence is padded up to a whole number of chunks with
    ``dt = 0`` steps, which leave the state untouched (decay ``exp(0) = 1``,
    zero input), so the padded tail changes neither the kept outputs nor
    the final state."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "ref":
        y, h = _ref.ssd_ref(x, dt, A, Bm, Cm, D, h0=h0, return_state=True)
        return y, h
    S = x.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x, dt, Bm, Cm = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (x, dt, Bm, Cm)
        )
    if impl == "xla":
        y, h = _ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D, h0=h0, chunk=chunk, return_state=True)
        return y[:, :S], h
    if impl == "pallas":
        y, h = ssd_scan_pallas(
            jnp.swapaxes(x, 1, 2),
            jnp.swapaxes(dt, 1, 2),
            A,
            jnp.swapaxes(Bm, 1, 2),
            jnp.swapaxes(Cm, 1, 2),
            D,
            h0,
            chunk=chunk,
            interpret=not _on_tpu(),
        )
        return jnp.swapaxes(y, 1, 2)[:, :S], h
    raise ValueError(f"unknown impl {impl!r}")
