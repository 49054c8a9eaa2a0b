"""Flash attention as a Pallas TPU kernel.

TPU-native tiling (not a CUDA port): the grid is
``(batch, q_heads, q_blocks, kv_blocks)`` with the **kv dimension innermost
and sequential** — TPU grids execute the trailing dimension in order on a
core, so the online-softmax running state (row max ``m``, denominator ``l``,
fp32 accumulator) lives in VMEM scratch carried across kv iterations.
GQA never materialises expanded K/V: the kv BlockSpec index maps
``q_head → kv_head`` (``h // group``).

Block shapes default to 128×128 — MXU-aligned (the 128×128 systolic array),
and the working set per grid step is

    q(128×D) + k(128×D) + v(128×D) + acc(128×D) fp32 + s(128×128) fp32
    ≈ 0.33 MB at D=128 (bf16 inputs)

far under the ~16 MB/core VMEM budget, leaving the compiler room to
double-buffer the K/V streams.  Causal masking skips fully-masked kv blocks
via ``pl.when`` (no MXU work issued); the diagonal block applies an element
mask built from global row/col indices; padded kv columns are masked
unconditionally.

Validated against ``ref.attention_ref`` in ``interpret=True`` mode (CPU
container; TPU is the compile target).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = float(np.finfo(np.float32).min)


def _flash_kernel(
    q_ref,  # (1, 1, q_blk, D)
    k_ref,  # (1, 1, kv_blk, D)
    v_ref,  # (1, 1, kv_blk, D)
    o_ref,  # (1, 1, q_blk, D)
    m_scr,  # (q_blk,)      fp32 running max
    l_scr,  # (q_blk,)      fp32 running denominator
    acc_scr,  # (q_blk, D)  fp32 accumulator
    *,
    scale: float,
    causal: bool,
    q_blk: int,
    kv_blk: int,
    kv_valid: int,  # real (unpadded) kv length
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_off = qi * q_blk
    k_off = ki * kv_blk

    # Block-level skip: causal future blocks and fully-padded blocks do no
    # MXU work at all.
    run = k_off < kv_valid
    if causal:
        run = jnp.logical_and(run, q_off + q_blk - 1 >= k_off)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (q_blk, kv_blk)

        rows = q_off + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
        cols = k_off + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
        mask = cols < kv_valid
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Head-major flash attention; pads Sq/Sk up to block multiples.

    The causal path assumes self-attention (``Sq == Sk``); decode-style
    single-query attention uses the jnp path in ``ops.py`` (bandwidth-bound,
    no kernel needed).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    if causal and Sq != Sk:
        raise ValueError("causal flash kernel expects Sq == Sk self-attention")
    scale = float(scale if scale is not None else D ** -0.5)

    q_blk = min(q_block, Sq) if Sq < q_block else q_block
    kv_blk = min(kv_block, Sk) if Sk < kv_block else kv_block
    q_blk = max(8, q_blk)
    kv_blk = max(8, kv_blk)

    pad_q = (-Sq) % q_blk
    pad_k = (-Sk) % kv_blk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k

    grid = (B, Hq, Sq_p // q_blk, Sk_p // kv_blk)
    group = Hq // Hkv

    kern = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        q_blk=q_blk,
        kv_blk=kv_blk,
        kv_valid=Sk,
    )

    out_p = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_blk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, kv_blk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out_p[:, :, :Sq, :]
