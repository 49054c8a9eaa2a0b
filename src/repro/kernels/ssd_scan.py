"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

The SSD (state-space duality) algorithm splits the linear recurrence

    h_t = exp(A·dt_t)·h_{t-1} + dt_t·(x_t ⊗ B_t);   y_t = C_t·h_t + D·x_t

into MXU-shaped block work per chunk of length L:

    intra-chunk   Y₁ = (tril(C·Bᵀ ⊙ decay) ⊙ dt) @ X          (L×L @ L×P)
    inter-chunk   Y₂ = (C ⊙ exp(cum)) @ h_prevᵀ               (L×N @ N×P)
    state update  h  = exp(cum_L)·h + Xᵀ @ (B ⊙ seg·dt)        (P×L @ L×N)

The original CUDA kernel leans on warp shuffles for the cumulative decay;
on TPU we restructure it as whole-chunk masked (L×L) reductions for the
prefix sums (VPU) plus three MXU matmuls — the TPU-native form of the same
math (DESIGN.md §6).

Grid: ``(batch, heads, chunks)`` with chunks innermost/sequential; the
running state ``h (P×N fp32)`` lives in VMEM scratch carried across chunk
iterations.  ``dt`` enters as a ``(1, L)`` lane row per block and the
per-head scalars ``A``/``D`` live in SMEM, which keeps every block shape
legal for the TPU compiler.  VMEM per step at L=256, P=64, N=128:
x(L×P) + B,C(L×N) + M and masks (L×L) + h(P×N fp32) ≈ 1 MB.

Outputs: per-position y (B,H,S,P) and the final state (B,H,P,N) — the
latter hands off to the decode path / chunked prefill.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(
    x_ref,  # (1, 1, L, P)
    dt_ref,  # (1, 1, 1, L)   step sizes as a lane row
    a_ref,  # (H,) SMEM      per-head decay rate A (negative)
    b_ref,  # (1, 1, L, N)
    c_ref,  # (1, 1, L, N)
    d_ref,  # (H,) SMEM      per-head skip gain
    h0_ref,  # (1, 1, P, N)   initial state
    y_ref,  # (1, 1, L, P)
    hout_ref,  # (1, 1, P, N)
    h_scr,  # (P, N) fp32 running state
    *,
    L: int,
):
    head = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (1, L)
    A = a_ref[head]
    Bm = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)

    # Prefix sums as masked reductions of 2-D tiles (no cumsum or 1-D
    # vectors in the kernel): cum_col[t] and cum_row[s] are both
    # s_t = Σ_{u<=t} A·dt_u, laid out down the sublanes and along the lanes.
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = rows >= cols
    a_row = A * dt  # (1, L) log-decay per step
    dt_col = jnp.sum(jnp.where(rows == cols, dt, 0.0), axis=1, keepdims=True)  # (L, 1)
    a_col = A * dt_col
    cum_col = jnp.sum(jnp.where(tri, a_row, 0.0), axis=1, keepdims=True)  # (L, 1)
    cum_row = jnp.sum(jnp.where(rows <= cols, a_col, 0.0), axis=0, keepdims=True)  # (1, L)
    total = jnp.sum(a_row, axis=1, keepdims=True)  # (1, 1) = s_L

    # --- intra-chunk: M[t,s] = (C_t·B_s)·exp(s_t−s_s)·dt_s, s ≤ t ------------
    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    # clamp masked entries before exp (they can overflow; and keeps the
    # kernel bit-consistent with the differentiable jnp form)
    diff = jnp.where(tri, cum_col - cum_row, -jnp.inf)
    M = jnp.where(tri, CB, 0.0) * jnp.exp(diff) * dt
    y = jax.lax.dot_general(
        M, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, P)

    # --- inter-chunk: y += (C ⊙ exp(cum)) @ hᵀ --------------------------------
    h_prev = h_scr[...]
    Ce = Cm * jnp.exp(cum_col)
    y = y + jax.lax.dot_general(
        Ce, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # --- state update: h = exp(s_L)·h + Xᵀ @ (B ⊙ exp(s_L−s)·dt) -------------
    Bw = Bm * (jnp.exp(total - cum_col) * dt_col)
    h_scr[...] = h_prev * jnp.exp(total) + jax.lax.dot_general(
        x, Bw, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # --- skip connection + writes ---------------------------------------------
    y_ref[0, 0] = (y + d_ref[head] * x).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_scr[...].astype(hout_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,  # (B, H, S, P)
    dt: jax.Array,  # (B, H, S)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, G, S, N)
    Cm: jax.Array,  # (B, G, S, N)
    D: Optional[jax.Array] = None,  # (H,)
    h0: Optional[jax.Array] = None,  # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Head-major chunked SSD.  Returns (y (B,H,S,P), h_final (B,H,P,N)).

    On TPU the chunk length must be a multiple of 128 or the whole
    sequence: it is the lane extent of ``dt``'s block."""
    B, H, S, P = x.shape
    _, G, _, N = Bm.shape
    assert H % G == 0, (H, G)
    L = min(chunk, S)
    if S % L != 0:
        raise ValueError(f"seq len {S} must be a multiple of chunk {L}")
    nc = S // L
    group = H // G

    if D is None:
        D = jnp.zeros((H,), jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    kern = functools.partial(_ssd_kernel, L=L)
    grid = (B, H, nc)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    y, h_final = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c: (b, h, 0, c)),
            smem,
            pl.BlockSpec((1, 1, L, N), lambda b, h, c, g=group: (b, h // g, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c, g=group: (b, h // g, c, 0)),
            smem,
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(
        x, dt[:, :, None, :], A.astype(jnp.float32), Bm, Cm,
        D.astype(jnp.float32), h0,
    )
    return y, h_final
