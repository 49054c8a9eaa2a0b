"""Attention variants: GQA/MQA (optionally biased QKV), cross-attention, and
DeepSeek-V2 MLA (multi-head latent attention) with weight-absorbed decode.

All functions are pure; caches are explicit pytrees:

* GQA cache:  ``{"k": (B, S, Hkv, D), "v": (B, S, Hkv, D)}``
* MLA cache:  ``{"ckv": (B, S, kv_lora + qk_rope)}`` — the compressed latent
  (this is MLA's point: the cache holds 576 B/token instead of 2·H·D).
* cross cache (enc-dec): precomputed ``{"k","v"}`` from encoder output.

Decode positions are per-sequence ``(B,)`` so the serving engine can batch
requests at different depths (continuous batching).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.kernels import ops
from .act_sharding import constrain
from .layers import rmsnorm, rmsnorm_defs, rope
from .params import ParamDef

__all__ = [
    "gqa_defs",
    "gqa_apply",
    "gqa_decode",
    "mla_defs",
    "mla_apply",
    "mla_decode",
    "cross_attn_defs",
    "cross_attn_apply",
    "init_gqa_cache",
    "init_mla_cache",
]


# ================================================================ decode caches
def _write_row(buf: jax.Array, row: jax.Array, pos: jax.Array, layer) -> jax.Array:
    """Scatter each sequence's new ``row`` into ``buf`` at its ``pos``.

    ``buf`` is one layer's ``(B, S, ...)`` cache, or with ``layer`` the
    ``(L, B, S, ...)`` stack of every layer's: the scatter then touches only
    ``B`` rows of the stack, in place where the stack is a donated or
    loop-carried buffer."""
    idx = (jnp.arange(row.shape[0]), pos)
    if layer is not None:
        idx = (layer,) + idx
    return buf.at[idx].set(row.astype(buf.dtype))


def _layer_of(buf: jax.Array, layer) -> jax.Array:
    """Layer ``layer`` of a stacked cache (``buf`` itself without a layer)."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


# =========================================================================== GQA
def gqa_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    hd = cfg.resolved_head_dim
    d = {
        "wq": ParamDef((cfg.d_model, cfg.n_heads, hd), ("embed", "heads", "qk_dim")),
        "wk": ParamDef((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "qk_dim")),
        "wv": ParamDef((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "v_dim")),
        "wo": ParamDef((cfg.n_heads, hd, cfg.d_model), ("heads", "v_dim", "embed"), init="out_proj"),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamDef((cfg.n_heads, hd), ("heads", "qk_dim"), "zeros")
        d["bk"] = ParamDef((cfg.n_kv_heads, hd), ("kv_heads", "qk_dim"), "zeros")
        d["bv"] = ParamDef((cfg.n_kv_heads, hd), ("kv_heads", "v_dim"), "zeros")
    return d


def _project_qkv(params, x, cfg: ModelConfig, positions) -> Tuple[jax.Array, jax.Array, jax.Array]:
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_apply(
    params,
    x: jax.Array,  # (B, S, d_model)
    cfg: ModelConfig,
    positions: jax.Array,  # (B, S)
    *,
    causal: bool = True,
    prefix_len: int = 0,
    return_cache: bool = False,
    attn_impl: str = "auto",
):
    """Full-sequence attention (training / prefill)."""
    q, k, v = _project_qkv(params, x, cfg, positions)
    q = constrain(q, "batch", "seq", "act_heads", None)
    k = constrain(k, "batch", "seq", "act_kv_heads", None)
    v = constrain(v, "batch", "seq", "act_kv_heads", None)
    o = ops.flash_attention(q, k, v, causal=causal, prefix_len=prefix_len, impl=attn_impl)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    if return_cache:
        return out, {"k": k, "v": v}
    return out


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict[str, jax.Array]:
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_decode(
    params,
    x: jax.Array,  # (B, d_model) — one new token per sequence
    cfg: ModelConfig,
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # (B,) write/read position of the new token
    layer: Optional[jax.Array] = None,
):
    """One decode step: write K/V at ``pos``, attend over the valid prefix.

    With ``layer``, ``cache`` holds every layer's K/V stacked on a leading
    axis: the new rows are written into layer ``layer`` in place and its
    prefix is read from the stack, so no slab of one layer is copied out and
    back (see :func:`_write_row`)."""
    dtype = x.dtype
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bd,dhk->bhk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bd,dhk->bhk", x, params["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    if cfg.use_rope:
        q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k_cache = _write_row(cache["k"], k, pos, layer)
    v_cache = _write_row(cache["v"], v, pos, layer)
    o = ops.decode_attention(q, _layer_of(k_cache, layer), _layer_of(v_cache, layer), pos + 1)
    out = jnp.einsum("bhk,hkd->bd", o, params["wo"].astype(dtype))
    return out, {"k": k_cache, "v": v_cache}


# =========================================================================== MLA
def mla_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    m = cfg.mla
    assert m is not None
    qk = m.qk_nope_dim + m.qk_rope_dim
    d = {
        "wq": ParamDef((cfg.d_model, cfg.n_heads, qk), ("embed", "heads", "qk_dim")),
        "w_dkv": ParamDef((cfg.d_model, m.kv_lora_rank + m.qk_rope_dim), ("embed", "kv_lora")),
        "kv_norm": rmsnorm_defs(m.kv_lora_rank),
        "w_uk": ParamDef((m.kv_lora_rank, cfg.n_heads, m.qk_nope_dim), ("kv_lora", "heads", "qk_dim")),
        "w_uv": ParamDef((m.kv_lora_rank, cfg.n_heads, m.v_head_dim), ("kv_lora", "heads", "v_dim")),
        "wo": ParamDef((cfg.n_heads, m.v_head_dim, cfg.d_model), ("heads", "v_dim", "embed"), init="out_proj"),
    }
    if m.q_lora_rank:
        d["w_dq"] = ParamDef((cfg.d_model, m.q_lora_rank), ("embed", "kv_lora"))
        d["q_norm"] = rmsnorm_defs(m.q_lora_rank)
        d["w_uq"] = ParamDef((m.q_lora_rank, cfg.n_heads, qk), ("kv_lora", "heads", "qk_dim"))
    return d


def _mla_q(params, x, cfg: ModelConfig, positions):
    m = cfg.mla
    dtype = x.dtype
    if m.q_lora_rank:
        cq = rmsnorm(params["q_norm"], jnp.einsum("...d,dr->...r", x, params["w_dq"].astype(dtype)), cfg.rms_eps)
        q = jnp.einsum("...r,rhk->...hk", cq, params["w_uq"].astype(dtype))
    else:
        q = jnp.einsum("...d,dhk->...hk", x, params["wq"].astype(dtype))
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(params, x, cfg: ModelConfig, positions):
    """Compressed latent + shared rope key (what the cache stores)."""
    m = cfg.mla
    dtype = x.dtype
    dkv = jnp.einsum("...d,dr->...r", x, params["w_dkv"].astype(dtype))
    c = rmsnorm(params["kv_norm"], dkv[..., : m.kv_lora_rank], cfg.rms_eps)
    k_rope = dkv[..., m.kv_lora_rank :]
    # the shared rope key has a single "head"
    k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c, k_rope


def mla_apply(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    causal: bool = True,
    return_cache: bool = False,
    attn_impl: str = "auto",
):
    """Training/prefill MLA: expand K/V per head (prefill-optimal form)."""
    m = cfg.mla
    dtype = x.dtype
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    c, k_rope = _mla_ckv(params, x, cfg, positions)

    k_nope = jnp.einsum("bsr,rhk->bshk", c, params["w_uk"].astype(dtype))
    v = jnp.einsum("bsr,rhv->bshv", c, params["w_uv"].astype(dtype))
    H = cfg.n_heads
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None], k_rope.shape[:2] + (H, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    o = ops.flash_attention(q, k, v, causal=causal, scale=scale, impl=attn_impl)
    out = jnp.einsum("bshv,hvd->bsd", o, params["wo"].astype(dtype))
    if return_cache:
        return out, {"ckv": jnp.concatenate([c, k_rope], axis=-1)}
    return out


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict[str, jax.Array]:
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank + m.qk_rope_dim), dtype)}


def mla_decode(
    params,
    x: jax.Array,  # (B, d_model)
    cfg: ModelConfig,
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # (B,)
    layer: Optional[jax.Array] = None,
):
    """Weight-absorbed MLA decode: attention runs in the compressed space.

    q_c = q_nope @ w_uk  → score = q_c·c + q_rope·k_rope over the latent
    cache; the weighted latent sum is expanded through w_uv once.  A stacked
    cache and its ``layer`` are read and written as in :func:`gqa_decode`.
    """
    m = cfg.mla
    dtype = x.dtype
    q_nope, q_rope = _mla_q(params, x[:, None], cfg, pos[:, None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]  # (B, H, ·)
    c_new, k_rope_new = _mla_ckv(params, x[:, None], cfg, pos[:, None])
    ckv_new = jnp.concatenate([c_new, k_rope_new], axis=-1)[:, 0]

    ckv_stack = _write_row(cache["ckv"], ckv_new, pos, layer)
    ckv = _layer_of(ckv_stack, layer)
    c_cache, r_cache = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank :]

    q_c = jnp.einsum("bhk,rhk->bhr", q_nope, params["w_uk"].astype(dtype))
    s = jnp.einsum("bhr,bsr->bhs", q_c, c_cache.astype(dtype)) + jnp.einsum(
        "bhk,bsk->bhs", q_rope, r_cache.astype(dtype)
    )
    s = s.astype(jnp.float32) * ((m.qk_nope_dim + m.qk_rope_dim) ** -0.5)
    valid = jnp.arange(ckv.shape[1])[None] < (pos + 1)[:, None]
    s = jnp.where(valid[:, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    o_c = jnp.einsum("bhs,bsr->bhr", p, c_cache.astype(dtype))
    o = jnp.einsum("bhr,rhv->bhv", o_c, params["w_uv"].astype(dtype))
    out = jnp.einsum("bhv,hvd->bd", o, params["wo"].astype(dtype))
    return out, {"ckv": ckv_stack}


# ==================================================================== cross-attn
def cross_attn_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    return gqa_defs(cfg)


def cross_attn_kv(params, enc_out: jax.Array, cfg: ModelConfig) -> Dict[str, jax.Array]:
    dtype = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(dtype))
    if cfg.qkv_bias:
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    return {"k": k, "v": v}


def cross_attn_apply(
    params,
    x: jax.Array,  # (B, S, d) or (B, d) for decode
    cfg: ModelConfig,
    kv: Dict[str, jax.Array],
    *,
    attn_impl: str = "auto",
):
    """Decoder→encoder attention (no positional rotation, never causal)."""
    dtype = x.dtype
    decode = x.ndim == 2
    xq = x[:, None] if decode else x
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"].astype(dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dtype)
    if decode:
        o = ops.decode_attention(q[:, 0], kv["k"], kv["v"], kv["k"].shape[1])[:, None]
    else:
        o = ops.flash_attention(q, kv["k"], kv["v"], causal=False, impl=attn_impl)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dtype))
    return out[:, 0] if decode else out
