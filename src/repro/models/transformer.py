"""Generic multi-family transformer LM assembly.

One model covers the ten assigned architectures through config:

* dense / GQA / MQA decoders (deepseek-7b, qwen2-72b, phi3, gemma),
* MoE decoders (llama4-scout, deepseek-v2-lite w/ MLA),
* hybrid SSM+attention+MoE (jamba: attention at position ``attn_offset`` of
  every ``attn_every`` layers, MoE every ``moe_every``),
* pure SSM (mamba2-130m),
* encoder–decoder with cross-attention (whisper-medium; conv frontend
  stubbed to precomputed frame embeddings),
* prefix-LM VLM (paligemma-3b; SigLIP stubbed to patch embeddings).

**Stacking**: layers are grouped into a repeating *superblock* (period =
lcm of the attention/MoE cadences), parameters are stacked along a leading
``layers`` axis, and the stack runs under ``jax.lax.scan`` — compile time is
O(superblock), not O(depth), which is what makes 80-layer × 512-device
dry-runs tractable.  ``first_k_dense`` prefix layers (deepseek-v2) are
unrolled before the scan.

All entry points are pure functions of (cfg, params, batch):

    model_defs(cfg)                          → ParamDef tree
    forward(cfg, params, batch)              → logits           (train path)
    prefill(cfg, params, batch)              → (logits, cache)
    decode_step(cfg, params, cache, ...)     → (logits, cache)
    init_cache(cfg, batch, max_len)          → zeroed cache pytree
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn_mod
from . import mamba as mamba_mod
from . import moe as moe_mod
from .act_sharding import constrain
from .layers import (
    embed_apply,
    embed_defs,
    ffn_apply,
    ffn_defs,
    lm_head_defs,
    logits_apply,
    rmsnorm,
    rmsnorm_defs,
    sinusoidal_positions,
)
from .params import ParamDef

__all__ = [
    "model_defs",
    "forward",
    "prefill",
    "decode_step",
    "init_cache",
    "num_layers_in_stack",
]


# ============================================================== per-layer defs
def _layer_defs(cfg: ModelConfig, layer_idx: int, *, decoder_cross: bool = False) -> Dict[str, Any]:
    d: Dict[str, Any] = {"ln1": rmsnorm_defs(cfg.d_model)}
    if cfg.layer_is_attn(layer_idx):
        d["attn"] = attn_mod.mla_defs(cfg) if cfg.mla is not None else attn_mod.gqa_defs(cfg)
    else:
        d["ssm"] = mamba_mod.mamba_defs(cfg)
    if decoder_cross:
        d["ln_x"] = rmsnorm_defs(cfg.d_model)
        d["cross"] = attn_mod.cross_attn_defs(cfg)
    if cfg.layer_is_moe(layer_idx):
        d["ln2"] = rmsnorm_defs(cfg.d_model)
        d["moe"] = moe_mod.moe_defs(cfg, cfg.moe)
    elif cfg.d_ff > 0:
        d["ln2"] = rmsnorm_defs(cfg.d_model)
        d["ffn"] = ffn_defs(cfg.d_model, cfg.d_ff)
    return d


def _stack_defs(defs, repeats: int):
    def one(d: ParamDef) -> ParamDef:
        return ParamDef((repeats,) + d.shape, ("layers",) + d.logical_axes, d.init, d.scale, d.dtype)

    return jax.tree_util.tree_map(one, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def num_layers_in_stack(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_prefix, period, repeats) of the decoder stack."""
    n_prefix = cfg.moe.first_k_dense if cfg.moe else 0
    period = cfg.superblock_period
    repeats = (cfg.n_layers - n_prefix) // period
    return n_prefix, period, repeats


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    n_prefix, period, repeats = num_layers_in_stack(cfg)
    d: Dict[str, Any] = {"embed": embed_defs(cfg), "final_norm": rmsnorm_defs(cfg.d_model)}
    if not cfg.tie_embeddings:
        d["lm_head"] = lm_head_defs(cfg)
    for j in range(n_prefix):
        d[f"prefix_{j}"] = _layer_defs(cfg, j)
    sb = {f"pos_{p}": _layer_defs(cfg, n_prefix + p, decoder_cross=cfg.encdec) for p in range(period)}
    d["blocks"] = _stack_defs(sb, repeats)
    if cfg.encdec:
        enc_cfg = cfg  # same width per the assigned config
        enc_layer = {
            "ln1": rmsnorm_defs(cfg.d_model),
            "attn": attn_mod.gqa_defs(enc_cfg),
            "ln2": rmsnorm_defs(cfg.d_model),
            "ffn": ffn_defs(cfg.d_model, cfg.d_ff),
        }
        d["encoder"] = {
            "blocks": _stack_defs(enc_layer, cfg.n_enc_layers),
            "final_norm": rmsnorm_defs(cfg.d_model),
        }
    return d


# ============================================================== layer application
def _apply_mixer(
    lp, x, cfg: ModelConfig, positions, *, causal, prefix_len, attn_impl, ssd_impl,
    return_cache=False,
):
    h = rmsnorm(lp["ln1"], x, cfg.rms_eps)
    if "attn" in lp:
        if cfg.mla is not None:
            out = attn_mod.mla_apply(
                lp["attn"], h, cfg, positions,
                causal=causal, return_cache=return_cache, attn_impl=attn_impl,
            )
        else:
            out = attn_mod.gqa_apply(
                lp["attn"], h, cfg, positions,
                causal=causal, prefix_len=prefix_len,
                return_cache=return_cache, attn_impl=attn_impl,
            )
    else:
        out = mamba_mod.mamba_apply(
            lp["ssm"], h, cfg, return_cache=return_cache, ssd_impl=ssd_impl
        )
    if return_cache:
        mixed, cache = out
        return x + mixed, cache
    return x + out


def _apply_ffn(lp, x, cfg: ModelConfig):
    """Post-mixer FFN/MoE sublayer; returns (x, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if "moe" in lp:
        h = rmsnorm(lp["ln2"], x, cfg.rms_eps)
        out, aux = moe_mod.moe_apply(lp["moe"], h, cfg, cfg.moe)
        return x + out, aux.astype(jnp.float32)
    if "ffn" in lp:
        h = rmsnorm(lp["ln2"], x, cfg.rms_eps)
        return x + ffn_apply(lp["ffn"], h, cfg.hidden_act), zero
    return x, zero


def _apply_layer_full(
    lp, x, cfg: ModelConfig, positions, *,
    causal=True, prefix_len=0, attn_impl="auto", ssd_impl="auto", enc_out=None,
    cross_kv=None, return_cache=False,
):
    """One full layer on a full sequence. Returns (x, aux, cache|None)."""
    if return_cache:
        x, mixer_cache = _apply_mixer(
            lp, x, cfg, positions, causal=causal, prefix_len=prefix_len,
            attn_impl=attn_impl, ssd_impl=ssd_impl, return_cache=True,
        )
    else:
        x = _apply_mixer(
            lp, x, cfg, positions, causal=causal, prefix_len=prefix_len,
            attn_impl=attn_impl, ssd_impl=ssd_impl,
        )
        mixer_cache = None
    if "cross" in lp and enc_out is not None:
        h = rmsnorm(lp["ln_x"], x, cfg.rms_eps)
        kv = attn_mod.cross_attn_kv(lp["cross"], enc_out, cfg) if cross_kv is None else cross_kv
        x = x + attn_mod.cross_attn_apply(lp["cross"], h, cfg, kv, attn_impl=attn_impl)
        if return_cache:
            mixer_cache = {"mixer": mixer_cache, "cross": kv}
    elif return_cache:
        mixer_cache = {"mixer": mixer_cache}
    x, aux = _apply_ffn(lp, x, cfg)
    x = constrain(x, "batch", "seq", "act_embed")
    return x, aux, mixer_cache


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ============================================================== encoder (whisper)
def _encode(cfg: ModelConfig, params, enc_embeds: jax.Array, attn_impl: str) -> jax.Array:
    """Bidirectional encoder over (stub) frame embeddings."""
    x = enc_embeds.astype(cfg.compute_jdtype())
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model, x.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

    def body(carry, lp):
        y, _, _ = _apply_layer_full(
            lp, carry, cfg, positions, causal=False, attn_impl=attn_impl
        )
        return y, None

    x, _ = jax.lax.scan(_remat_wrap(body, cfg), x, params["encoder"]["blocks"])
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.rms_eps)


# ============================================================== full forward
def _assemble_input(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    """Token embeddings (+ VLM prefix embeddings).  Returns (x, prefix_len)."""
    x = embed_apply(params["embed"], batch["tokens"], cfg)
    prefix_len = 0
    if cfg.vision_tokens > 0 and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
        prefix_len = cfg.vision_tokens if cfg.prefix_lm else 0
    return x, prefix_len


def _run_stack(
    cfg, params, x, positions, *, prefix_len, attn_impl, ssd_impl, enc_out, collect_cache
):
    """Prefix layers + scanned superblocks.  Returns (x, aux, caches)."""
    n_prefix, period, repeats = num_layers_in_stack(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    prefix_caches = []
    for j in range(n_prefix):
        x, aux, c = _apply_layer_full(
            params[f"prefix_{j}"], x, cfg, positions,
            prefix_len=prefix_len, attn_impl=attn_impl, ssd_impl=ssd_impl,
            enc_out=enc_out, return_cache=collect_cache,
        )
        aux_total = aux_total + aux
        prefix_caches.append(c)

    def body(carry, lp):
        y, aux_c = carry
        cache_p = {}
        for p in range(period):
            y, aux, c = _apply_layer_full(
                lp[f"pos_{p}"], y, cfg, positions,
                prefix_len=prefix_len, attn_impl=attn_impl, ssd_impl=ssd_impl,
                enc_out=enc_out, return_cache=collect_cache,
            )
            aux_c = aux_c + aux
            cache_p[f"pos_{p}"] = c
        return (y, aux_c), (cache_p if collect_cache else None)

    (x, aux_total), stack_caches = jax.lax.scan(
        _remat_wrap(body, cfg), (x, aux_total), params["blocks"]
    )
    return x, aux_total, (prefix_caches, stack_caches)


def forward(
    cfg: ModelConfig,
    params,
    batch: Dict[str, jax.Array],
    *,
    attn_impl: str = "auto",
    ssd_impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Training forward pass → (logits, aux_loss).

    ``attn_impl`` / ``ssd_impl`` pick the attention and SSD kernels
    (:mod:`repro.kernels.ops`); a differentiated caller asks for ``"xla"``."""
    enc_out = None
    if cfg.encdec:
        enc_out = _encode(cfg, params, batch["enc_embeds"], attn_impl)
    x, prefix_len = _assemble_input(cfg, params, batch)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    x, aux, _ = _run_stack(
        cfg, params, x, positions, prefix_len=prefix_len, attn_impl=attn_impl,
        ssd_impl=ssd_impl, enc_out=enc_out, collect_cache=False,
    )
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if cfg.vision_tokens > 0 and "vision_embeds" in batch:
        x = x[:, cfg.vision_tokens :]  # logits over text positions only
    logits = logits_apply(params["embed"], params.get("lm_head"), x, cfg)
    return logits, aux


# ============================================================== caches
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, enc_len: int = 0, dtype=None):
    """Zeroed decode cache (use under ``jax.eval_shape`` for dry-runs)."""
    if dtype is None:
        dtype = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype else cfg.compute_jdtype()
    n_prefix, period, repeats = num_layers_in_stack(cfg)

    def one_layer(layer_idx: int):
        c: Dict[str, Any] = {}
        if cfg.layer_is_attn(layer_idx):
            if cfg.mla is not None:
                c["mixer"] = attn_mod.init_mla_cache(cfg, batch, max_len, dtype)
            else:
                c["mixer"] = attn_mod.init_gqa_cache(cfg, batch, max_len, dtype)
        else:
            c["mixer"] = mamba_mod.init_mamba_cache(cfg, batch, dtype)
        if cfg.encdec:
            hd = cfg.resolved_head_dim
            c["cross"] = {
                "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
            }
        return c

    cache: Dict[str, Any] = {
        f"prefix_{j}": one_layer(j) for j in range(n_prefix)
    }
    sb = {f"pos_{p}": one_layer(n_prefix + p) for p in range(period)}
    cache["blocks"] = jax.tree_util.tree_map(
        lambda a: jnp.zeros((repeats,) + a.shape, a.dtype), sb
    )
    return cache


@jax.named_scope("prefill")
def prefill(
    cfg: ModelConfig,
    params,
    batch: Dict[str, jax.Array],
    *,
    attn_impl: str = "auto",
):
    """Prefill: full forward that also returns the decode cache.

    Returns (last-position logits, cache).  The cache's attention entries
    hold exactly the prompt K/V (length = prompt length); the serving layer
    pads/copies them into its slot buffers.
    """
    enc_out = None
    if cfg.encdec:
        enc_out = _encode(cfg, params, batch["enc_embeds"], attn_impl)
    x, prefix_len = _assemble_input(cfg, params, batch)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    x, aux, (prefix_caches, stack_caches) = _run_stack(
        cfg, params, x, positions, prefix_len=prefix_len, attn_impl=attn_impl,
        ssd_impl="auto", enc_out=enc_out, collect_cache=True,
    )
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = logits_apply(params["embed"], params.get("lm_head"), x[:, -1:], cfg)[:, 0]
    cache = {f"prefix_{j}": c for j, c in enumerate(prefix_caches)}
    cache["blocks"] = stack_caches
    return logits, cache


@jax.named_scope("decode_step")
def decode_step(
    cfg: ModelConfig,
    params,
    cache,
    tokens: jax.Array,  # (B,) next input token ids
    pos: jax.Array,  # (B,) their positions (0-based)
    *,
    attn_impl: str = "auto",
):
    """One decode step for every sequence in the batch → (logits, new cache)."""
    x = embed_apply(params["embed"], tokens[:, None], cfg)[:, 0]
    if cfg.scale_embedding:
        pass  # scaling applied inside embed_apply
    n_prefix, period, repeats = num_layers_in_stack(cfg)

    def take(tree, layer):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), tree
        )

    def one_layer(lp, lc, x, layer=None):
        """One decoder layer.  With ``layer``, ``lc`` is the stacked cache of
        every repeat and ``layer`` the index of this one: attention writes
        its new row into the stack in place and reads its prefix there; an
        SSM mixer, whose whole state changes every step, is taken out and
        written back; cross caches are only read."""
        h = rmsnorm(lp["ln1"], x, cfg.rms_eps)
        if "attn" in lp:
            decode = attn_mod.mla_decode if cfg.mla is not None else attn_mod.gqa_decode
            out, new_mixer = decode(lp["attn"], h, cfg, lc["mixer"], pos, layer)
        elif layer is None:
            out, new_mixer = mamba_mod.mamba_decode(lp["ssm"], h, cfg, lc["mixer"])
        else:
            out, state = mamba_mod.mamba_decode(lp["ssm"], h, cfg, take(lc["mixer"], layer))
            new_mixer = jax.tree_util.tree_map(
                lambda buf, upd: jax.lax.dynamic_update_index_in_dim(buf, upd.astype(buf.dtype), layer, 0),
                lc["mixer"],
                state,
            )
        x = x + out
        new_cache = {"mixer": new_mixer}
        if "cross" in lp and "cross" in lc:
            hx = rmsnorm(lp["ln_x"], x, cfg.rms_eps)
            kv = lc["cross"] if layer is None else take(lc["cross"], layer)
            x = x + attn_mod.cross_attn_apply(lp["cross"], hx, cfg, kv)
            new_cache["cross"] = lc["cross"]
        if "moe" in lp:
            h2 = rmsnorm(lp["ln2"], x[:, None], cfg.rms_eps)
            out, _ = moe_mod.moe_apply(lp["moe"], h2, cfg, cfg.moe)
            x = x + out[:, 0]
        elif "ffn" in lp:
            h2 = rmsnorm(lp["ln2"], x, cfg.rms_eps)
            x = x + ffn_apply(lp["ffn"], h2, cfg.hidden_act)
        return x, new_cache

    new_prefix = {}
    for j in range(n_prefix):
        x, c = one_layer(params[f"prefix_{j}"], cache[f"prefix_{j}"], x)
        new_prefix[f"prefix_{j}"] = c

    if cfg.decode_loop == "scan":
        def body(x, scanned):
            lp, lc = scanned
            new_c = {}
            for p in range(period):
                x, c = one_layer(lp[f"pos_{p}"], lc[f"pos_{p}"], x)
                new_c[f"pos_{p}"] = c
            return x, new_c

        x, new_blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    else:
        # in-place loop: the stacked cache is the carry and each layer
        # updates its part of it where it lies — XLA keeps ONE cache buffer
        # (aliased with the donated input) instead of scan's xs/ys pair.
        def fbody(r, carry):
            x, blocks_cache = carry
            lp = take(params["blocks"], r)
            new_c = {}
            for p in range(period):
                x, new_c[f"pos_{p}"] = one_layer(lp[f"pos_{p}"], blocks_cache[f"pos_{p}"], x, r)
            return (x, new_c)

        x, new_blocks = jax.lax.fori_loop(0, repeats, fbody, (x, cache["blocks"]))
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = logits_apply(params["embed"], params.get("lm_head"), x[:, None], cfg)[:, 0]
    new_cache = dict(new_prefix)
    new_cache["blocks"] = new_blocks
    return logits, new_cache
