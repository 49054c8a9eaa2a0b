"""Plain float32 reference for a LLaMA-style decoder (deepseek-llm-7b).

Follows the published architecture (arXiv 2401.02954, section 2.2): pre-norm
RMSNorm, rotary embeddings on the first and second halves of each head
(the HF ``rotate_half`` layout), causal multi-head attention with
``1/sqrt(head_dim)`` scaling, a SwiGLU feed-forward, a final RMSNorm and an
untied output head.  Every matrix product runs in float32 at
``precision=HIGHEST``; nothing here imports the program under test.

The weights are made here too, from the seed, in the layout the serving
program takes (stacked layers under ``blocks.pos_0``); a norm's weight is
stored as ``1 + scale``, so the published initial weight of 1 is a scale
of 0.  The reference reads the same arrays, upcast to float32.

``precision="fp8"`` is the control: the same computation with both
operands of every matrix product rounded to float8 e4m3, each tensor
scaled to the format's range first (per tensor for weights, per row for
activations), accumulated in float32.

This module is also the one place that reads the family's published keys
for the harness: ``program_config`` maps the file onto the program's
``ModelConfig`` (the only function here that imports the program), and
``cache_bytes_per_token``, ``prefill_flops``, ``decode_flops``,
``attention_flops``, ``attention_bytes`` and ``attention_calls`` count from
those keys alone what the checks and the metric readers compare.
"""

from __future__ import annotations

import functools
from dataclasses import replace
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def program_config(conf: Dict):
    """The program's model configuration with every size the file states."""
    from repro.configs import get_config

    m = conf["model"]
    return replace(
        get_config(conf["arch"]),
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim", 0), d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        rms_eps=float(m["rms_norm_eps"]), param_dtype=m["torch_dtype"],
        compute_dtype=m["torch_dtype"],
    )


def _head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def cache_bytes_per_token(m: Dict) -> int:
    """K and V of every layer for one token, in the served dtype."""
    return (2 * m["num_key_value_heads"] * _head_dim(m) * m["num_hidden_layers"]
            * jnp.dtype(m["torch_dtype"]).itemsize)


def layer_matmul_params(m: Dict) -> int:
    d, H, Hkv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    D = _head_dim(m)
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * m["intermediate_size"]


def prefill_flops(m: Dict, S: int) -> int:
    """Model FLOPs of an ``S``-token prefill: the matrix products of every
    layer, causal attention over the positions each token sees, and the
    output head at the last position only."""
    L, H = m["num_hidden_layers"], m["num_attention_heads"]
    D = _head_dim(m)
    return (2 * layer_matmul_params(m) * L * S + 2 * H * D * S * (S + 1) * L
            + 2 * m["hidden_size"] * m["vocab_size"])


def decode_flops(m: Dict, pos: int) -> int:
    """One token at position ``pos``, attending to ``pos + 1`` positions."""
    L, H = m["num_hidden_layers"], m["num_attention_heads"]
    D = _head_dim(m)
    return (2 * layer_matmul_params(m) * L + 4 * H * D * (pos + 1) * L
            + 2 * m["hidden_size"] * m["vocab_size"])


def attention_flops(m: Dict, S: int) -> int:
    """One attention kernel call at length ``S``: QK^T and PV over the
    causal triangle, diagonal included."""
    return 2 * m["num_attention_heads"] * _head_dim(m) * S * (S + 1)


def attention_bytes(m: Dict, S: int, itemsize: int = 2) -> int:
    """One attention kernel call at length ``S``: Q, K and V read once, O
    written once."""
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return itemsize * S * _head_dim(m) * (2 * H + 2 * Hkv)


def attention_calls(m: Dict) -> int:
    """Attention kernel calls per prefill: one per layer."""
    return m["num_hidden_layers"]


def make_weights(m: Dict, seed_key: jax.Array):
    """Every weight from one jitted call on the device, in ``m["dtype"]``.

    Matrices are N(0, initializer_range), as the published config's
    ``initializer_range`` states; norm weights start at 1 (scale 0)."""
    L, d, H = m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"]
    D = m.get("head_dim") or d // H
    Hkv, F, V = m["num_key_value_heads"], m["intermediate_size"], m["vocab_size"]
    dtype = jnp.dtype(m["torch_dtype"])
    std = float(m["initializer_range"])
    shapes = {
        "embed": (V, d), "lm_head": (d, V),
        "wq": (L, d, H, D), "wk": (L, d, Hkv, D), "wv": (L, d, Hkv, D), "wo": (L, H, D, d),
        "wi_gate": (L, d, F), "wi_up": (L, d, F), "w_down": (L, F, d),
    }

    @jax.jit
    def build(key):
        keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
        w = {n: (jax.random.normal(keys[n], s, dtype) * jnp.asarray(std, dtype))
             for n, s in shapes.items()}
        zeros = lambda *s: jnp.zeros(s, dtype)
        return {
            "embed": {"embedding": w["embed"]},
            "final_norm": {"scale": zeros(d)},
            "lm_head": {"w": w["lm_head"]},
            "blocks": {"pos_0": {
                "ln1": {"scale": zeros(L, d)},
                "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
                "ln2": {"scale": zeros(L, d)},
                "ffn": {"wi_gate": w["wi_gate"], "wi_up": w["wi_up"], "wo": w["w_down"]},
            }},
        }

    return build(seed_key)


def _round_fp8(x: jax.Array, axis) -> jax.Array:
    """``x`` rounded to float8 e4m3 after scaling its ``axis`` to the range."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, _FP8_MAX / amax, 1.0)
    return (x * scale).astype(_FP8).astype(jnp.float32) / scale


def _mm(spec: str, a: jax.Array, b: jax.Array, fp8: bool, a_axis=-1) -> jax.Array:
    if fp8:
        a = _round_fp8(a, a_axis)
        b = _round_fp8(b, None)
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    """(S, H, D) rotary embedding at positions 0..S-1, HF ``rotate_half``."""
    S, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _layer(x, blocks, i, *, eps, theta, fp8):
    """One decoder layer over a whole (S, d) float32 sequence."""
    lw = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32),
        blocks["pos_0"])
    S = x.shape[0]
    h = _rmsnorm(x, lw["ln1"]["scale"], eps)
    at = lw["attn"]
    q = _rope(_mm("sd,dhk->shk", h, at["wq"], fp8), theta)
    k = _rope(_mm("sd,dhk->shk", h, at["wk"], fp8), theta)
    v = _mm("sd,dhk->shk", h, at["wv"], fp8)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = _mm("qhk,shk->hqs", q, k, fp8) * (q.shape[-1] ** -0.5)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hqs,shk->qhk", p, v, fp8)
    x = x + _mm("qhk,hkd->qd", o, at["wo"], fp8, a_axis=(1, 2))
    h = _rmsnorm(x, lw["ln2"]["scale"], eps)
    f = lw["ffn"]
    g = _mm("sd,df->sf", h, f["wi_gate"], fp8)
    u = _mm("sd,df->sf", h, f["wi_up"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, f["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, rows, final_scale, w, *, eps, fp8):
    h = _rmsnorm(x[rows], final_scale.astype(jnp.float32), eps)
    return _mm("sd,dv->sv", h, w.astype(jnp.float32), fp8)


def logits_at(m: Dict, weights, tokens: np.ndarray, rows: Sequence[int], *,
              pad_to: int, rows_to: int, precision: str = "float32") -> np.ndarray:
    """Logits (len(rows), vocab) of the causal forward over ``tokens`` at
    positions ``rows``.  The sequence is padded at its end to ``pad_to``
    positions, which causal attention keeps from every kept position, and
    the rows to ``rows_to``, so that one compiled program serves every
    request."""
    fp8 = precision == "fp8"
    S, n = len(tokens), len(rows)
    if S > pad_to or n > rows_to:
        raise ValueError(f"{S} positions or {n} rows exceed {pad_to} / {rows_to}")
    toks = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, pad_to - S)))
    x = weights["embed"]["embedding"][toks].astype(jnp.float32)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    for i in range(m["num_hidden_layers"]):
        x = _layer(x, weights["blocks"], jnp.int32(i), eps=eps, theta=theta, fp8=fp8)
    idx = jnp.asarray(np.pad(np.asarray(rows, np.int32), (0, rows_to - n)))
    out = _head(x, idx, weights["final_norm"]["scale"], weights["lm_head"]["w"], eps=eps, fp8=fp8)
    return np.asarray(out)[:n]


def served_gaps(m: Dict, weights, prompt: np.ndarray, served: List[int], *,
                pad_to: int, rows_to: int, control: bool = False) -> Dict[str, float]:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position.  ``served`` are the program's greedy
    tokens; the reference runs once over ``prompt + served[:-1]``.  With
    ``control``, also the widest such gap of the token the fp8 control puts
    first at the same positions."""
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits_at(m, weights, seq, rows, pad_to=pad_to, rows_to=rows_to)
    best = ref.max(-1)
    idx = np.arange(len(rows))
    out = {"served": float((best - ref[idx, np.asarray(served)]).max()), "tokens": len(rows)}
    if control:
        ctl = logits_at(m, weights, seq, rows, pad_to=pad_to, rows_to=rows_to, precision="fp8")
        out["control"] = float((best - ref[idx, ctl.argmax(-1)]).max())
    return out
