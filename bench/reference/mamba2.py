"""Plain float32 reference for Mamba-2 language-model training (mamba2-130m).

Follows the published block (arXiv 2405.21060, section 7 and the
reference ``Mamba2`` module): pre-norm RMSNorm; projections to z, x, B, C
and dt; a causal depthwise convolution of width 4 and SiLU on x, B and C;
the SSD layer in its quadratic ("attention") form,

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} A dt_r) dt_s x_s + D x_t,

with A = -exp(A_log) and dt = softplus(x W_dt + dt_bias); the gated
RMSNorm ``norm(y * silu(z))``; the output projection and the residual.
Embeddings are tied to the output head.  Departures of the served program
that the reference shares, because they define the model being trained:
the convolution has no bias, and the projections are separate tensors
(the published ``in_proj`` split).  The loss is the token-mean cross
entropy plus ``z_loss`` times the mean squared log-partition, and the
optimizer is AdamW with global-norm clipping and linear warm-up, as the
configuration states.  Every matrix product runs in float32 at
``precision=HIGHEST``; nothing here imports the program under test.

The weights are made here, from the seed, in the program's layout (layers
stacked under ``blocks.pos_0``; a norm's weight stored as ``1 + scale``).

``precision="fp8"`` is the control: the same computation with both
operands of every matrix product rounded to float8 e4m3 after scaling to
its range (per tensor for weights, per row for activations), and the
gradients flowing back into them rounded to e5m2 the same way.

This module is also the one place that reads the family's published keys
for the harness: ``program_config`` maps the file onto the program's
``ModelConfig`` (the only function here that imports the program), and
``train_flops_per_token`` counts from those keys alone what
``mfu_pct.train`` reads.
"""

from __future__ import annotations

import functools
import math
from dataclasses import replace
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def program_config(conf: Dict):
    """The program's model configuration with every size the file states."""
    from repro.configs import SSMConfig, get_config

    m = conf["model"]
    return replace(
        get_config(conf["arch"]),
        n_layers=m["n_layer"], d_model=m["d_model"], vocab_size=m["vocab_size"],
        rms_eps=float(m["rms_norm_eps"]),
        ssm=SSMConfig(d_state=m["d_state"], expand=m["expand"], head_dim=m["headdim"],
                      n_groups=m["ngroups"], conv_width=m["d_conv"], chunk=m["chunk_size"],
                      dt_min=m["dt_min"], dt_max=m["dt_max"]),
        param_dtype=conf["train"]["param_dtype"], compute_dtype=conf["train"]["compute_dtype"],
        remat=conf["train"]["remat"],
    )


def matmul_params(m: Dict) -> int:
    """The projections of every Mamba-2 block and the tied output head."""
    d, P, N, G = m["d_model"], m["headdim"], m["d_state"], m["ngroups"]
    d_in = m["expand"] * d
    H = d_in // P
    per_layer = d * (2 * d_in + 2 * G * N + H) + d_in * d
    return per_layer * m["n_layer"] + m["vocab_size"] * d


def ssd_flops_per_token(m: Dict) -> float:
    """Forward, per token, all layers: C.B over the causal pairs of its
    chunk, their weighted sum of x, the chunk state's update and readout."""
    d, P, N, G, Q = m["d_model"], m["headdim"], m["d_state"], m["ngroups"], m["chunk_size"]
    H = m["expand"] * d // P
    pairs = (Q + 1) / 2
    return m["n_layer"] * (2 * pairs * N * G + 2 * pairs * P * H + 4 * N * P * H)


def train_flops_per_token(m: Dict) -> float:
    """Training's model FLOPs per token: 6 x the matrix-product parameters
    plus 3 x the SSD layer's own forward FLOPs in its chunked form;
    recomputation does not count."""
    return 6 * matmul_params(m) + 3 * ssd_flops_per_token(m)


def sizes(m: Dict) -> Dict[str, int]:
    d = m["d_model"]
    d_inner = m["expand"] * d
    V = m["vocab_size"]
    return {"L": m["n_layer"], "d": d, "H": d_inner // m["headdim"], "P": m["headdim"],
            "N": m["d_state"], "G": m["ngroups"], "W": m["d_conv"], "V": V,
            "Vp": -(-V // 128) * 128}


def make_weights(m: Dict, seed_key: jax.Array):
    """Every weight from one jitted call on the device, in float32.

    Matrices are N(0, initializer_range), the output projection divided by
    sqrt(n_layer) as the published init rescales it; A_log = log U[1, 16];
    dt_bias is softplus^-1 of a log-uniform dt in [dt_min, dt_max]; D = 1;
    convolution taps N(0, 1/width); norm weights 1 (scale 0)."""
    s = sizes(m)
    L, d, H, P, N, G, W = (s[k] for k in "L d H P N G W".split())
    std = float(m["initializer_range"])
    lo, hi = math.log(m["dt_min"]), math.log(m["dt_max"])

    @jax.jit
    def build(key):
        k = iter(jax.random.split(key, 16))
        nrm = lambda shape, sd: jax.random.normal(next(k), shape, jnp.float32) * sd
        dt = jnp.exp(jax.random.uniform(next(k), (L, H), minval=lo, maxval=hi))
        return {
            "embed": {"embedding": nrm((s["Vp"], d), std)},
            "final_norm": {"scale": jnp.zeros((d,))},
            "blocks": {"pos_0": {
                "ln1": {"scale": jnp.zeros((L, d))},
                "ssm": {
                    "w_z": nrm((L, d, H, P), std), "w_x": nrm((L, d, H, P), std),
                    "w_B": nrm((L, d, G, N), std), "w_C": nrm((L, d, G, N), std),
                    "w_dt": nrm((L, d, H), std),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.log(jax.random.uniform(next(k), (L, H), minval=1.0, maxval=16.0)),
                    "D": jnp.ones((L, H)),
                    "conv_x": nrm((L, W, H, P), W ** -0.5),
                    "conv_B": nrm((L, W, G, N), W ** -0.5),
                    "conv_C": nrm((L, W, G, N), W ** -0.5),
                    "gate_norm": {"scale": jnp.zeros((L, H * P))},
                    "out": nrm((L, H, P, d), std / math.sqrt(L)),
                },
            }},
        }

    return build(seed_key)


def _scaled_round(x, axis, dtype, top):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_fp8(x, axis):
    """An operand rounded to e4m3; its gradient rounded to e5m2, each scaled
    to its format's range first, as fp8 training does."""
    return _scaled_round(x, axis, _FP8, _FP8_MAX)


def _round_fp8_fwd(x, axis):
    return _round_fp8(x, axis), None


def _round_fp8_bwd(axis, _, g):
    return (_scaled_round(g, axis, jnp.float8_e5m2, 57344.0),)


_round_fp8.defvjp(_round_fp8_fwd, _round_fp8_bwd)


def _mm(spec, a, b, fp8, a_axis=-1):
    if fp8:
        a, b = _round_fp8(a, a_axis), _round_fp8(b, None)
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _conv(u, w):
    """Causal depthwise convolution over axis 0; tap W-1 is the current step."""
    W, S = w.shape[0], u.shape[0]
    ext = jnp.concatenate([jnp.zeros((W - 1,) + u.shape[1:], u.dtype), u], 0)
    return jax.nn.silu(sum(ext[i:i + S] * w[i] for i in range(W)))


def _layer(x, lw, eps, fp8):
    """One Mamba-2 block over a whole (S, d) sequence."""
    S = x.shape[0]
    p = lw["ssm"]
    h = _rmsnorm(x, lw["ln1"]["scale"], eps)
    z = _mm("sd,dhp->shp", h, p["w_z"], fp8)
    xs = _conv(_mm("sd,dhp->shp", h, p["w_x"], fp8), p["conv_x"])
    Bm = _conv(_mm("sd,dgn->sgn", h, p["w_B"], fp8), p["conv_B"])
    Cm = _conv(_mm("sd,dgn->sgn", h, p["w_C"], fp8), p["conv_C"])
    dt = jax.nn.softplus(_mm("sd,dh->sh", h, p["w_dt"], fp8) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    seg = jnp.cumsum(A * dt, axis=0)  # (S, H)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    decay = jnp.exp(jnp.where(causal[None], seg.T[:, :, None] - seg.T[:, None, :], -jnp.inf))
    H, G = xs.shape[1], Bm.shape[1]
    cb = _mm("tgn,sgn->gts", Cm, Bm, fp8)
    cb = jnp.repeat(cb, H // G, axis=0)  # (H, S, S): head h reads group h // (H / G)
    y = _mm("hts,shp->thp", cb * decay * dt.T[:, None, :], xs, fp8)
    y = y + p["D"][None, :, None] * xs
    g = (y * jax.nn.silu(z)).reshape(S, -1)
    g = _rmsnorm(g, p["gate_norm"]["scale"], eps).reshape(y.shape)
    return x + _mm("shp,hpd->sd", g, p["out"], fp8, a_axis=(1, 2))


def _row_loss(params, tokens, labels, *, eps, vocab, z_loss, fp8):
    """Sum over one row of cross entropy plus z_loss x lse^2."""
    x = params["embed"]["embedding"][tokens]
    layer = jax.checkpoint(functools.partial(_layer, eps=eps, fp8=fp8))
    x, _ = jax.lax.scan(lambda h, lw: (layer(h, lw), None), x, params["blocks"]["pos_0"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = _mm("sd,vd->sv", x, params["embed"]["embedding"][:vocab], fp8)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll + z_loss * lse * lse)


@functools.lru_cache(maxsize=None)
def _row_grad(eps, vocab, z_loss, fp8):
    f = functools.partial(_row_loss, eps=eps, vocab=vocab, z_loss=z_loss, fp8=fp8)
    return jax.jit(jax.value_and_grad(f))


def loss_and_grad(m: Dict, t: Dict, params, batch: Dict[str, np.ndarray],
                  precision: str = "float32") -> Tuple[float, Dict]:
    """The token-mean objective (cross entropy with z-loss) of the batch and
    its gradient, row by row, accumulated in float32."""
    fn = _row_grad(float(m["rms_norm_eps"]), int(m["vocab_size"]), float(t["z_loss"]),
                   precision == "fp8")
    B, S = batch["tokens"].shape
    grad = jax.tree_util.tree_map(jnp.zeros_like, params)
    total = 0.0
    for r in range(B):
        v, g = fn(params, jnp.asarray(batch["tokens"][r]), jnp.asarray(batch["labels"][r]))
        grad = jax.tree_util.tree_map(jnp.add, grad, g)
        total += float(v)
    return total / (B * S), jax.tree_util.tree_map(lambda g: g / (B * S), grad)


def _lr(step: int, t: Dict) -> float:
    """Linear warm-up to ``peak_lr`` over ``warmup_steps`` (steps counted
    from 0); the reference only takes steps inside the warm-up."""
    if step >= t["warmup_steps"]:
        raise ValueError("the reference follows the warm-up only")
    return t["peak_lr"] * min(1.0, (step + 1) / t["warmup_steps"])


def train(m: Dict, t: Dict, params, batches: List[Dict], precision: str = "float32"):
    """AdamW over ``batches`` from ``params``.  Returns the losses, the first
    step's clipped gradient, and the parameters after the last step."""
    b1, b2, eps, wd, clip = (t[k] for k in ("b1", "b2", "adam_eps", "weight_decay", "grad_clip"))
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    var = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for k, batch in enumerate(batches):
        loss, g = loss_and_grad(m, t, params, batch, precision)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(lambda x: x * jnp.minimum(1.0, clip / norm), g)
        if first is None:
            first = g
        lr, step = _lr(k, t), k + 1
        mom = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mom, g)
        var = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, var, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        params = jax.tree_util.tree_map(
            lambda p, a, v: p - lr * ((a / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
            params, mom, var)
        losses.append(loss)
    return losses, first, params
