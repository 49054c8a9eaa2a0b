"""A family module for the host tests only, never a benchmark cell: a
DeepSeek-V2-style decoder with multi-head latent attention (MLA), its keys
named as in the published DeepSeek-V2-Lite config, and with no experts, so
that every layer's feed-forward is dense.

It shows that the harness takes a new family as one module with the
functions its kind and its metrics call, and no edit elsewhere.  Its
``served_gaps`` is **not** an independent reference: it runs the program's
own float32 ``forward`` at ``HIGHEST`` precision over the prompt and the
served tokens, and so tests the harness's plumbing and the engine's
prefill-then-decode path through the latent cache, not the model.  Its
weights are the program's own initialisation.  It has no control.
"""

from __future__ import annotations

import functools
from dataclasses import replace
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "deepseek-v2-lite-16b"


def _config(m: Dict):
    from repro.configs import MLAConfig, get_config

    return replace(
        get_config(ARCH),
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), rms_eps=float(m["rms_norm_eps"]),
        mla=MLAConfig(kv_lora_rank=m["kv_lora_rank"], q_lora_rank=m["q_lora_rank"] or 0,
                      qk_nope_dim=m["qk_nope_head_dim"], qk_rope_dim=m["qk_rope_head_dim"],
                      v_head_dim=m["v_head_dim"]),
        moe=None, param_dtype=m["torch_dtype"], compute_dtype=m["torch_dtype"], remat="none",
    )


def program_config(conf: Dict):
    """The program's model configuration with every size the file states."""
    return _config(conf["model"])


def cache_bytes_per_token(m: Dict) -> int:
    """The latent and the shared rope key of every attention layer for one
    token, in the served dtype; every layer attends."""
    return ((m["kv_lora_rank"] + m["qk_rope_head_dim"]) * m["num_hidden_layers"]
            * jnp.dtype(m["torch_dtype"]).itemsize)


def make_weights(m: Dict, seed_key: jax.Array):
    """The program's own initialisation, from the seed."""
    from repro.models.params import init_params
    from repro.models.transformer import model_defs

    cfg = _config(m)
    return jax.jit(lambda k: init_params(model_defs(cfg), k, cfg.param_jdtype()))(seed_key)


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    from repro.models.transformer import forward

    def logits(weights, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(cfg, weights, {"tokens": tokens[None]}, attn_impl="xla")[0][0]

    return jax.jit(logits)


def served_gaps(m: Dict, weights, prompt: np.ndarray, served: List[int], *,
                pad_to: int, rows_to: int, control: bool = False) -> Dict[str, float]:
    """Widest gap by which a served token's logit lies below the best at its
    position, in one causal forward of the program over ``prompt +
    served[:-1]``, padded at its end to ``pad_to``.  ``rows_to`` is unused:
    the forward reads every row."""
    if control:
        raise ValueError("the test-only MLA family has no control")
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
    if len(seq) > pad_to:
        raise ValueError(f"{len(seq)} positions exceed {pad_to}")
    toks = jnp.asarray(np.pad(seq, (0, pad_to - len(seq))))
    rows = np.arange(len(prompt) - 1, len(seq))
    out = np.asarray(_forward(_config(m))(weights, toks), np.float32)[rows]
    gap = out.max(-1) - out[np.arange(len(rows)), np.asarray(served)]
    return {"served": float(gap.max()), "tokens": len(rows)}
