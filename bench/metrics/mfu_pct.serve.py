"""Whole step: the model FLOPs of every prompt and output token the engine
processed inside the window, over (window x the chip's bf16 peak).

Counted for a LLaMA-style decoder from its sizes: the matrix products of
every layer, causal attention over the positions each token sees, and the
output head once per emitted token (prefill computes it for the last
position only).  Work done outside the window, and recomputation, do not
count."""


def layer_matmul_params(m):
    d, H, Hkv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    D = m.get("head_dim") or d // H
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * m["intermediate_size"]


def prefill_flops(m, S):
    L, H = m["num_hidden_layers"], m["num_attention_heads"]
    D = m.get("head_dim") or m["hidden_size"] // H
    return (2 * layer_matmul_params(m) * L * S + 2 * H * D * S * (S + 1) * L
            + 2 * m["hidden_size"] * m["vocab_size"])


def decode_flops(m, pos):
    """One token at position ``pos``, attending to ``pos + 1`` positions."""
    L, H = m["num_hidden_layers"], m["num_attention_heads"]
    D = m.get("head_dim") or m["hidden_size"] // H
    return (2 * layer_matmul_params(m) * L + 4 * H * D * (pos + 1) * L
            + 2 * m["hidden_size"] * m["vocab_size"])


def read(run):
    m = run.config["model"]
    flops = 0
    for c in run.requests:
        for j, t in enumerate(c.token_times):
            if t > run.t_end:
                break
            flops += prefill_flops(m, c.prompt_len) if j == 0 else decode_flops(m, c.prompt_len + j - 1)
    if not flops:
        return None
    return 100.0 * flops / run.window_s / run.peaks.bf16_flops
