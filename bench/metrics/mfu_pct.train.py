"""Whole step: training's model FLOP/s over the chip's bf16 peak.

Per token, 6 x the matrix-product parameters (the projections of every
Mamba-2 block and the tied output head over the vocabulary) plus 3 x the
SSD layer's own forward FLOPs in its chunked form (intra-chunk causal
pairs, chunk states and their readout), times the tokens per second of the
window.  Recomputation does not count."""


def matmul_params(m):
    d, P, N, G = m["d_model"], m["headdim"], m["d_state"], m["ngroups"]
    d_in = m["expand"] * d
    H = d_in // P
    per_layer = d * (2 * d_in + 2 * G * N + H) + d_in * d
    return per_layer * m["n_layer"] + m["vocab_size"] * d


def ssd_flops_per_token(m):
    """Forward, per token, all layers: C.B over the causal pairs of its
    chunk, their weighted sum of x, the chunk state's update and readout."""
    d, P, N, G, Q = m["d_model"], m["headdim"], m["d_state"], m["ngroups"], m["chunk_size"]
    H = m["expand"] * d // P
    pairs = (Q + 1) / 2
    return m["n_layer"] * (2 * pairs * N * G + 2 * pairs * P * H + 4 * N * P * H)


def flops_per_token(m):
    return 6 * matmul_params(m) + 3 * ssd_flops_per_token(m)


def read(run):
    if not run.steps:
        return None
    tokens_per_s = sum(s.tokens for s in run.steps) / run.window_s
    return 100.0 * flops_per_token(run.config["model"]) * tokens_per_s / run.peaks.bf16_flops
