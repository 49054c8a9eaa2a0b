"""Whole step: the model FLOPs of every prompt and output token the engine
processed inside the window, over (window x the chip's bf16 peak).

The configuration's family module counts the FLOPs from its sizes
(``prefill_flops`` of a prompt, ``decode_flops`` of each output token
after the first).  Work done outside the window, and recomputation, do
not count."""

#: what the reader calls in the configuration's family module
NEEDS = ("prefill_flops", "decode_flops")


def read(run):
    m, f = run.config["model"], run.family
    flops = 0
    for c in run.requests:
        for j, t in enumerate(c.token_times):
            if t > run.t_end:
                break
            flops += f.prefill_flops(m, c.prompt_len) if j == 0 else f.decode_flops(m, c.prompt_len + j - 1)
    if not flops:
        return None
    return 100.0 * flops / run.window_s / run.peaks.bf16_flops
