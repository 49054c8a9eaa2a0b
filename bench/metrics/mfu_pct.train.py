"""Whole step: training's model FLOP/s over the chip's bf16 peak.

The configuration's family module counts the model FLOPs per token
(``train_flops_per_token``), times the tokens per second of the window.
Recomputation does not count."""

#: what the reader calls in the configuration's family module
NEEDS = ("train_flops_per_token",)


def read(run):
    if not run.steps:
        return None
    tokens_per_s = sum(s.tokens for s in run.steps) / run.window_s
    return (100.0 * run.family.train_flops_per_token(run.config["model"]) * tokens_per_s
            / run.peaks.bf16_flops)
