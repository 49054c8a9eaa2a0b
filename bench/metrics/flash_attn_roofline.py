"""Kernel: the Pallas flash-attention kernel's share of its roofline.

For every call, the least time the chip could take for what causal
attention needs at that call's true sequence length S (not the kernel's
padded blocks): the larger of its FLOPs over the bf16 peak and its bytes
over the HBM peak, both counted by the configuration's family module
(``attention_flops``, ``attention_bytes``).  The share is their sum over
the kernel's measured time in the device trace.  Each prefill calls the
kernel ``attention_calls`` times at its prompt length, so the calls are the
trace's kernel events, matched in count to the prefills the traced window
ran."""

from bench import trace as tr

#: how the kernel's operation is named in the device trace: the prefill holds
#: one Pallas call, a custom call to ``tpu_custom_call`` (``bench.trace``
#: keeps the target in the operation's short name)
KERNEL = r"tpu_custom_call$"

#: what the reader calls in the configuration's family module
NEEDS = ("attention_flops", "attention_bytes", "attention_calls")


def least_time(family, m, S, peaks):
    return max(family.attention_flops(m, S) / peaks.bf16_flops,
               family.attention_bytes(m, S) / peaks.hbm_bytes)


def read(run):
    if run.trace is None:
        return None
    m, f = run.config["model"], run.family
    events = tr.matching(run.trace, KERNEL, float("-inf"), float("inf"))
    prefills = [c.prompt_len for c in run.requests if c.token_times]
    calls = [S for S in prefills for _ in range(f.attention_calls(m))]
    if not events or len(events) != len(calls):
        return None
    measured = sum(e - s for _, s, e in events) / 1e9
    return 100.0 * sum(least_time(f, m, S, run.peaks) for S in calls) / measured
