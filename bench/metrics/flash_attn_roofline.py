"""Kernel: the Pallas flash-attention kernel's share of its roofline.

For every call, the least time the chip could take for what causal
attention needs at that call's true sequence length S (not the kernel's
padded blocks): the larger of its FLOPs over the bf16 peak and its bytes
over the HBM peak.  The share is their sum over the kernel's measured time
in the device trace.  Each prefill calls the kernel once per layer at its
prompt length, so the calls are the trace's kernel events, matched in
count to the prefills the traced window ran."""

from bench import trace as tr

#: how the kernel's operation is named in the device trace: the prefill holds
#: one Pallas call, a custom call to ``tpu_custom_call`` (``bench.trace``
#: keeps the target in the operation's short name)
KERNEL = r"tpu_custom_call$"


def flops(m, S):
    """QK^T and PV over the causal triangle, diagonal included."""
    H = m["num_attention_heads"]
    D = m.get("head_dim") or m["hidden_size"] // H
    return 2 * H * D * S * (S + 1)


def bytes_moved(m, S, itemsize=2):
    """Q, K and V read once, O written once."""
    H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    D = m.get("head_dim") or m["hidden_size"] // H
    return itemsize * S * D * (2 * H + 2 * Hkv)


def least_time(m, S, peaks):
    return max(flops(m, S) / peaks.bf16_flops, bytes_moved(m, S) / peaks.hbm_bytes)


def read(run):
    if run.trace is None:
        return None
    m = run.config["model"]
    events = tr.matching(run.trace, KERNEL, float("-inf"), float("inf"))
    prefills = [c.prompt_len for c in run.requests if c.token_times]
    calls = [S for S in prefills for _ in range(m["num_hidden_layers"])]
    if not events or len(events) != len(calls):
        return None
    measured = sum(e - s for _, s, e in events) / 1e9
    return 100.0 * sum(least_time(m, S, run.peaks) for S in calls) / measured
