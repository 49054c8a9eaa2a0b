"""Model step, prefill: the program's own prefill time of every admitted
window request (``Request.prefill_s``, host clock around prefill and the
first token's sync), over their prompt tokens, in microseconds."""


def read(run):
    done = [c for c in run.requests if c.token_times]
    tokens = sum(c.prompt_len for c in done)
    return sum(c.req.prefill_s for c in done) / tokens * 1e6 if tokens else None
