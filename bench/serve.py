"""Serving cells: a model configuration behind ``repro.serve.Engine`` under
an open-loop traffic mix.

Set-up makes the weights on the device from the seed, builds the one
``Engine`` the window drives, and warms it up on every prompt length the
mix uses.  The window submits each request when it is due, steps the
engine whenever it has work, and records, on the host clock, when every
token became visible: a token is seen when the ``Engine.step`` that
produced it returns.  After the window closes the engine drains the
requests that were due in it, for at most ``DRAIN_S`` seconds; their
latencies count the wait.  Then the per-stream statistics and the served
tokens are checked against what the client saw and against the plain
reference.
"""

from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import traffic

DRAIN_S = 60.0


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclass
class ClientRequest:
    """One request as the client sees it (host clock, seconds)."""

    due: float
    prompt_len: int
    max_new_tokens: int
    tenant: str
    req: object = None  # the engine's Request
    submitted: float = 0.0
    admit_step_start: float = float("nan")
    token_times: List[float] = field(default_factory=list)


@dataclass
class StepSpan:
    start: float
    end: float
    admitted: int
    queued: int  # requests waiting for a slot when the step returned


@dataclass
class ServeWindow:
    t0: float
    t_end: float
    requests: List[ClientRequest]
    steps: List[StepSpan]
    drained_at: float


class ServeCell:
    #: what a serving cell calls in the configuration's family module
    NEEDS = ("program_config", "make_weights", "cache_bytes_per_token", "served_gaps")

    def __init__(self, conf: Dict, mix: Dict, family, seed: int, seconds: float) -> None:
        self.conf, self.mix, self.ref, self.seed = conf, mix, family, seed
        self.arrivals = traffic.schedule(mix, seed, seconds)
        self.prompts = traffic.prompt_tokens(seed, self.arrivals, conf["model"]["vocab_size"])
        self.seconds = seconds
        self.params = None
        self.eng = None

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax

        from repro.serve import Engine, ServeConfig

        t = time.perf_counter()
        self.make_weights()
        jax.block_until_ready(self.params)
        t_w = time.perf_counter()
        self.eng = Engine(self.ref.program_config(self.conf), self.params,
                          ServeConfig(**self.conf["serve"]))
        self.warmup()
        log(f"set-up: weights {t_w - t:.2f} s, engine and warm-up "
            f"{time.perf_counter() - t_w:.2f} s; {len(self.arrivals)} requests due in the window")

    def warmup(self) -> None:
        """Admit one request per prompt length the mix uses, with every slot
        filled at once, and decode: every program and eager operation the
        window runs is compiled here."""
        from jax.profiler import TraceAnnotation

        from repro.serve import Request

        rng = traffic.rng_for(self.seed, 4)
        vocab = self.conf["model"]["vocab_size"]
        n_slots = self.conf["serve"]["n_slots"]
        lens = sorted({a.prompt_len for a in self.arrivals})
        lens += [lens[-1]] * max(0, n_slots - len(lens))
        with TraceAnnotation("bench.warmup"):
            for n in lens:
                self.eng.submit(Request(prompt=rng.integers(0, vocab, n, dtype=np.int32),
                                        max_new_tokens=3, name=f"warmup{n}"))
            self.eng.run_until_idle()
        self.eng.drain_retired()

    # ------------------------------------------------------------- window
    def window(self, on_window_start: Optional[Callable[[], None]] = None) -> ServeWindow:
        from jax.profiler import TraceAnnotation

        from repro.serve import Request

        eng = self.eng
        clients = [ClientRequest(a.due_s, a.prompt_len, a.max_new_tokens, a.tenant)
                   for a in self.arrivals]
        steps: List[StepSpan] = []
        live: List[ClientRequest] = []

        def busy() -> bool:
            return bool(eng.queue) or any(s is not None for s in eng.slots)

        def step() -> None:
            start = time.perf_counter()
            with TraceAnnotation("bench.engine_step"):
                eng.step()
            end = time.perf_counter()
            admitted = 0
            with TraceAnnotation("bench.collect"):
                for c in live:
                    n = len(c.req.generated)
                    if n > len(c.token_times):
                        if not c.token_times:
                            c.admit_step_start = start
                            admitted += 1
                        c.token_times.extend([end] * (n - len(c.token_times)))
                live[:] = [c for c in live if not c.req.done]
            steps.append(StepSpan(start, end, admitted, len(eng.queue)))

        if on_window_start is not None:
            on_window_start()
        t0 = time.perf_counter()
        for c in clients:
            c.due += t0
        t_end = t0 + self.seconds
        i, n = 0, len(clients)
        with TraceAnnotation("bench.window"):
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                if i < n and clients[i].due <= now:
                    with TraceAnnotation("bench.submit"):
                        while i < n and clients[i].due <= now:
                            c = clients[i]
                            c.req = Request(prompt=self.prompts[i],
                                            max_new_tokens=c.max_new_tokens,
                                            name=f"w{i}", tenant=c.tenant)
                            c.submitted = time.perf_counter()
                            eng.submit(c.req)
                            live.append(c)
                            i += 1
                if busy():
                    step()
                else:
                    nxt = clients[i].due if i < n else t_end
                    with TraceAnnotation("bench.idle"):
                        time.sleep(max(0.0, min(nxt, t_end) - now))
        with TraceAnnotation("bench.drain"):
            while live and busy() and time.perf_counter() < t_end + DRAIN_S:
                step()
        return ServeWindow(t0, t_end, clients[:i], steps, time.perf_counter())

    # ------------------------------------------------------------- checks
    def check(self, w: ServeWindow, control: bool = False) -> Dict[str, Dict[str, float]]:
        """Every number compared, with its limit; the run is correct when no
        number exceeds its limit.  The engine is freed before the reference
        runs.  With ``control`` (calibration only) the engine is kept and
        ``self.readings`` also gets the fp8 control's reading."""
        m = self.conf["model"]
        per_tok = self.ref.cache_bytes_per_token(m)
        frame = self.eng.frame
        kv_bad = tok_bad = unfinished = 0
        for c in w.requests:
            r = c.req
            seen = len(c.token_times)
            if not r.done or r.status != "done" or seen != c.max_new_tokens:
                unfinished += 1
                continue
            sid = r.stream_id
            kv = int(frame.filter(stream=sid, access_type="KV_ACC_W").sum())
            kv_bad += kv != (c.prompt_len + seen - 1) * per_tok
            lane = int(frame.filter(stream=sid, access_type="SLO", outcome="TOKENS_OUT").sum())
            tok_bad += lane != seen or len(r.generated) != seen
        report = self.eng.per_stream_report()
        per_stream = sum(int(v["kv_bytes"]) for v in report.values())
        aggregate = int(frame.filter(access_type="KV_ACC_W").sum())
        checks = {
            "unfinished": {"value": unfinished, "limit": 0},
            "kv_bytes_mismatch": {"value": kv_bad, "limit": 0},
            "tokens_out_mismatch": {"value": tok_bad, "limit": 0},
            "stream_sum_minus_aggregate": {"value": abs(per_stream - aggregate), "limit": 0},
        }
        sample = self.sample(w)
        if not control:
            # the program's state goes before the reference runs
            self.eng.cache = None
            self.eng = None
            gc.collect()
        t = time.perf_counter()
        gap = ctl = 0.0
        tokens = 0
        for c in sample:
            g = self.ref.served_gaps(m, self.params, c.req.prompt, c.req.generated,
                                     pad_to=self.conf["serve"]["max_len"],
                                     rows_to=self.mix["output"]["max"], control=control)
            gap, tokens = max(gap, g["served"]), tokens + g["tokens"]
            ctl = max(ctl, g.get("control", 0.0))
        log(f"reference: {len(sample)} requests, {tokens} served tokens, "
            f"{time.perf_counter() - t:.2f} s")
        checks["logit_gap"] = {"value": gap, "limit": self.conf["limits"]["logit_gap"]}
        self.readings = {"logit_gap": gap}
        if control:
            self.readings["control.logit_gap"] = ctl
        return checks

    def compiled_memory(self) -> Dict[str, int]:
        """The engine runs many programs; ``memory_peak_bytes`` covers them."""
        return {}

    def log_window(self, w: ServeWindow) -> None:
        """The longest engine step and how late the client submitted, so
        that a stalled step or a starved client shows in the run's log."""
        if not w.steps:
            return
        s = max(w.steps, key=lambda s: s.end - s.start)
        late = max(c.submitted - c.due for c in w.requests)
        log(f"window: {len(w.steps)} engine steps, the longest {(s.end - s.start) * 1e3:.1f} ms "
            f"(admitting {s.admitted}) at {s.start - w.t0:.2f} s; a request submitted at most "
            f"{late * 1e3:.1f} ms after it was due")

    def label_spans(self, trace, w: ServeWindow) -> None:
        """Tag each traced engine step by whether it admitted a request."""
        from . import trace as tr

        tr.relabel(trace, "engine_step",
                   ["engine_step.admit" if s.admitted else "engine_step.decode" for s in w.steps])

    def outcomes(self, w: ServeWindow):
        """(requests due in the window, those that did not finish)."""
        failed = sum(1 for c in w.requests if not (c.req.done and c.req.status == "done"))
        return len(w.requests), failed

    def restart(self, seed: int) -> None:
        """Calibration only: new traffic and new weights from ``seed`` on the
        same engine, whose programs are compiled already."""
        self.seed = seed
        self.arrivals = traffic.schedule(self.mix, seed, self.seconds)
        self.prompts = traffic.prompt_tokens(seed, self.arrivals, self.conf["model"]["vocab_size"])
        self.eng.params = self.params = None
        gc.collect()
        self.eng.params = self.make_weights()

    def make_weights(self):
        """The weights of ``self.seed``, made on the device."""
        import jax

        key = jax.random.key(traffic.weights_seed(self.seed))
        self.params = self.ref.make_weights(self.conf["model"], key)
        return self.params

    def sample(self, w: ServeWindow) -> List[ClientRequest]:
        """Finished window requests for the reference, drawn from the seed:
        the one with the most served tokens and the one with the longest
        prompt, then others at random among those with at least the median
        number of served tokens, until ``limits.sample_tokens`` tokens."""
        done = [c for c in w.requests if c.req.done and c.req.status == "done"]
        if not done:
            return []
        n_out = [len(c.req.generated) for c in done]
        med = float(np.median(n_out))
        rng = traffic.rng_for(self.seed, 2)
        first = [max(range(len(done)), key=lambda k: (n_out[k], done[k].prompt_len)),
                 max(range(len(done)), key=lambda k: (done[k].prompt_len, n_out[k]))]
        rest = [int(k) for k in rng.permutation(len(done)) if n_out[k] >= med]
        picked, total = [], 0
        for k in first + rest:
            if k in picked:
                continue
            picked.append(k)
            total += n_out[k]
            if total >= self.conf["limits"]["sample_tokens"]:
                break
        return [done[k] for k in picked]
