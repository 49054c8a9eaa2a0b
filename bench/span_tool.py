"""Read one cell's run through the program's own spans.

    python3 -m bench.span_tool --workload <cell> --seed <n> --seconds <s> --out <dir>

Runs the cell once with ``--trace 1`` as ``bench.run`` does, keeps the
profiler's output under ``<dir>/raw``, and writes ``<dir>/spans.json``:

- ``clock``: the trace's clock minus the host's, from the engine or
  training steps both clocks saw (median, quartiles, extremes), and how far
  the ring's span starts, mapped by it, lie from the same spans' profiler
  annotations in the kept trace;
- ``idle_by_span``: the first device's idle time in the window by the
  innermost program span the host was in (the gap's midpoint), from the
  kept trace's annotations;
- ``spans_per_step``, the ``longest_steps`` with the time of each child
  span, and the ``kernel`` operations ``flash_attn_roofline`` matches;
- ``cost``: ns per empty span, with the profiler off and on, over
  ``--cost-loops`` spans on a fresh ring.

The last line of standard output is the run's result line.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

from bench import run as R
from bench import spans
from bench import trace as tr
from bench import traffic
from bench.stats import gaps

PROGRAM = ("engine.", "train.")


def annotations(xplane_path: str) -> Dict[str, List]:
    """The program's spans as the profiler saw them: name -> [(start, end)]
    on the trace's clock, in order of start."""
    from jax.profiler import ProfileData

    out: Dict[str, List] = defaultdict(list)
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM):
                        out[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def quartiles(v: List[float]) -> Dict[str, float]:
    q = statistics.quantiles(v, n=4) if len(v) > 1 else [v[0]] * 3
    return {"n": len(v), "min": min(v), "q1": q[0], "median": q[1], "q3": q[2], "max": max(v)}


def offsets(run) -> List[float]:
    """Per step, the traced benchmark span's start minus its host start, ns."""
    found = spans.step_offsets(run)
    if found is not None:
        return found
    traced = [s for n, s, _ in run.trace.spans if n == "train_step"]
    return [t - s.start * 1e9 for t, s in zip(traced, run.steps)]


def agreement(ring: List, seen: Dict[str, List], offset: float) -> Dict:
    """How far each ring span's mapped start lies from the start of the
    nearest annotation of the same name: quartiles, the 99th percentile,
    the spans more than 0.1 ms off, and the five farthest."""
    d = []
    for s in ring:
        starts = [a for a, _ in seen.get(s.name, [])]
        if not starts:
            continue
        k = bisect.bisect_left(starts, s.start_ns + offset)
        near = [starts[j] for j in (k - 1, k) if 0 <= j < len(starts)]
        d.append((min(abs(s.start_ns + offset - a) for a in near), s.name))
    if not d:
        return {"matched": 0, "of": len(ring)}
    ns = sorted(x for x, _ in d)
    return {"matched": len(d), "of": len(ring), "ns": quartiles(ns),
            "p99_ns": ns[int(0.99 * (len(ns) - 1))],
            "over_100us": sum(1 for x in ns if x > 1e5), "farthest": sorted(d)[-5:]}


def idle_by_span(run, seen: Dict[str, List]) -> Dict[str, List[float]]:
    """Idle time of the first device in the traced window by the innermost
    program span around each gap's midpoint (the queue wait is no host
    work, and is left out): [seconds, % of the window]."""
    if not run.trace.devices:
        return {}
    lo, hi = run.trace_window
    ops = next(iter(run.trace.devices.values()))
    ivs = sorted((a, b, b - a, n) for n, v in seen.items() if n != "engine.queued"
                 for a, b in v)
    starts = [a for a, _, _, _ in ivs]
    longest = max((w for _, _, w, _ in ivs), default=0)
    tot: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps(((s, e) for _, s, e in ops), lo, hi):
        mid = (gs + ge) / 2
        k = bisect.bisect_right(starts, mid)
        j = bisect.bisect_left(starts, mid - longest)
        inside = [iv for iv in ivs[j:k] if iv[1] > mid]
        tot[min(inside, key=lambda iv: iv[2])[3] if inside else "outside"] += ge - gs
    return {n: [t / 1e9, 100.0 * t / (hi - lo)]
            for n, t in sorted(tot.items(), key=lambda kv: -kv[1])}


def longest_steps(ring: List, name: str, k: int = 5) -> List[Dict]:
    """The ``k`` longest ``name`` spans, each with its children's time by name."""
    kids: Dict[int, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for s in ring:
        kids[s.parent][s.name] += (s.end_ns - s.start_ns) / 1e6
    top = sorted((s for s in ring if s.name == name), key=lambda s: s.start_ns - s.end_ns)[:k]
    return [{"ms": (s.end_ns - s.start_ns) / 1e6, "counters": s.counters,
             "children_ms": dict(kids[s.id])} for s in top]


def cost(loops: int) -> Dict[str, float]:
    """ns per empty span on a fresh ring, the profiler off and then on."""
    import jax

    from repro.core.instrument import SpanLog

    def per_span() -> float:
        log = SpanLog()
        t = time.perf_counter_ns()
        for _ in range(loops):
            with log.span("cost"):
                pass
        return (time.perf_counter_ns() - t) / loops

    t = time.perf_counter_ns()
    for _ in range(loops):
        pass
    loop = (time.perf_counter_ns() - t) / loops
    off = per_span()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        on = per_span()
        jax.profiler.stop_trace()
    return {"loops": loops, "off_ns": off, "on_ns": on, "empty_loop_ns": loop}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cost-loops", type=int, default=100_000)
    args = ap.parse_args(argv)
    out = Path(args.out)
    raw = out / "raw"
    raw.mkdir(parents=True, exist_ok=True)
    manifest = R.load_manifest()
    cell = R.find_cell(manifest, args.workload)
    kept = {}
    result = R.run_cell(cell, R.load_config(cell["config"]), traffic.load_mix(cell["traffic"]),
                        R.metrics_for(manifest, cell["name"], True), args.seed, args.seconds,
                        True, trace_dir=str(raw), on_record=lambda run: kept.update(run=run))
    run = kept["run"]
    ring = spans.window_spans(run) or []
    seen = annotations(tr.find_xplane(str(raw)))
    off = offsets(run)
    offset = statistics.median(off)
    step_ms = [s.seconds * 1e3 for s in ring if s.name in ("engine.step", "train.step")]
    steps = len(step_ms)
    calls = getattr(run.family, "attention_calls", lambda m: 0)(run.config["model"])
    kernels = tr.matching(run.trace, r"tpu_custom_call$", float("-inf"), float("inf"))
    report = {
        "workload": args.workload, "seed": args.seed,
        "clock": {"offset_ns": quartiles(off),
                  "ring_vs_annotations": agreement(ring, seen, offset)},
        "idle_by_span": idle_by_span(run, seen),
        "spans_in_window": len(ring), "steps": steps,
        "spans_per_step": len(ring) / steps if steps else None,
        "step_ms": quartiles(step_ms) if step_ms else None,
        "spans_by_name": {n: sum(1 for s in ring if s.name == n)
                          for n in sorted({s.name for s in ring})},
        "longest_steps": longest_steps(ring, "engine.step" if "engine.step" in seen
                                       else "train.step"),
        "kernel": {"events": len(kernels), "names": sorted({n for n, _, _ in kernels}),
                   "prefills_x_calls": calls * sum(1 for c in run.requests if c.token_times)},
        "cost": cost(args.cost_loops),
        "result": result,
    }
    (out / "spans.json").write_text(json.dumps(report, indent=1))
    print(json.dumps({k: v for k, v in report.items() if k not in ("result", "spans_by_name")}),
          file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
