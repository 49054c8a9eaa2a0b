"""Reduction of a profiler trace to what the metrics read.

``extract`` turns the JAX profiler's ``.xplane.pb`` into a small neutral
form: the device operations (per device plane, the ``XLA Ops`` line) and
the benchmark's own host spans (``TraceAnnotation`` names starting with
``bench.``), all on the trace's one clock, in nanoseconds.  The functions
below work on that form only, so they are checked on a small recorded
trace in ``bench/tests/data``.
"""

from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .stats import gaps, union_length

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    #: device plane name -> its operations
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    #: the benchmark's host spans, names without the ``bench.`` prefix
    spans: List[Event] = field(default_factory=list)

    def to_json(self) -> Dict:
        return {"devices": self.devices, "spans": self.spans}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(e) for e in d["spans"]])


_OPCODE = re.compile(r"\s([a-z][a-z0-9_.-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """A short name for a device operation from its HLO text: the
    instruction's name, its result shape, its opcode and, for a custom
    call, its target (``%checkpoint.7 = bf16[1,32,1024,128] custom-call
    tpu_custom_call``)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    shape = "(tuple)" if rest.startswith("(") else rest.split("{", 1)[0].split(" ", 1)[0]
    op = _OPCODE.search(rest)
    target = _TARGET.search(rest)
    return " ".join([head, "=", shape, op.group(1) if op else "?"]
                    + ([target.group(1)] if target else []))


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def extract(xplane_path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices.setdefault(plane.name, []).extend(
                        (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append((ev.name[len(SPAN_PREFIX):], ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    tr.spans.sort(key=lambda e: e[1])
    return tr


def describe(xplane_path: str, top: int = 40) -> Dict:
    """Every plane and line with its event count and its most frequent
    event names, each with one event's stats: for looking at a trace by
    hand before writing code against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            by_name: Dict[str, List] = {}
            for ev in line.events:
                e = by_name.setdefault(ev.name, [0, 0.0, None, ev.start_ns])
                e[0] += 1
                e[1] += ev.duration_ns
                if e[2] is None:
                    e[2] = {k: str(v)[:300] for k, v in ev.stats}
            names = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
            out.append({"plane": plane.name, "line": line.name,
                        "events": sum(v[0] for v in by_name.values()),
                        "top": [{"name": n, "count": v[0], "total_ns": v[1],
                                 "first_start_ns": v[3], "stats": v[2]} for n, v in names]})
    return {"lines": out}


def span(tr: Trace, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) of the first host span called ``name``."""
    for n, s, e in tr.spans:
        if n == name:
            return s, e
    return None


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Union of device operation intervals in [lo, hi), averaged over devices."""
    if not tr.devices:
        return 0.0
    return sum(union_length(((s, e) for _, s, e in ops), lo, hi)
               for ops in tr.devices.values()) / len(tr.devices)


def idle_pct(run) -> Optional[float]:
    """The share of a run's traced window in which no operation ran on the
    device, or None where the run has no device trace."""
    if run.trace is None or not run.trace.devices or run.trace_window is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - busy_ns(run.trace, lo, hi) / (hi - lo))


def self_times(ops: List[Event]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self time) of every operation of one device line,
    where a loop or call that contains others keeps only the time no
    operation inside it covers."""
    out: List[List] = []
    stack: List[int] = []
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(e, out[stack[-1]][2]) - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10) -> List[List]:
    """The ``k`` device operations with the most self time in [lo, hi), in
    seconds, summed by name over devices and divided by their number."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in tr.devices.values():
        for n, s, e, own in self_times(ops):
            if lo <= s < hi:
                tot[n] += own / 1e9 / len(tr.devices)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_by_span(tr: Trace, lo: float, hi: float, k: int = 10) -> List[List]:
    """Idle time of the first device in [lo, hi), in seconds, summed by the
    innermost benchmark span that covers each gap's midpoint
    (``"none"`` where no span does); the ``k`` largest."""
    if not tr.devices:
        return []
    ops = next(iter(tr.devices.values()))
    spans = [sp for sp in tr.spans if sp[0] != "window"]
    tot: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps(((s, e) for _, s, e in ops), lo, hi):
        mid = (gs + ge) / 2
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "none"
        tot[label] += (ge - gs) / 1e9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def matching(tr: Trace, pattern: str, lo: float, hi: float) -> List[Event]:
    """Operations of every device whose name matches ``pattern``, starting in
    [lo, hi), in order of start."""
    rx = re.compile(pattern)
    return sorted((ev for ops in tr.devices.values() for ev in ops
                   if rx.search(ev[0]) and lo <= ev[1] < hi), key=lambda ev: ev[1])


def relabel(tr: Trace, name: str, labels: List[str]) -> None:
    """Rename the spans called ``name``, in order of start, to ``labels``."""
    it = iter(labels)
    for i, (n, s, e) in enumerate(tr.spans):
        if n == name:
            tr.spans[i] = (next(it, n), s, e)


def excerpt(tr: Trace, lo: float, hi: float) -> Trace:
    """The operations and spans that start in [lo, hi)."""
    return Trace({k: [e for e in v if lo <= e[1] < hi] for k, v in tr.devices.items()},
                 [e for e in tr.spans if lo <= e[1] < hi])


def dump(tr: Trace, path: Path) -> None:
    path.write_text(json.dumps(tr.to_json()))
