"""Runtime-side per-stream telemetry for *real* JAX training/serving loops.

The simulator (``repro.sim``) tracks cycle-level stats; this module is the
same idea applied to the live runtime: every jitted step executed by the
framework is attributed to a :class:`~repro.core.stream.Stream`, and the
quantities we *can* measure on a real host are recorded per stream:

* wall-clock start/end of each step  (``gpu_kernel_time`` analog, §3.2),
* tokens / samples processed,
* HLO FLOPs and HBM bytes of the compiled step (``compiled.cost_analysis()``),
* collective bytes of the compiled step (parsed from the lowered HLO),
* loss / custom scalar metrics.

Beside the per-stream records sits the program's one span recorder,
:data:`SPANS`: a bounded ring of named host intervals on the same clock as
:class:`StepRecord` (``time.perf_counter_ns``), each with its parent span,
its stream and a few integer counters, and each also entered as a
``jax.profiler.TraceAnnotation`` so that a profiler trace shows it on the
device trace's clock.

The per-(type,outcome) *cache* matrix is a simulator-only concept — real TPUs
do not expose per-stream cache counters (that is precisely why the paper
instruments a simulator) — but byte/FLOP attribution per stream is real and
is what production observability needs.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, IO, Iterable, Iterator, List, NamedTuple, Optional, Tuple

import sys

from .query import StatsFrame
from .sinks import Report, ReportSink, TextSink, stream_report
from .stats import DEFAULT_STREAM, StatTable, AccessType, AccessOutcome
from .timeline import KernelTimeline

__all__ = [
    "SPANS", "OpenSpan", "SpanLog", "SpanRecord", "SpanWindow", "StepRecord", "StepCost",
    "StreamStats", "current_span", "current_stream", "stream_scope",
]


_tls = threading.local()


def current_stream() -> int:
    """The stream id active in this thread (default stream if none set)."""
    return getattr(_tls, "stream_id", DEFAULT_STREAM)


@contextlib.contextmanager
def stream_scope(stream_id: int) -> Iterator[int]:
    """Attribute all instrumented work in this scope to ``stream_id``."""
    prev = getattr(_tls, "stream_id", DEFAULT_STREAM)
    _tls.stream_id = stream_id
    try:
        yield stream_id
    finally:
        _tls.stream_id = prev


def current_span() -> int:
    """The id of the span open around this code in this thread, or -1."""
    stack = getattr(_tls, "spans", None)
    return stack[-1] if stack else -1


def _span_stack() -> List[int]:
    stack = getattr(_tls, "spans", None)
    if stack is None:
        stack = _tls.spans = []
    return stack


_trace_me: Any = None


def _annotation(name: str, stream: int, counters: Dict[str, int]):
    """A profiler annotation for one span, entered, with its stream and
    counters: a step annotation (the profiler's step view) when the span
    counts a ``step_num``.  It does nothing while no profiler runs."""
    global _trace_me
    if _trace_me is None:
        from jax.profiler import TraceAnnotation

        _trace_me = TraceAnnotation
    meta = dict(counters, stream=stream) if stream >= 0 else counters
    tm = _trace_me(name, _r=1, **meta) if "step_num" in counters else _trace_me(name, **meta)
    tm.__enter__()
    return tm


class SpanRecord(NamedTuple):
    """One finished span.  ``parent`` is the id of the span open around it
    in its thread when it began (-1 for none); ``stream`` the stream of the
    request it belongs to (-1 for engine-wide work)."""

    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int
    stream: int
    counters: Dict[str, int]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanWindow(NamedTuple):
    #: the spans that start in the window, in order of start
    spans: List[SpanRecord]
    #: spans the ring overwrote that may reach into the window: every span
    #: overwritten so far if the latest-ending of them ends at or after the
    #: window's start, else 0
    dropped: int


class OpenSpan:
    """A span being timed.  As a context manager it nests: it is the parent
    of the spans begun inside it in the same thread.  Begun with
    :meth:`SpanLog.begin` it does not nest and ends with :meth:`end`, in
    any later call (the queue wait of a request)."""

    __slots__ = ("log", "name", "stream", "counters", "id", "parent", "start_ns", "end_ns",
                 "_tm", "_stack")

    def __init__(self, log: "SpanLog", name: str, stream: int, counters: Dict[str, int]) -> None:
        self.log, self.name, self.stream, self.counters = log, name, stream, counters
        self.end_ns = -1

    def _begin(self, nested: bool) -> "OpenSpan":
        stack = _span_stack()
        self.parent = stack[-1] if stack else -1
        self.id = next(self.log._ids)
        self._stack = stack if nested else None
        if nested:
            stack.append(self.id)
        self._tm = _annotation(self.name, self.stream, self.counters)
        self.start_ns = time.perf_counter_ns()
        return self

    def __enter__(self) -> "OpenSpan":
        return self._begin(nested=True)

    def __exit__(self, *exc) -> None:
        self.end()

    def count(self, **counters: int) -> None:
        """Counters known only inside the span, kept with it at its end."""
        self.counters.update(counters)
        self._tm.set_metadata(**counters)

    def end(self, at_ns: Optional[int] = None) -> None:
        """Ends the span now, or at ``at_ns`` (a stamp read for another span:
        one boundary, one clock read).  A second call does nothing."""
        if self.end_ns >= 0:
            return
        self.end_ns = time.perf_counter_ns() if at_ns is None else at_ns
        self._tm.__exit__(None, None, None)
        if self._stack is not None:
            self._stack.pop()
        self.log._record((self.name, self.start_ns, self.end_ns, self.id, self.parent,
                          self.stream, self.counters))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanLog:
    """The process's bounded log of program spans: a ring of ``capacity``
    finished spans, the oldest overwritten first and counted as dropped, so
    its memory stays constant however long the program runs (as
    :meth:`StreamStats.retire_stream` keeps the per-stream records bounded).
    Always on: a span costs two clock reads, one tuple into the ring and a
    profiler annotation that does nothing while no profiler runs."""

    def __init__(self, capacity: int = 1 << 16) -> None:
        self.capacity = capacity
        self._ring: List[Optional[tuple]] = [None] * capacity
        self._written = 0
        self._lost_end_ns = -1
        self._ids = itertools.count(1)
        self._lock = threading.Lock()

    def span(self, name: str, stream: int = -1, **counters: int) -> OpenSpan:
        """``with SPANS.span("engine.decode", active=8) as sp:`` times the
        block as a child of the span open around it."""
        return OpenSpan(self, name, stream, counters)

    def begin(self, name: str, stream: int = -1, **counters: int) -> OpenSpan:
        """A span begun now that does not nest; :meth:`OpenSpan.end` ends it."""
        return OpenSpan(self, name, stream, counters)._begin(nested=False)

    def _record(self, rec: tuple) -> None:
        with self._lock:
            slot = self._written % self.capacity
            old = self._ring[slot]
            if old is not None and old[2] > self._lost_end_ns:
                self._lost_end_ns = old[2]
            self._ring[slot] = rec
            self._written += 1

    @property
    def dropped(self) -> int:
        """Spans overwritten since the log began."""
        return max(0, self._written - self.capacity)

    def read(self, lo_ns: int, hi_ns: int) -> SpanWindow:
        """The spans whose start falls in ``[lo_ns, hi_ns)``, and the drops
        that may reach into that window."""
        with self._lock:
            ring = list(self._ring)
            dropped = self.dropped if self._lost_end_ns >= lo_ns else 0
        spans = [SpanRecord._make(r) for r in ring if r is not None and lo_ns <= r[1] < hi_ns]
        spans.sort(key=lambda r: r.start_ns)
        return SpanWindow(spans, dropped)


#: the one recorder every program span goes through
SPANS = SpanLog()


@dataclass(frozen=True)
class StepCost:
    """Static per-execution costs of a compiled step function."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0


@dataclass
class StepRecord:
    uid: int
    stream_id: int
    name: str
    t_start_ns: int
    t_end_ns: int = -1
    #: the id of the program span (:data:`SPANS`) open when the record began
    parent: int = -1
    tokens: int = 0
    samples: int = 0
    metrics: Dict[str, float] = field(default_factory=dict)
    cost: StepCost = field(default_factory=StepCost)

    @property
    def seconds(self) -> float:
        if self.t_end_ns < 0:
            raise ValueError("step not finished")
        return (self.t_end_ns - self.t_start_ns) * 1e-9


class StreamStats:
    """Per-stream aggregation of live step records.

    Also maintains a :class:`StatTable` in *byte units* (GLOBAL/ICI rows) so
    live telemetry and simulator output share one report format, and a
    :class:`KernelTimeline` in nanoseconds so the paper's §3.2 per-kernel
    launch/exit tracking exists on the real runtime too.

    **Bounded memory** (docs/DESIGN.md §5.12): a long-running engine calls
    :meth:`retire_stream` when a stream's work is finished, which folds that
    stream's :class:`StepRecord` list into a small per-stream aggregate (and
    drops its timeline intervals).  :meth:`summary` / :meth:`streams` /
    :meth:`reports` answer identically before and after the fold — proven
    by an equality test — so the live state is O(live streams' records)
    plus one constant-size aggregate per retired stream, instead of one
    record per step per request forever.
    """

    def __init__(self) -> None:
        self.table = StatTable(name="Runtime_stats")
        self.timeline = KernelTimeline()
        self.records: List[StepRecord] = []
        #: stream id → folded sums of its retired records (see retire_stream)
        self._agg: Dict[int, Dict[str, float]] = {}
        self._uid = 0
        self._open: Dict[int, StepRecord] = {}
        self._lock = threading.Lock()

    # -- step lifecycle ---------------------------------------------------------
    def step_begin(self, name: str, stream_id: Optional[int] = None, *,
                   t_ns: Optional[int] = None, parent: Optional[int] = None) -> int:
        """Opens a record now, or at ``t_ns``; its parent is the span open
        here unless ``parent`` names another."""
        sid = current_stream() if stream_id is None else stream_id
        with self._lock:
            self._uid += 1
            uid = self._uid
        rec = StepRecord(uid=uid, stream_id=sid, name=name,
                         t_start_ns=time.perf_counter_ns() if t_ns is None else t_ns,
                         parent=current_span() if parent is None else parent)
        with self._lock:
            self._open[uid] = rec
        self.timeline.on_launch(sid, uid, rec.t_start_ns, name)
        return uid

    def step_end(
        self,
        uid: int,
        *,
        tokens: int = 0,
        samples: int = 0,
        cost: Optional[StepCost] = None,
        t_ns: Optional[int] = None,
        **metrics: float,
    ) -> StepRecord:
        with self._lock:
            rec = self._open.pop(uid)
        rec.t_end_ns = time.perf_counter_ns() if t_ns is None else t_ns
        rec.tokens = tokens
        rec.samples = samples
        rec.metrics.update(metrics)
        if cost is not None:
            rec.cost = cost
            # Mirror into the shared stat-table format (byte-granularity rows).
            self.table.inc_stats(AccessType.GLOBAL_ACC_R, AccessOutcome.MISS, rec.stream_id, int(cost.hbm_bytes))
            if cost.collective_bytes:
                self.table.inc_stats(AccessType.ICI_SND, AccessOutcome.MISS, rec.stream_id, int(cost.collective_bytes))
        self.timeline.on_done(rec.stream_id, uid, rec.t_end_ns)
        with self._lock:
            self.records.append(rec)
        return rec

    def land(self, name: str, span: OpenSpan, stream_id: Optional[int] = None,
             **end_kwargs) -> StepRecord:
        """Records a step that one program span timed: its start, end and
        parent are the span's, so the step's boundaries are read once."""
        uid = self.step_begin(name, span.stream if stream_id is None else stream_id,
                              t_ns=span.start_ns, parent=span.id)
        return self.step_end(uid, t_ns=span.end_ns, **end_kwargs)

    @contextlib.contextmanager
    def step(self, name: str, stream_id: Optional[int] = None, **end_kwargs):
        uid = self.step_begin(name, stream_id)
        try:
            yield uid
        finally:
            self.step_end(uid, **end_kwargs)

    # -- retirement (bounded memory) ----------------------------------------------
    def retire_stream(self, stream_id: int, *, drop_timeline: bool = True) -> int:
        """Fold every record of one finished stream into its per-stream
        aggregate and forget the records (plus, by default, the stream's
        timeline intervals).  Returns the number of records folded.

        Summaries are unchanged by construction: the fold computes exactly
        the sums :meth:`summary` would have computed over the same records
        in the same order, so ``summary(sid)`` before and after the fold is
        equal, float-for-float.  Call this once a stream can receive no more
        steps — e.g. the serving engine calls it when a request retires —
        and a million-request run holds one record per *live* step plus one
        small dict per retired stream, instead of every step ever."""
        with self._lock:
            mine = [r for r in self.records if r.stream_id == stream_id]
            if mine:
                self.records = [r for r in self.records if r.stream_id != stream_id]
            agg = self._agg.get(stream_id)
            if agg is None:
                agg = self._agg[stream_id] = {
                    "steps": 0, "seconds": 0.0, "tokens": 0, "flops": 0.0,
                    "hbm_bytes": 0.0, "collective_bytes": 0.0,
                }
            if mine:
                agg["steps"] += len(mine)
                agg["seconds"] += sum(r.seconds for r in mine)
                agg["tokens"] += sum(r.tokens for r in mine)
                agg["flops"] += sum(r.cost.flops for r in mine)
                agg["hbm_bytes"] += sum(r.cost.hbm_bytes for r in mine)
                agg["collective_bytes"] += sum(r.cost.collective_bytes for r in mine)
        if drop_timeline:
            self.timeline.drop_stream(stream_id)
        return len(mine)

    # -- per-stream summaries -----------------------------------------------------
    def streams(self) -> Tuple[int, ...]:
        return tuple(sorted(set(self._agg) | {r.stream_id for r in self.records}))

    def summary(self, stream_id: int) -> Dict[str, float]:
        rs = [r for r in self.records if r.stream_id == stream_id]
        agg = self._agg.get(stream_id)
        if not rs and agg is None:
            return {"steps": 0}
        steps = (agg["steps"] if agg else 0) + len(rs)
        if steps == 0:
            return {"steps": 0}
        secs = agg["seconds"] if agg else 0.0
        toks = agg["tokens"] if agg else 0
        flops = agg["flops"] if agg else 0.0
        hbm = agg["hbm_bytes"] if agg else 0.0
        coll = agg["collective_bytes"] if agg else 0.0
        if rs:
            secs += sum(r.seconds for r in rs)
            toks += sum(r.tokens for r in rs)
            flops += sum(r.cost.flops for r in rs)
            hbm += sum(r.cost.hbm_bytes for r in rs)
            coll += sum(r.cost.collective_bytes for r in rs)
        return {
            "steps": steps,
            "seconds": secs,
            "tokens": toks,
            "tokens_per_s": toks / secs if secs > 0 else 0.0,
            "flops": flops,
            "flops_per_s": flops / secs if secs > 0 else 0.0,
            "hbm_bytes": hbm,
            "collective_bytes": coll,
        }

    def frame(self) -> StatsFrame:
        """The byte-attribution table + wall-clock timeline as a query frame
        (``stats.frame().filter(stream=train_stream, access_type="ICI_SND")
        .sum()`` — live-runtime collective bytes per stream)."""
        return StatsFrame(self.table, timeline=self.timeline)

    # -- reporting (sink subsystem; see repro.core.sinks) -------------------------
    def reports(self, source: str = "runtime") -> "list[Report]":
        """One :class:`Report` per stream — the summary line plus the
        byte-attribution block (a StatsFrame selection), consumable by any
        sink."""
        frame = self.frame()
        out = []
        for sid in self.streams():
            s = self.summary(sid)
            header = (
                f"stream {sid}: steps={s['steps']} tokens={s.get('tokens', 0)} "
                f"time={s.get('seconds', 0.0):.3f}s "
                f"tok/s={s.get('tokens_per_s', 0.0):.1f} "
                f"TFLOP/s={s.get('flops_per_s', 0.0) / 1e12:.3f}\n"
            )
            out.append(
                stream_report(
                    frame,
                    sid,
                    source=source,
                    event="stream_summary",
                    cache_name="Runtime_bytes",
                    header=header,
                    fields={k: v for k, v in s.items()},
                )
            )
        return out

    def emit(self, sinks: "Iterable[ReportSink]", source: str = "runtime") -> int:
        """Push every stream's summary report through the given sinks."""
        reports = self.reports(source)
        for sink in sinks:
            for rep in reports:
                sink.emit(rep)
        return len(reports)

    def print_summary(self, fout: IO[str] = sys.stdout) -> None:
        sink = TextSink(fout)
        for rep in self.reports():
            sink.emit(rep)
