"""The readers of the program's own spans (``bench/spans.py`` and the metrics
that read it) on a synthetic run: spans in a ring on the host clock, a
device trace on a clock offset from it by a known amount, and each case
in which a reader finds nothing to read."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import run as R
from bench import spans
from bench import trace as tr
from bench.peaks import PEAKS
from bench.record import RunRecord
from bench.serve import ClientRequest, StepSpan

from repro.core import instrument
from repro.core.instrument import SpanLog

S = 1_000_000_000  # ns per second
T0, T_END = 100.0, 101.0  # the window on the host clock, seconds
OFFSET = 7_300_000_123.0  # the trace's clock minus the host's, ns
JITTER = [0, 200, 100, 50]  # each traced step's start after its host start, ns
STEPS = [(100.1, 100.2), (100.3, 100.4), (100.5, 100.6), (100.7, 100.8)]
NEW = ("admit_wait_p95_ms", "decode_step_ms", "stats_landing_us_per_step", "engine_idle_pct")


def ns(t: float) -> int:
    return round(t * S)


def trace_ns(t: float) -> float:
    return t * S + OFFSET


def program_ring(capacity: int = 64) -> SpanLog:
    """Per step an ``engine.step`` holding a 20 ms ``engine.decode`` and a
    1 ms ``engine.stats``; requests 11 and 12 waited 30 and 60 ms, and a
    request from before the window (stream 99) waited 900 ms."""
    log = SpanLog(capacity)
    rid = iter(range(1, 1000))

    def add(name, a, b, parent=-1, stream=-1):
        i = next(rid)
        log._record((name, a, b, i, parent, stream, {}))
        return i

    add("engine.queued", ns(99.0), ns(99.9), stream=99)
    add("engine.queued", ns(100.05), ns(100.08), stream=11)
    add("engine.queued", ns(100.24), ns(100.30), stream=12)
    for a, b in STEPS:
        step = add("engine.step", ns(a) + 1000, ns(b) - 1000)
        add("engine.decode", ns(a) + 2000, ns(a) + 2000 + 20_000_000, parent=step)
        add("engine.stats", ns(a) + 30_000_000, ns(a) + 31_000_000, parent=step)
    return log


def device_trace() -> tr.Trace:
    """Busy except for two gaps: 100.15-100.25 (half inside the first step)
    and 100.52-100.54 (inside the third)."""
    ops = [("op", trace_ns(a), trace_ns(b))
           for a, b in [(T0, 100.15), (100.25, 100.52), (100.54, T_END)]]
    steps = [("engine_step.decode", trace_ns(a) + j, trace_ns(b) + j)
             for (a, b), j in zip(STEPS, JITTER)]
    return tr.Trace({"/device:TPU:0": ops}, [("window", trace_ns(T0), trace_ns(T_END))] + steps)


def synthetic_run(trace: bool = True) -> RunRecord:
    clients = []
    for sid in (11, 12):
        c = ClientRequest(due=T0, prompt_len=256, max_new_tokens=2, tenant="chat")
        c.req = SimpleNamespace(stream_id=sid)
        clients.append(c)
    t = device_trace() if trace else None
    return RunRecord(cell={}, config={}, mix={}, seconds=T_END - T0, setup_s=1.0,
                     peaks=PEAKS["TPU v5 lite"], t0=T0, t_end=T_END, requests=clients,
                     steps=[StepSpan(a, b, 0, 0) for a, b in STEPS], trace=t,
                     trace_window=tr.span(t, "window") if t else None)


@pytest.fixture
def ring(monkeypatch):
    log = program_ring()
    monkeypatch.setattr(instrument, "SPANS", log)
    return log


def test_the_trace_clock_is_found_from_the_steps(ring):
    run = synthetic_run()
    assert spans.step_offsets(run) == pytest.approx([OFFSET + j for j in JITTER], abs=1e-3)
    assert spans.clock_offset(run) == pytest.approx(OFFSET + 75, abs=1e-3)
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == 12


def test_admit_wait_reads_the_window_requests_queue_waits(ring):
    # 30 and 60 ms; the request of stream 99 is not a window request
    assert R.read_metric("admit_wait_p95_ms", synthetic_run()) == pytest.approx(60.0)


def test_decode_step_is_the_median_decode_span(ring):
    assert R.read_metric("decode_step_ms", synthetic_run()) == pytest.approx(20.0)


def test_stats_landing_is_per_engine_step(ring):
    assert R.read_metric("stats_landing_us_per_step", synthetic_run()) == pytest.approx(1000.0)


def test_engine_idle_is_the_device_idle_inside_engine_steps(ring):
    run = synthetic_run()
    # 50 ms of the first gap and all 20 ms of the second fall inside steps
    assert R.read_metric("engine_idle_pct", run) == pytest.approx(7.0, abs=1e-4)
    # all idle time, inside steps or not, is the device's idle share
    assert R.read_metric("device_idle_pct.serve", run) == pytest.approx(12.0)


def test_without_a_trace_only_engine_idle_is_missing(ring):
    run = synthetic_run(trace=False)
    assert R.read_metric("engine_idle_pct", run) is None
    assert R.read_metric("decode_step_ms", run) == pytest.approx(20.0)


def test_engine_idle_needs_the_same_steps_on_both_clocks(ring):
    run = synthetic_run()
    run.steps = run.steps[:-1]
    assert spans.clock_offset(run) is None
    assert R.read_metric("engine_idle_pct", run) is None


def test_a_ring_that_dropped_window_spans_reads_nothing(monkeypatch):
    log = program_ring(capacity=8)  # 15 spans: seven overwritten, some in the window
    monkeypatch.setattr(instrument, "SPANS", log)
    assert log.dropped == 7
    for name in NEW:
        assert R.read_metric(name, synthetic_run()) is None, name


def test_drops_before_the_window_do_not_count(monkeypatch):
    log = SpanLog(capacity=16)
    for k in range(20):  # overwritten spans all end before the window
        log._record(("engine.step", ns(50.0) + k, ns(50.0) + k + 1, k + 1, -1, -1, {}))
    for rec in program_ring().read(0, 2**62).spans[1:9]:
        log._record(tuple(rec))
    monkeypatch.setattr(instrument, "SPANS", log)
    assert log.dropped > 0
    assert R.read_metric("decode_step_ms", synthetic_run()) == pytest.approx(20.0)


def test_a_window_without_the_spans_reads_nothing(monkeypatch):
    log = SpanLog()
    log._record(("engine.step", ns(10.0), ns(10.1), 1, -1, -1, {}))
    monkeypatch.setattr(instrument, "SPANS", log)
    for name in NEW:
        assert R.read_metric(name, synthetic_run()) is None, name


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    monkeypatch.delattr(instrument, "SPANS")
    for name in NEW:
        assert R.read_metric(name, synthetic_run()) is None, name
