"""Program spans: the bounded ring in ``repro.core.instrument``, the spans
``Engine.step`` and ``Trainer.run`` record on it, and the step records
that hang under them."""

import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core import instrument
from repro.core.instrument import SPANS, SpanLog, StreamStats, current_span
from repro.models import init_params, model_defs
from repro.serve import Engine, Request, ServeConfig


def _names(spans):
    return [s.name for s in spans]


# ------------------------------------------------------------------ the ring
class TestSpanLog:
    def test_capacity_bounds_the_ring_and_counts_drops(self):
        log = SpanLog(capacity=4)
        lo = time.perf_counter_ns()
        for k in range(6):
            with log.span(f"s{k}"):
                pass
        assert log.dropped == 2
        got = log.read(0, 2**62)
        assert _names(got.spans) == ["s2", "s3", "s4", "s5"]
        # the overwritten spans reach into a window that starts before them
        assert got.dropped == 2 and log.read(lo, 2**62).dropped == 2
        # and not into one that starts after the last of them ended
        s2 = got.spans[0]
        assert log.read(s2.start_ns, 2**62) == (got.spans, 0)

    def test_read_keeps_spans_that_start_in_the_window(self):
        log = SpanLog(capacity=8)
        for k in range(3):
            with log.span(f"s{k}"):
                pass
        spans = log.read(0, 2**62).spans
        mid = log.read(spans[1].start_ns, spans[2].start_ns)
        assert _names(mid.spans) == ["s1"] and mid.dropped == 0
        assert all(a.start_ns <= b.start_ns for a, b in zip(spans, spans[1:]))

    def test_parents_follow_nesting_and_stay_in_their_thread(self):
        log = SpanLog()
        seen = {}

        def other():
            assert current_span() == -1
            with log.span("other") as o:
                with log.span("other.child") as c:
                    seen["other"] = (o.parent, c.parent, o.id)

        with log.span("outer") as outer:
            with log.span("inner", 5) as inner:
                assert current_span() == inner.id
                t = threading.Thread(target=other)
                t.start()
                t.join()
            assert current_span() == outer.id
        assert current_span() == -1
        by = {s.name: s for s in log.read(0, 2**62).spans}
        assert by["outer"].parent == -1
        assert by["inner"].parent == by["outer"].id
        assert by["other"].parent == -1 and seen["other"] == (-1, by["other"].id, by["other"].id)
        assert by["other.child"].parent == by["other"].id
        assert by["inner"].stream == 5 and by["outer"].stream == -1
        assert len({s.id for s in by.values()}) == 4

    def test_threads_lose_no_span_and_keep_their_own_parents(self):
        log = SpanLog(capacity=1 << 14)
        n_threads, per = 2 * (os.cpu_count() or 4), 200
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)

        def work(k):
            for _ in range(per):
                with log.span("outer", k):
                    with log.span("inner", k):
                        pass

        try:
            threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        spans = log.read(0, 2**62).spans
        assert len(spans) == 2 * n_threads * per and log.dropped == 0
        assert len({s.id for s in spans}) == len(spans)
        outer = {s.id: s.stream for s in spans if s.name == "outer"}
        assert all(s.parent == -1 for s in spans if s.name == "outer")
        assert all(outer[s.parent] == s.stream for s in spans if s.name == "inner")

    def test_a_begun_span_does_not_nest_and_ends_once(self):
        log = SpanLog()
        q = log.begin("queued", 3)
        with log.span("step") as st:
            assert st.parent == -1  # the begun span is nobody's parent
            q.end(st.start_ns)
            q.end()
        (queued,) = [s for s in log.read(0, 2**62).spans if s.name == "queued"]
        assert queued.end_ns == st.start_ns and queued.stream == 3
        assert len(log.read(0, 2**62).spans) == 2

    def test_counters_given_at_either_boundary(self):
        log = SpanLog()
        with log.span("engine.step", queued=4) as sp:
            sp.count(active=2)
        (s,) = log.read(0, 2**62).spans
        assert s.counters == {"queued": 4, "active": 2}
        assert s.seconds == (s.end_ns - s.start_ns) * 1e-9 >= 0

    def test_each_span_enters_a_profiler_annotation_with_its_name(self, monkeypatch):
        events = []

        class Annotation:
            def __init__(self, name, **kw):
                self.name, self.kw = name, kw

            def __enter__(self):
                events.append(("enter", self.name, self.kw))

            def __exit__(self, *exc):
                events.append(("exit", self.name))

            def set_metadata(self, **kw):
                events.append(("meta", self.name, kw))

        monkeypatch.setattr(instrument, "_trace_me", Annotation)
        log = SpanLog()
        with log.span("engine.step", queued=1) as sp:
            with log.span("engine.prefill", 9, prompt_tokens=5):
                pass
            sp.count(active=1)
        with log.span("train.step", step_num=3):
            pass
        assert events == [
            ("enter", "engine.step", {"queued": 1}),
            ("enter", "engine.prefill", {"prompt_tokens": 5, "stream": 9}),
            ("exit", "engine.prefill"),
            ("meta", "engine.step", {"active": 1}),
            ("exit", "engine.step"),
            # the profiler's step annotation
            ("enter", "train.step", {"_r": 1, "step_num": 3}),
            ("exit", "train.step"),
        ]

    def test_spans_reach_a_profiler_trace(self, tmp_path):
        log = SpanLog()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with log.span("test.outer", 4, active=2):
                with log.span("test.inner"):
                    time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
        found = {ev.name: dict(ev.stats) for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for ev in line.events if ev.name.startswith("test.")}
        assert found["test.outer"] == {"active": 2, "stream": 4}
        assert "test.inner" in found

    def test_step_records_name_their_parent_span(self):
        st = StreamStats()
        log = SpanLog()
        with log.span("train.step") as sp:
            uid = st.step_begin("train_step", 1)
        rec = st.step_end(uid, tokens=4)
        assert rec.parent == sp.id
        # a record landed from a span takes its stamps and its id
        landed = st.land("decode", sp, 2, tokens=1)
        assert (landed.t_start_ns, landed.t_end_ns, landed.parent, landed.stream_id) == (
            sp.start_ns, sp.end_ns, sp.id, 2)
        assert st.step_end(st.step_begin("alone", 1)).parent == -1


# ------------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("deepseek-7b")
    return cfg, init_params(model_defs(cfg), jax.random.PRNGKey(3), cfg.param_jdtype())


def _one_step(small_model, max_new_tokens):
    cfg, params = small_model
    eng = Engine(cfg, params, ServeConfig(n_slots=2, max_len=64))
    req = Request(prompt=np.arange(1, 8, dtype=np.int32), max_new_tokens=max_new_tokens)
    eng.step()  # an idle step compiles nothing; the window below starts clean
    lo = time.perf_counter_ns()
    eng.submit(req)
    eng.step()
    got = SPANS.read(lo, time.perf_counter_ns())
    assert got.dropped == 0
    return eng, req, got.spans


def _children(spans, parent):
    return [s for s in spans if s.parent == parent.id]


class TestEngineSpans:
    def test_one_step_records_its_tree(self, small_model):
        eng, req, spans = _one_step(small_model, max_new_tokens=2)
        (step,) = [s for s in spans if s.name == "engine.step"]
        assert step.parent == -1 and step.counters == {"queued": 1, "active": 1}
        assert _names(_children(spans, step)) == ["engine.admit", "engine.decode", "engine.stats"]
        admit, decode, landing = _children(spans, step)
        assert admit.counters == {"admitted": 1}
        assert _names(_children(spans, admit)) == ["engine.prefill", "engine.stats",
                                                   "engine.place"]
        assert decode.counters == {"active": 1, "bucket": 2}
        assert landing.counters == {"records": 1}
        # the request finished inside the decode's stats landing
        assert _names(_children(spans, landing)) == ["engine.finish"]
        sid = req.stream_id
        per_request = {s.name: s for s in spans if s.stream == sid}
        assert set(per_request) == {"engine.queued", "engine.prefill", "engine.place",
                                    "engine.finish"}
        assert req.done and len(req.generated) == 2

    def test_request_times_are_its_spans(self, small_model):
        eng, req, spans = _one_step(small_model, max_new_tokens=3)
        by = {s.name: s for s in spans}
        queued, prefill, decode = by["engine.queued"], by["engine.prefill"], by["engine.decode"]
        assert queued.parent == -1 and queued.end_ns == prefill.start_ns
        assert req.prefill_s == prefill.seconds
        assert req.ttft_s == (prefill.end_ns - queued.start_ns) * 1e-9
        assert req.submitted_s == queued.start_ns * 1e-9
        assert req.decode_s == decode.seconds  # one active slot takes the whole step
        assert prefill.counters == {"prompt_tokens": 7}
        recs = {r.name: r for r in eng.stats.records if r.stream_id == req.stream_id}
        assert (recs["prefill"].parent, recs["prefill"].t_start_ns) == (prefill.id,
                                                                        prefill.start_ns)
        assert (recs["decode"].parent, recs["decode"].t_end_ns) == (decode.id, decode.end_ns)

    def test_a_request_shed_from_the_queue_ends_its_wait_at_its_finish(self, small_model):
        cfg, params = small_model
        eng = Engine(cfg, params, ServeConfig(n_slots=1, max_len=64, max_live=1))
        lo = time.perf_counter_ns()
        first, second = (Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
                         for _ in range(2))
        eng.submit(first)
        eng.submit(second)  # over max_live: shed at once, never prefilled
        spans = SPANS.read(lo, time.perf_counter_ns()).spans
        fin = [s for s in spans if s.name == "engine.finish" and s.stream == second.stream_id]
        queued = [s for s in spans if s.name == "engine.queued" and s.stream == second.stream_id]
        assert second.status == "shed" and len(fin) == len(queued) == 1
        assert queued[0].end_ns == fin[0].start_ns


# ------------------------------------------------------------------ the trainer
def test_trainer_steps_record_batch_step_and_report():
    from repro.train.trainer import TrainConfig, Trainer

    cfg = get_smoke_config("mamba2-130m")
    rng = np.random.default_rng(0)

    def feed():
        while True:
            t = rng.integers(0, cfg.vocab_size, (2, 17), dtype=np.int32)
            yield {"tokens": t[:, :-1], "labels": t[:, 1:]}

    tr = Trainer(cfg, TrainConfig(), feed())
    params, opt = tr.restore_or_init()
    lo = time.perf_counter_ns()
    tr.run(params, opt, 2)
    spans = SPANS.read(lo, time.perf_counter_ns()).spans
    assert _names(spans) == ["train.batch", "train.step", "train.batch", "train.step",
                             "train.report"]
    steps = [s for s in spans if s.name == "train.step"]
    assert [s.counters["step_num"] for s in steps] == [0, 1]
    assert all(s.stream == tr.train_stream for s in steps)
    assert [r.parent for r in tr.stats.records] == [s.id for s in steps]
