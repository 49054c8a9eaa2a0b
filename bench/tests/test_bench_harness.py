"""The benchmark's yardstick on the host: manifest, traffic, statistics,
metric readers, FLOP and byte counters, and the trace reduction (checked on
a small trace recorded on a TPU v5e)."""

from __future__ import annotations

import gc
import json
import math
import re
import time
import types
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run as R
from bench import stats, traffic
from bench import trace as tr
from bench.peaks import PEAKS, peaks_for
from bench.record import RunRecord
from bench.serve import ClientRequest

DATA = Path(__file__).resolve().parent / "data"
V5E = PEAKS["TPU v5 lite"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ------------------------------------------------------------------ manifest
def test_manifest_names_every_file_it_needs():
    man = R.load_manifest()
    assert man["command"][:3] == ["python3", "-m", "bench.run"]
    assert man["paths"] == ["bench"]
    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        conf = R.load_config(c["name"])
        assert Path(R.ROOT / c["file"]) == R.BENCH_DIR / "configs" / f"{c['name']}.json"
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert (R.BENCH_DIR / "reference" / f"{conf['reference']}.py").is_file()
    for cell in man["workloads"]:
        assert cell["config"] in configs and cell["chips"] in (1, 4)
        traffic.load_mix(cell["traffic"])
        for trace in (False, True):
            assert R.metrics_for(man, cell["name"], trace), (cell["name"], trace)
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert callable(R.load_module(R.BENCH_DIR / "metrics" / f"{m['name']}.py").read)
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    for m in man["per_layer"]:
        assert m["moves"] in e2e
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
}


def test_manifest_keeps_to_the_schema():
    man = R.load_manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    seen = set()
    for group, keys in KEYS.items():
        for e in man[group]:
            assert set(e) <= keys, (group, e["name"])
            assert e["name"] not in seen
            seen.add(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"])
    for e in man["end_to_end"] + man["per_layer"]:
        assert e["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for e in man["end_to_end"]:
        assert e["source"] in ("device_trace", "host_clock")
    cells = {c["name"] for c in man["workloads"]}
    for e in man["end_to_end"] + man["per_layer"]:
        assert set(e.get("workloads", cells)) <= cells


def test_unknown_names_are_errors():
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no_such_mix")
    with pytest.raises(KeyError):
        R.find_cell(R.load_manifest(), "no-such-cell")
    with pytest.raises(KeyError):
        peaks_for("TPU v0 imaginary")
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12


def test_no_chip_exits_before_model_work(capsys):
    # the host has no TPU: exit code 2 and nothing on standard output
    rc = R.main(["--workload", "ds7b-prefill-burst", "--seed", "1", "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""


# ------------------------------------------------------------------ traffic
@pytest.fixture
def mix():
    return traffic.load_mix("prefill_burst")


def test_traffic_is_the_same_for_a_seed_and_on_the_grid(mix):
    a = traffic.schedule(mix, 3_000_000_007, 30)
    assert a == traffic.schedule(mix, 3_000_000_007, 30)
    assert all(x.prompt_len % 128 == 0 and 256 <= x.prompt_len <= 1152 for x in a)
    assert all(8 <= x.max_new_tokens <= 64 for x in a)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    assert all(0 <= x.due_s < 30 for x in a)
    p = traffic.prompt_tokens(3_000_000_007, a, 102400)
    assert all(len(t) == x.prompt_len for t, x in zip(p, a))
    assert all(int(t.max()) < 102400 for t in p)


def test_every_seed_gets_the_same_work_in_another_order(mix):
    P = mix["burst"]["period_s"]

    def periods(s):
        out = {}
        for x in s:
            out.setdefault(int(x.due_s // P), []).append(
                (round(x.due_s % P, 9), x.prompt_len, x.max_new_tokens, x.tenant))
        return out

    a, b = periods(traffic.schedule(mix, 1, 50)), periods(traffic.schedule(mix, 2, 50))
    # the same five periods, each whole, in another order
    assert sorted(map(tuple, a.values())) == sorted(map(tuple, b.values()))
    assert [a[k] for k in range(5)] != [b[k] for k in range(5)]
    # arrivals are Poisson: over many periods the burst runs at three times
    # the base rate, and the mean rate is the mix's
    s = traffic.schedule(mix, 1, 100 * P)
    burst = sum(1 for x in s if 3 <= x.due_s % P < 5) / (100 * 2)
    base = sum(1 for x in s if not 3 <= x.due_s % P < 5) / (100 * 8)
    assert 2.6 < burst / base < 3.4
    assert len(s) / (100 * P) == pytest.approx(mix["rate_per_s"], rel=0.05)
    assert len({x.prompt_len for x in s}) == 8 and {x.tenant for x in s} == {"chat", "docqa"}


def test_phases_keep_the_mean_rate(mix):
    ph = traffic.phases(mix, 30)
    assert math.isclose(sum(l * r for _, l, r in ph) / 30, mix["rate_per_s"])
    assert math.isclose(sum(l for _, l, _ in ph), 30)


# ------------------------------------------------------------------ statistics
def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile(list(range(1, 21)), 95) == 19


def test_union_and_gaps_of_intervals():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7), (9, 12)]
    assert stats.union_length(iv, 0, 10) == 3 + 1 + 1
    assert stats.gaps(iv, 0, 10) == [(3, 5), (6, 9)]
    assert stats.union_length([], 0, 10) == 0
    assert stats.gaps([], 0, 1) == [(0, 1)]


def test_collections_are_timed_while_watched():
    t0 = time.perf_counter()
    with R.Collections() as c:
        gc.collect()
    assert c not in gc.callbacks
    start, secs, generation = c.pauses[-1]
    assert t0 <= start and secs >= 0 and generation == 2
    assert c.summary(t0, time.perf_counter()).startswith(f"{len(c.pauses)} garbage collections")
    assert R.Collections().summary(0, 1).startswith("0 garbage collections")


def _run(requests, t0=0.0, t_end=10.0, **kw):
    return RunRecord(cell={}, config={"model": DS7B}, mix={}, seconds=t_end - t0,
                     setup_s=12.5, peaks=V5E, family=LLAMA, t0=t0, t_end=t_end,
                     requests=requests, **kw)


def _client(due, times, plen=256, admit=None):
    c = ClientRequest(due=due, prompt_len=plen, max_new_tokens=len(times), tenant="chat")
    c.token_times = list(times)
    c.admit_step_start = due if admit is None else admit
    return c


def test_latencies_are_measured_from_due_times():
    reqs = [_client(due=i, times=[i + 0.1 * (i + 1), i + 0.1 * (i + 1) + 0.02])
            for i in range(20)]
    ttft = R.read_metric("ttft_p95_ms", _run(reqs))
    assert ttft == pytest.approx(1900.0)  # nearest rank: the 19th of 20
    assert R.read_metric("itl_p95_ms", _run(reqs)) == pytest.approx(20.0)
    assert R.read_metric("setup_s", _run(reqs)) == 12.5
    reqs = [_client(due=0.0, times=[1.0], admit=0.25 * k) for k in range(20)]
    assert R.read_metric("queue_wait_p95_ms", _run(reqs)) == pytest.approx(4500.0)


def test_rates_are_taken_over_the_whole_window():
    # tokens seen after the window closes do not count; the divisor is the window
    reqs = [_client(due=1.0, times=[2.0, 3.0, 9.5, 10.5]), _client(due=8.0, times=[11.0])]
    assert R.read_metric("output_tokens_per_s", _run(reqs, t0=0.0, t_end=10.0)) == 0.3


# ------------------------------------------------------------------ counters
DS7B = R.load_config("deepseek-7b-serve")["model"]
MAMBA2 = R.load_config("mamba2-130m-train")["model"]
LLAMA = R.load_reference({"reference": "llama"})
MAMBA2_FAMILY = R.load_reference({"reference": "mamba2"})


def test_flash_attention_flops_and_bytes_at_known_shapes():
    fa = R.load_metric("flash_attn_roofline")
    # S=640, 32 heads of 128: QK^T and PV over 640*641/2 pairs, 2 FLOPs each
    assert LLAMA.attention_flops(DS7B, 640) == 2 * 2 * 32 * 128 * (640 * 641 // 2)
    # Q, K, V read and O written once, bf16
    assert LLAMA.attention_bytes(DS7B, 640) == 4 * 640 * 32 * 128 * 2
    # one kernel call per layer of the prefill
    assert LLAMA.attention_calls(DS7B) == 16
    # at 640 the kernel is bandwidth-bound on a v5e
    assert fa.least_time(LLAMA, DS7B, 640, V5E) == pytest.approx(4 * 640 * 4096 * 2 / 819e9)
    assert fa.least_time(LLAMA, DS7B, 8192, V5E) == pytest.approx(
        LLAMA.attention_flops(DS7B, 8192) / 197e12)


def test_model_flops_at_known_shapes():
    mfu = R.load_metric("mfu_pct.serve")
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert LLAMA.layer_matmul_params(DS7B) == per_layer
    head = 2 * 4096 * 102400
    assert LLAMA.prefill_flops(DS7B, 1) == 2 * per_layer * 16 + 2 * 4096 * 2 * 16 + head
    assert LLAMA.decode_flops(DS7B, 99) == 2 * per_layer * 16 + 4 * 4096 * 100 * 16 + head
    # one 640-token prefill and one decode token in a one-second window
    run = _run([_client(due=0.0, times=[0.5, 0.6], plen=640)], t_end=1.0)
    want = (LLAMA.prefill_flops(DS7B, 640) + LLAMA.decode_flops(DS7B, 640)) / 197e12 * 100
    assert mfu.read(run) == pytest.approx(want)


def test_training_flops_at_the_published_shape():
    mfu = R.load_metric("mfu_pct.train")
    # mamba2-130m: d 768, d_inner 1536, 24 heads of 64, d_state 128, one group
    per_layer = 768 * (2 * 1536 + 2 * 128 + 24) + 1536 * 768
    assert MAMBA2_FAMILY.matmul_params(MAMBA2) == 24 * per_layer + 50277 * 768
    # per layer, chunks of 256: (256 + 1) / 2 causal pairs a token
    ssd = 24 * (2 * 128.5 * 128 + 2 * 128.5 * 64 * 24 + 4 * 128 * 64 * 24)
    assert MAMBA2_FAMILY.ssd_flops_per_token(MAMBA2) == ssd
    want = 6 * (24 * per_layer + 50277 * 768) + 3 * ssd
    assert MAMBA2_FAMILY.train_flops_per_token(MAMBA2) == want == 859_663_872
    # 1000 tokens a second for two seconds
    steps = [SimpleNamespace(tokens=1000), SimpleNamespace(tokens=1000)]
    run = RunRecord(cell={}, config={"model": MAMBA2}, mix={}, seconds=2.0, setup_s=1.0,
                    peaks=V5E, family=MAMBA2_FAMILY, t0=0.0, t_end=2.0, steps=steps)
    assert mfu.read(run) == pytest.approx(100.0 * want * 1000 / 197e12)


# ------------------------------------------------------------------ family modules
def _all_metrics(man, cell):
    return R.metrics_for(man, cell, False) + R.metrics_for(man, cell, True)


def test_every_configuration_has_a_family_module_with_what_its_cells_call():
    man = R.load_manifest()
    for cell in man["workloads"]:
        conf = R.load_config(cell["config"])
        R.check_family(R.load_reference(conf), conf["kind"], _all_metrics(man, cell["name"]))


def test_a_missing_function_is_an_error_before_model_work(monkeypatch):
    man = R.load_manifest()
    cell = R.find_cell(man, "ds7b-prefill-burst")
    conf = R.load_config(cell["config"])
    lacking = types.ModuleType("lacking")
    for name in dir(LLAMA):
        if name != "decode_flops" and not name.startswith("__"):
            setattr(lacking, name, getattr(LLAMA, name))
    monkeypatch.setattr(R, "load_reference", lambda conf: lacking)
    # the look for a chip comes first in a run, before any model work: the
    # family's error is raised before it (the host has no chip)
    with pytest.raises(AttributeError, match=r"lacking lacks decode_flops \(for mfu_pct.serve\)"):
        R.run_cell(cell, conf, traffic.load_mix(cell["traffic"]),
                   R.metrics_for(man, cell["name"], True), 1, 1.0, True)
    # a run whose metrics do not call it gets past the family, to the chip
    with pytest.raises(R.NoChip):
        R.run_cell(cell, conf, traffic.load_mix(cell["traffic"]),
                   R.metrics_for(man, cell["name"], False), 1, 1.0, False)
    with pytest.raises(AttributeError, match="cache_bytes_per_token \\(for serve cells\\)"):
        R.check_family(types.ModuleType("empty"), "serve", [])


# ------------------------------------------------------------------ trace
@pytest.fixture(scope="module")
def recorded():
    """An excerpt of a ``--trace 1`` run of ds7b-prefill-burst on one v5e."""
    return tr.Trace.from_json(json.loads((DATA / "trace_excerpt.json").read_text()))


def test_reduction_on_a_synthetic_trace():
    # a loop that holds two operations, then one more operation
    t = tr.Trace({"/device:TPU:0": [("loop", 0, 20), ("a", 0, 10), ("b", 10, 20), ("a", 30, 40)]},
                 [("window", 0, 50), ("engine_step.admit", 0, 25), ("collect", 25, 35)])
    lo, hi = tr.span(t, "window")
    assert tr.busy_ns(t, lo, hi) == 30
    # self time: the loop keeps none of what its operations cover
    assert tr.top_ops(t, lo, hi) == [["a", 20e-9], ["b", 10e-9], ["loop", 0.0]]
    # the gap at 20-30 falls in collect (its midpoint, 25, is past the step)
    assert sorted(tr.idle_by_span(t, lo, hi)) == [["collect", 10e-9], ["none", 10e-9]]
    run = _run([], trace=t, trace_window=(lo, hi))
    assert R.read_metric("device_idle_pct.serve", run) == pytest.approx(40.0)
    tr.relabel(t, "collect", ["collect.x"])
    assert t.spans[-1][0] == "collect.x"


def test_reduction_on_the_recorded_trace(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    ops = recorded.devices["/device:TPU:0"]
    lo = min(s for _, s, _ in ops)
    hi = max(e for _, _, e in ops)
    busy = tr.busy_ns(recorded, lo, hi)
    assert 0 < busy <= hi - lo
    assert busy == pytest.approx(stats.union_length([(s, e) for _, s, e in ops], lo, hi))
    top = tr.top_ops(recorded, lo, hi)
    assert len(top) == 10 and top == sorted(top, key=lambda x: -x[1])
    idle = tr.idle_by_span(recorded, lo, hi)
    assert sum(t for _, t in idle) == pytest.approx((hi - lo - busy) / 1e9, rel=1e-6)
    names = {n for n, _, _ in recorded.spans}
    assert {"engine_step.admit", "collect"} <= names
    fa = R.load_module(R.BENCH_DIR / "metrics" / "flash_attn_roofline.py")
    kernels = tr.matching(recorded, fa.KERNEL, lo, hi)
    assert kernels and len(kernels) % LLAMA.attention_calls(DS7B) == 0
