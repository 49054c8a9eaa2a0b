"""BatchRunner: pooled/serial bit-identity, deterministic merge, and the
randomized scenario differential (cycle == event under parameter draws).

The bit-identity contract is asserted on :meth:`BatchResult.signature` —
per-job uid-normalized run signatures *and* the namespaced merged engine —
so a pool-path divergence anywhere (worker scheduling, merge order, stream
namespacing) fails loudly.
"""

import itertools
import random

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core.collector import split_namespaced
from repro.core.sinks import ALL_STREAMS, JSONSink
from repro.sim.batch import BatchJob, BatchRunner, merge_payloads, run_job, sweep_jobs
from repro.sim.scenarios import build, get_spec, list_scenarios

import io


SMALL_SWEEP = [
    BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2)),
    BatchJob.make("mps_like", dict(tenants=2, kernels_each=2)),
    BatchJob.make("producer_consumer", dict(stages=2)),
    BatchJob.make("fork_join", dict(rounds=1, width=2)),
]


class TestBatchRunner:
    def test_pooled_merge_bit_identical_to_serial(self):
        runner = BatchRunner(SMALL_SWEEP, workers=2)
        serial = runner.run(parallel=False)
        pooled = runner.run(parallel=True)
        assert serial.signature() == pooled.signature()
        assert not serial.parallel and serial.workers == 1
        assert serial.oracle_failures() == [] and pooled.oracle_failures() == []

    def test_full_registry_sweep_serial_equals_pool(self):
        jobs = sweep_jobs(engines=("event",))
        assert len(jobs) == len(list_scenarios())
        runner = BatchRunner(jobs, workers=2)
        assert runner.run(parallel=False).signature() == runner.run(parallel=True).signature()

    def test_merged_aggregate_is_sum_of_jobs(self):
        result = BatchRunner(SMALL_SWEEP).run(parallel=False)
        total = np.zeros_like(result.merged.aggregate())
        for p in result.payloads:
            for views in p["signature"]["stats"]["streams"].values():
                total += np.asarray(views["cum"], dtype=np.uint64)
        assert (result.merged.aggregate() == total).all()

    def test_stream_namespacing_recovers_job_and_stream(self):
        result = BatchRunner(SMALL_SWEEP).run(parallel=False)
        rows = result.stream_rows()
        for (job_idx, sid), matrix in rows.items():
            payload = result.payloads[job_idx]
            want = np.asarray(payload["signature"]["stats"]["streams"][sid]["cum"],
                              dtype=np.uint64)
            assert (matrix == want).all()
        # every job contributed at least its counting streams
        jobs_seen = {j for j, _ in rows}
        assert jobs_seen == set(range(len(SMALL_SWEEP)))

    def test_merge_payloads_accepts_json_roundtripped_keys(self):
        # sweep scripts persist payloads as JSON, which stringifies int keys
        import json

        payloads = [run_job(j) for j in SMALL_SWEEP[:2]]
        roundtripped = json.loads(json.dumps(payloads))
        a = merge_payloads(payloads)
        b = merge_payloads(roundtripped)
        assert a.signature() == b.signature()

    def test_job_order_preserved_in_payloads(self):
        result = BatchRunner(SMALL_SWEEP, workers=2).run(parallel=True)
        assert [p["scenario"] for p in result.payloads] == [j.scenario for j in SMALL_SWEEP]

    def test_pooled_chunked_shape_grouped_order_restored(self):
        """The pooled path hands jobs out in shape-grouped order; payloads
        must come back in job order and bit-identical to serial even with
        interleaved duplicate shapes."""
        jobs = [
            SMALL_SWEEP[0], SMALL_SWEEP[1], SMALL_SWEEP[0], SMALL_SWEEP[2],
            SMALL_SWEEP[1], SMALL_SWEEP[0],
        ]
        runner = BatchRunner(jobs, workers=2)
        serial = runner.run(parallel=False)
        pooled = runner.run(parallel=True)
        assert serial.signature() == pooled.signature()
        assert [p["scenario"] for p in pooled.payloads] == [j.scenario for j in jobs]

    def test_pooled_with_config_overrides(self):
        jobs = [
            BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2),
                          config=dict(max_cycles=9_999_999)),
            BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2),
                          config=dict(hbm_latency=60)),
        ]
        runner = BatchRunner(jobs, workers=2)
        serial = runner.run(parallel=False)
        pooled = runner.run(parallel=True)
        assert serial.signature() == pooled.signature()
        # structural override actually changed the simulation
        assert serial.payloads[0]["cycles"] != serial.payloads[1]["cycles"]

    def test_empty_jobs_rejected(self):
        with pytest.raises(ValueError, match="at least one job"):
            BatchRunner([])

    def test_sweep_jobs_unknown_scenario_fails_fast(self):
        with pytest.raises(KeyError, match="unknown scenario"):
            sweep_jobs(scenarios=["no_such_scenario"])

    def test_merged_report_roundtrips_through_json_sink(self):
        result = BatchRunner(SMALL_SWEEP).run(parallel=False)
        report = result.report()
        assert report.stream_id == ALL_STREAMS
        assert report.fields["n_jobs"] == len(SMALL_SWEEP)
        buf = io.StringIO()
        JSONSink(buf).emit(report)
        (obj,) = JSONSink.parse(buf.getvalue())
        main = JSONSink.block_matrix(obj["blocks"][0])
        assert (main == result.merged.aggregate()).all()


# --------------------------------------------------------------------------- differential
def _space_combos(name):
    spec = get_spec(name)
    keys = sorted(spec.space)
    return [dict(zip(keys, combo)) for combo in itertools.product(*(spec.space[k] for k in keys))]


#: (scenario, params) pairs spanning every registered scenario's space.
ALL_DRAWS = [(n, p) for n in list_scenarios() for p in _space_combos(n)]


def _assert_cycle_equals_event(name, params):
    inst = build(name, **params)
    a = inst.run(engine="cycle").signature()
    b = inst.run(engine="event").signature()
    for key in a:
        assert a[key] == b[key], f"{name} {params}: engine mismatch in {key!r}"


@pytest.mark.parametrize("seed", range(6))
def test_randomized_scenario_differential(seed):
    """Random scenario + space draw: cycle and event engines bit-identical,
    pooled and serial batch merges bit-identical."""
    rng = random.Random(seed)
    draws = rng.sample(ALL_DRAWS, 3)
    for name, params in draws:
        _assert_cycle_equals_event(name, params)
    jobs = [BatchJob.make(n, p, engine=rng.choice(("cycle", "event"))) for n, p in draws]
    runner = BatchRunner(jobs, workers=2)
    assert runner.run(parallel=False).signature() == runner.run(parallel=True).signature()


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None, suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data())
    def test_scenario_differential_hypothesis(data):
        """Hypothesis-driven draw over the registry: scenario name + params
        from its declared space must satisfy cycle == event and
        pool-merge == serial-merge (the ISSUE's differential contract)."""
        name = data.draw(st.sampled_from(list_scenarios()))
        params = data.draw(st.sampled_from(_space_combos(name)))
        _assert_cycle_equals_event(name, params)
        engine = data.draw(st.sampled_from(("cycle", "event")))
        jobs = [BatchJob.make(name, params, engine=engine),
                BatchJob.make(name, params, engine="event")]
        runner = BatchRunner(jobs, workers=2)
        assert runner.run(parallel=False).signature() == runner.run(parallel=True).signature()
