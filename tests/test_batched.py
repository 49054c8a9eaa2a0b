"""Batched divergent backend: bit-identity to the serial pool, masked
lockstep degeneracies, and the numpy/jax array-ops element-identity
contract.

The acceptance bar mirrors ``test_batch.py``: :meth:`BatchResult.signature`
over the **whole scenario registry** under divergent parameter draws must be
byte-for-byte equal between ``backend="pool"`` (serial, one true simulation
per job) and ``backend="batched"`` (one process, SoA state, one deferred
segment-scatter landing).  Any divergence — event order, flush boundaries,
report text, clean-lane carries — fails loudly.
"""

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core.array_ops import NumpyOps, get_backend
from repro.core.faults import FaultPlan
from repro.sim.batch import BatchJob, BatchRunner
from repro.sim.scenarios import divergent_draws, get_spec, list_scenarios, space_draws


def _serial(jobs):
    return BatchRunner(jobs, backend="pool").run(parallel=False)


def _batched(jobs):
    return BatchRunner(jobs, backend="batched").run()


# --------------------------------------------------------------------------- identity
class TestBatchedBitIdentity:
    def test_full_registry_divergent_draws(self):
        """The headline contract: every scenario, divergent params per run."""
        draws = divergent_draws(2, seed=0)
        assert len({(d["scenario"], tuple(sorted(d["params"].items()))) for d in draws}) > len(
            list_scenarios()
        )  # the draws actually diverge
        jobs = [BatchJob.make(d["scenario"], d["params"], engine="event") for d in draws]
        assert _serial(jobs).signature() == _batched(jobs).signature()

    @pytest.mark.parametrize("engine", ["cycle", "compiled"])
    def test_other_engines(self, engine):
        draws = divergent_draws(1, seed=3)
        jobs = [BatchJob.make(d["scenario"], d["params"], engine=engine) for d in draws]
        assert _serial(jobs).signature() == _batched(jobs).signature()

    def test_mixed_engines_in_one_batch(self):
        draws = divergent_draws(1, seed=5)
        engines = ["cycle", "event", "compiled"]
        jobs = [
            BatchJob.make(d["scenario"], d["params"], engine=engines[i % 3])
            for i, d in enumerate(draws)
        ]
        assert _serial(jobs).signature() == _batched(jobs).signature()

    def test_config_overrides_diverge_runs(self):
        """Structural + value-only overrides vary per run and stay identical."""
        jobs = [
            BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2)),
            BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2),
                          config=dict(hbm_latency=60)),
            BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2),
                          config=dict(max_cycles=9_999_999)),
            BatchJob.make("mps_like", dict(tenants=3, kernels_each=2),
                          config=dict(vmem_lines=8)),
        ]
        serial = _serial(jobs)
        assert serial.signature() == _batched(jobs).signature()
        # the structural override actually changed the simulation
        assert serial.payloads[0]["cycles"] != serial.payloads[1]["cycles"]

    def test_payloads_in_job_order_with_scenarios(self):
        draws = divergent_draws(1, seed=9)
        jobs = [BatchJob.make(d["scenario"], d["params"], engine="event") for d in draws]
        res = _batched(jobs)
        assert [p["scenario"] for p in res.payloads] == [j.scenario for j in jobs]
        assert res.oracle_failures() == []


# --------------------------------------------------------------------------- degeneracies
class TestMaskedLockstepDegeneracies:
    def test_single_run_batch(self):
        """N=1: the SoA machinery degenerates to one run, still identical."""
        jobs = [BatchJob.make("l2_lat", dict(n_loads=128, n_streams=4))]
        assert _serial(jobs).signature() == _batched(jobs).signature()

    def test_early_finishing_run_masked_out(self):
        """One run retires orders of magnitude before the other: the long
        run's remaining steps execute with the short run masked done, and
        neither signature moves."""
        jobs = [
            BatchJob.make("l2_lat", dict(n_loads=16, n_streams=1)),
            BatchJob.make("cache_thrash", dict(n_lines=96, rounds=4)),
        ]
        serial = _serial(jobs)
        assert serial.signature() == _batched(jobs).signature()
        cycles = [p["cycles"] for p in serial.payloads]
        assert max(cycles) > 2 * min(cycles)  # the divergence is real

    def test_duplicate_jobs(self):
        """Identical runs land into distinct segment rows, never aliased."""
        job = BatchJob.make("producer_consumer", dict(stages=3))
        jobs = [job, job, job]
        serial = _serial(jobs)
        batched = _batched(jobs)
        assert serial.signature() == batched.signature()
        sigs = [p["signature"] for p in batched.payloads]
        assert sigs[0] == sigs[1] == sigs[2]

    def test_failed_job_isolated(self):
        """A job that raises mid-batch must not corrupt its neighbours."""
        good = BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2))
        bad = BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2),
                            config=dict(max_cycles=1))
        serial = _serial([good, bad, good])
        batched = _batched([good, bad, good])
        assert [p.get("failed", False) for p in batched.payloads] == \
               [p.get("failed", False) for p in serial.payloads]
        assert batched.payloads[0]["signature"] == serial.payloads[0]["signature"]
        assert batched.payloads[2]["signature"] == serial.payloads[2]["signature"]


# --------------------------------------------------------------------------- S1: fault plans
class TestFaultPlanGating:
    @pytest.mark.parametrize("backend", ["vector", "batched"])
    def test_empty_plan_accepted(self, backend):
        jobs = [BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2)),
                BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2))]
        plan = FaultPlan(seed=1)
        assert plan.is_empty()
        runner = BatchRunner(jobs, backend=backend, fault_plan=plan)
        assert runner.run().signature() == _serial(jobs).signature()

    @pytest.mark.parametrize("backend", ["vector", "batched"])
    def test_armed_plan_rejected_naming_pool(self, backend):
        # The rejection must name the offending job's scenario and the
        # backend, not just restate the flag (docs/DESIGN.md §5.11).
        jobs = [BatchJob.make("mps_like"),
                BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2))]
        plan = FaultPlan(seed=1, crash_jobs=(1,))
        with pytest.raises(ValueError) as exc:
            BatchRunner(jobs, backend=backend, fault_plan=plan)
        msg = str(exc.value)
        assert "backend='pool'" in msg
        assert "job 1 ('l2_lat')" in msg and f"backend={backend!r}" in msg

    @pytest.mark.parametrize("backend", ["vector", "batched"])
    def test_journal_rejected(self, backend, tmp_path):
        jobs = [BatchJob.make("l2_lat", dict(n_loads=64, n_streams=2))]
        with pytest.raises(ValueError) as exc:
            BatchRunner(jobs, backend=backend, journal=str(tmp_path / "j.jsonl"))
        msg = str(exc.value)
        assert "backend='pool'" in msg
        assert "'l2_lat'" in msg and f"backend={backend!r}" in msg


# --------------------------------------------------------------------------- array ops
def _rand_events(rng, n, n_cells):
    lin = rng.integers(0, n_cells, size=n).astype(np.int64)
    cnt = rng.integers(1, 1000, size=n).astype(np.uint64)
    return lin, cnt


class TestArrayOpsElementIdentity:
    """Every op: jax output must equal the numpy reference exactly."""

    def setup_method(self):
        self.np_ops = get_backend("numpy")
        self.jax_ops = pytest.importorskip("jax") and get_backend("jax")

    @pytest.mark.parametrize("n,n_cells", [(0, 64), (17, 64), (5000, 64), (5000, 100_000)])
    def test_scatter_add_u64(self, n, n_cells):
        rng = np.random.default_rng(n + n_cells)
        lin, cnt = _rand_events(rng, n, n_cells)
        base = rng.integers(0, 1 << 40, size=n_cells).astype(np.uint64)
        a, b = base.copy(), base.copy()
        self.np_ops.scatter_add_u64(a, lin, cnt)
        self.jax_ops.scatter_add_u64(b, lin, cnt)
        assert np.array_equal(a, b)

    @pytest.mark.parametrize("unit_counts", [True, False])
    def test_scatter_bincount_and_add_at_branches_identical(self, unit_counts):
        """S2: both bincount fast paths (unweighted for unit counts,
        weighted otherwise) are count-identical to np.add.at."""
        rng = np.random.default_rng(int(unit_counts))
        lin, cnt = _rand_events(rng, 4096, 256)
        if unit_counts:
            cnt = np.ones_like(cnt)
        via_bincount = np.zeros(256, dtype=np.uint64)
        via_add_at = np.zeros(256, dtype=np.uint64)
        NumpyOps(bincount_min_events=1).scatter_add_u64(via_bincount, lin, cnt)
        NumpyOps(bincount_min_events=1 << 60).scatter_add_u64(via_add_at, lin, cnt)
        assert np.array_equal(via_bincount, via_add_at)

    @pytest.mark.parametrize("shape", [(0,), (1,), (257,), (64, 3)])
    def test_running_sum_float64(self, shape):
        rng = np.random.default_rng(hash(shape) % 2**31)
        # adversarial magnitudes so any reassociation changes the rounding
        vals = rng.uniform(-1.0, 1.0, size=shape) * (10.0 ** rng.integers(-8, 8, size=shape))
        a = self.np_ops.running_sum(vals)
        b = self.jax_ops.running_sum(vals)
        assert a.dtype == b.dtype and np.array_equal(a, b)
        assert np.array_equal(a, np.add.accumulate(vals, axis=0))

    def test_running_sum_int64(self):
        vals = np.arange(100, dtype=np.int64) * 3
        assert np.array_equal(self.np_ops.running_sum(vals), self.jax_ops.running_sum(vals))

    @pytest.mark.parametrize("table_size", [0, 1, 7, 500])
    def test_sorted_membership(self, table_size):
        rng = np.random.default_rng(table_size)
        table = np.unique(rng.integers(0, 1000, size=table_size).astype(np.int64))
        values = rng.integers(-5, 1005, size=300).astype(np.int64)
        a = self.np_ops.sorted_membership(values, table)
        b = self.jax_ops.sorted_membership(values, table)
        want = np.isin(values, table)
        assert np.array_equal(a, want) and np.array_equal(b, want)

    @pytest.mark.parametrize("n_segs,row_size", [(1, 8), (5, 64), (16, 300)])
    def test_segment_scatter(self, n_segs, row_size):
        rng = np.random.default_rng(n_segs * row_size)
        n = 2000
        # deliberately include seg == n_segs + slack: overflow must drop
        seg = rng.integers(0, n_segs + 2, size=n).astype(np.int64)
        lin = rng.integers(0, row_size, size=n).astype(np.int64)
        cnt = rng.integers(1, 50, size=n).astype(np.uint64)
        a = self.np_ops.segment_scatter(seg, lin, cnt, n_segs, row_size)
        b = self.jax_ops.segment_scatter(seg, lin, cnt, n_segs, row_size)
        assert a.shape == (n_segs, row_size) and np.array_equal(a, b)
        # reference: dense scatter with overflow rows masked out
        want = np.zeros((n_segs, row_size), dtype=np.uint64)
        keep = seg < n_segs
        np.add.at(want, (seg[keep], lin[keep]), cnt[keep])
        assert np.array_equal(a, want)

    def test_segment_scatter_all_events_overflow(self):
        seg = np.full(64, 9, dtype=np.int64)
        lin = np.zeros(64, dtype=np.int64)
        cnt = np.ones(64, dtype=np.uint64)
        for ops in (self.np_ops, self.jax_ops):
            out = ops.segment_scatter(seg, lin, cnt, 4, 16)
            assert out.shape == (4, 16) and out.sum() == 0

    def test_segment_scatter_empty(self):
        e = np.empty(0, dtype=np.int64)
        for ops in (self.np_ops, self.jax_ops):
            out = ops.segment_scatter(e, e, e.astype(np.uint64), 3, 5)
            assert out.shape == (3, 5) and out.sum() == 0

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown array backend"):
            get_backend("torch")


class TestJaxBackendEndToEnd:
    def test_batched_jax_payloads_match_numpy(self):
        pytest.importorskip("jax")
        draws = divergent_draws(1, seed=7)
        mk = lambda cfg: [
            BatchJob.make(d["scenario"], d["params"], engine="event", config=cfg)
            for d in draws
        ]
        num = BatchRunner(mk(None), backend="batched").run()
        jx = BatchRunner(mk(dict(array_backend="jax")), backend="batched").run()
        for pn, pj in zip(num.payloads, jx.payloads):
            assert pn["signature"] == pj["signature"]
            assert pn["cycles"] == pj["cycles"] and pn["oracle"] == pj["oracle"]

    @pytest.mark.parametrize("backend", ["pool", "vector"])
    def test_jax_jobs_never_fan_out_to_workers(self, backend):
        """One process per accelerator: a sweep with a jax-backed job runs
        in process even when a pool was asked for, with the same payloads."""
        jobs = [
            BatchJob.make("l2_lat", {"n_loads": 16 + 16 * i},
                          config=dict(array_backend="jax"))
            for i in range(3)
        ]
        pooled = BatchRunner(jobs, workers=3, backend=backend).run(parallel=True)
        serial = BatchRunner(jobs, backend=backend).run(parallel=False)
        assert not pooled.parallel and pooled.workers == 1
        assert pooled.signature() == serial.signature()


# --------------------------------------------------------------------------- hypothesis
if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None, suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data())
    def test_batched_identity_hypothesis(data):
        """Hypothesis-drawn divergent batches: scenario subset, per-run param
        draws from each declared space, mixed engines — batched must stay
        bit-identical to the serial pool."""
        names = data.draw(
            st.lists(st.sampled_from(list_scenarios()), min_size=1, max_size=4, unique=True)
        )
        jobs = []
        for name in names:
            spec = get_spec(name)
            draws = space_draws(name, 2, seed=data.draw(st.integers(0, 1000)))
            for params in draws:
                engine = data.draw(st.sampled_from(("cycle", "event")))
                jobs.append(BatchJob.make(name, params, engine=engine))
        assert _serial(jobs).signature() == _batched(jobs).signature()
