"""A whole training run on the host at a small size, with the look for a chip
skipped: a sound run is correct, and each fault planted in the timed step
underneath makes ``correct`` come out false.  The fp8 control and a
half-batch reference, put in the program's place, fail the comparison."""

from __future__ import annotations

import pytest

from bench import run as R
from bench import traffic
from bench.peaks import PEAKS

SECONDS = 1.0


def small():
    conf = R.load_config("mamba2-130m-train")
    conf["model"].update(d_model=64, n_layer=2, vocab_size=500, d_state=16, headdim=16,
                         chunk_size=16)
    conf["train"].update(global_batch=4)
    mix = traffic.load_mix("uniform_2k")
    mix["seq_len"] = 64
    return conf, mix


def run_small(trace=False):
    man = R.load_manifest()
    cell = R.find_cell(man, "mamba2-train-2k")
    conf, mix = small()
    return R.run_cell(cell, conf, mix, R.metrics_for(man, cell["name"], trace),
                      4_000_000_001, SECONDS, trace, require_chip=False,
                      peaks=PEAKS["TPU v5 lite"], compile_cache=False)


@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_training_run_is_correct(trace):
    res = run_small(trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # the host has no device trace: its readers find nothing and are left out
    want = {"mfu_pct.train"} if trace else {"train_tokens_per_s", "setup_s"}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "checks"


@pytest.fixture
def planted(monkeypatch):
    """Replace the program's train step by ``fault(step)`` for one test."""
    import repro.train.trainer as trainer

    make = trainer.make_train_step

    def plant(fault):
        monkeypatch.setattr(trainer, "make_train_step", lambda cfg, tcfg: fault(make(cfg, tcfg)))

    return plant


def test_a_step_that_returns_its_state_unchanged_is_caught(planted):
    def unchanged(step):
        def f(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return f

    planted(unchanged)
    res = run_small()
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > res["checks"]["change_gap"]["limit"]


def test_half_of_the_batch_left_out_is_caught(planted):
    def half(step):
        def f(params, opt, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()})
        return f

    planted(half)
    res = run_small()
    assert not res["correct"]


def test_the_fp8_control_and_a_half_batch_fail_where_the_program_passes():
    conf, mix = small()
    cell = R.cell_class("train")(conf, mix, R.load_reference(conf), 11, SECONDS)
    cell.setup()
    checks = cell.check(cell.window(), control=True)
    r = cell.readings
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    for name in ("control", "fault.half_batch"):
        assert any(r[f"{name}.{k}"] > conf["limits"][k] for k in checks), r
