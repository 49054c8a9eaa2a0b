"""End-to-end behaviour tests for the paper's system.

The headline test reproduces the paper's full §5 validation flow in one
pass: concurrent multi-stream execution with per-stream stat tracking,
validated against closed-form counts, the clean baseline, and the
serialized build — then checks the framework-level integration (training
lanes + serving requests as streams).
"""

import io

import numpy as np
import pytest


def test_paper_validation_end_to_end():
    from repro.core.stats import AccessOutcome, AccessType
    from repro.sim import l2_lat_expected_counts, l2_lat_multistream

    R = AccessType.GLOBAL_ACC_R
    n_streams, n_loads = 4, 256
    exp = l2_lat_expected_counts(n_streams, n_loads)

    tip = l2_lat_multistream(n_streams, n_loads)
    ser = l2_lat_multistream(n_streams, n_loads, serialize=True)

    # (1) aggregate == closed form
    agg = tip.stats.aggregate()
    assert int(agg[R, AccessOutcome.MISS]) == exp["MISS"]
    assert int(agg[R, AccessOutcome.HIT_RESERVED]) == exp["MSHR_HIT"]
    assert int(agg[R, AccessOutcome.HIT]) == exp["HIT"]
    # (2) paper §5.1: clean equals Σ tip for the latency-bound benchmark
    for o in (AccessOutcome.HIT, AccessOutcome.HIT_RESERVED, AccessOutcome.MISS):
        assert tip.clean.get(R, o) == int(agg[R, o])
    # (3) per-stream: every stream saw exactly n_loads accesses
    for sid in tip.stats.streams():
        assert tip.stats.stream_matrix(sid)[R].sum() == n_loads
    # (4) serialized ⇒ MSHR hits become plain hits, streams never overlap
    sa = ser.stats.aggregate()
    assert int(sa[R, AccessOutcome.HIT_RESERVED]) == 0
    sids = ser.stats.streams()
    assert ser.timeline.overlap_cycles(sids[0], sids[1]) == 0
    # (5) print-on-exit emits only the exiting stream's stats
    exit_blocks = [l for l in tip.log if "finished on stream" in l]
    assert len(exit_blocks) == n_streams


def test_framework_streams_integration():
    """Train + eval lanes and serving requests are first-class streams."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core import stream_scope, current_stream

    with stream_scope(42):
        assert current_stream() == 42
    assert current_stream() == 0


def test_quickstart_example_runs():
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "examples/quickstart.py", "--steps", "3"],
        capture_output=True, text=True, timeout=600,
        env={
            "PYTHONPATH": "src",
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", ""),
            # the child runs on the host backend, never on an accelerator
            # this test process may hold
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "stream" in proc.stdout


def test_compile_cache_left_to_jax_or_fixed_in_checkout(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: no directory is set in code.
    Unset: the cache goes to one fixed, git-ignored path in the checkout."""
    from pathlib import Path

    import jax

    from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)  # same path every call
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = Path(__file__).resolve().parents[1]
    assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split("\n")
