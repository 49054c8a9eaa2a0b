"""Pluggable array-ops backend for the hottest landing paths.

The simulator's inner loops funnel through a handful of array primitives:
the ``np.add.at`` stat scatter in :meth:`repro.core.engine.StatsEngine.flush`,
the strictly-sequential ``np.add.accumulate`` bandwidth-pointer fold in
``repro.sim.executor._occupy_sequence`` / ``repro.sim.compiled.replay_batch``,
the sorted-membership probes (stat-slot lookup, the batched VMEM cache-tag
probe), and the batched backend's segment-scatter landing kernel
(``repro.sim.batched``).  Each primitive has a NumPy reference
implementation and a jit-compiled jax implementation, selected by
``SimConfig.array_backend = "numpy" | "jax"``.

The contract is **element identity**: for every op and every input, the jax
backend must return exactly the NumPy reference's values — uint64 scatters
are exact by construction, and the float64 running sum is implemented as a
``lax.scan`` left fold because ``jnp.cumsum`` may reassociate (tree
reduction) while ``np.add.accumulate`` is strictly sequential.
``tests/test_batched.py`` asserts the identity per op; the whole-registry
bit-identity suites then cover the routed call sites end to end.

Importing this module never imports jax (``import repro`` stays jax-free);
the jax backend materializes lazily on first ``get_backend("jax")``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

__all__ = ["ArrayOps", "NumpyOps", "get_backend", "BACKENDS"]

#: S2 threshold: route the flush scatter through ``np.bincount`` on the
#: linearized cell index once a landing exceeds this many events —
#: ``np.add.at`` is notoriously slow for large batches (it dispatches per
#: element), while ``bincount`` is a single C pass.
_BINCOUNT_MIN_EVENTS = 2048

#: ``np.bincount`` accumulates float64 weights; integer sums are exact only
#: below 2**53.  The guard is on the *total* count of the landing, which
#: bounds every per-cell sum.
_FLOAT64_EXACT_MAX = 1 << 53


class ArrayOps:
    """Backend interface — see :class:`NumpyOps` for reference semantics."""

    name: str = "abstract"

    def scatter_add_u64(self, dense_flat: np.ndarray, lin: np.ndarray,
                        cnt: np.ndarray) -> None:
        raise NotImplementedError

    def running_sum(self, values: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def sorted_membership(self, values: np.ndarray, table: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def segment_scatter(self, seg: np.ndarray, lin: np.ndarray, cnt: np.ndarray,
                        n_segs: int, row_size: int) -> np.ndarray:
        raise NotImplementedError


class NumpyOps(ArrayOps):
    """Reference backend: plain NumPy, bit-defining for every op."""

    name = "numpy"

    def __init__(self, bincount_min_events: int = _BINCOUNT_MIN_EVENTS) -> None:
        self.bincount_min_events = int(bincount_min_events)

    def scatter_add_u64(self, dense_flat: np.ndarray, lin: np.ndarray,
                        cnt: np.ndarray) -> None:
        """In-place ``dense_flat[lin] += cnt`` with duplicate indices summed.

        Large landings route through ``np.bincount`` on the linearized index
        when the dense store is not vastly larger than the event batch (the
        ``minlength`` allocation would dominate).  Unit-count landings — the
        dominant per-access trace case — histogram in one unweighted C pass;
        weighted landings use float64-weighted bincount only while provably
        exact (total count below 2**53 bounds every per-cell partial sum).
        All branches produce the same uint64 values, including on
        wraparound, since the scatter sums are exact before the modular
        add."""
        n = lin.shape[0]
        if 0 < n >= self.bincount_min_events and dense_flat.size <= 8 * n + (1 << 16):
            if int(cnt.max()) == 1:
                dense_flat += np.bincount(lin, minlength=dense_flat.size).astype(
                    np.uint64
                )
                return
            if int(cnt.sum()) < _FLOAT64_EXACT_MAX:
                binned = np.bincount(lin, weights=cnt, minlength=dense_flat.size)
                dense_flat += binned.astype(np.uint64)
                return
        np.add.at(dense_flat, lin, cnt)

    def running_sum(self, values: np.ndarray) -> np.ndarray:
        """Strictly-sequential prefix sum along axis 0 (``ufunc.accumulate``
        is a left fold, so float64 rounding is order-defined)."""
        return np.add.accumulate(values, axis=0)

    def sorted_membership(self, values: np.ndarray, table: np.ndarray) -> np.ndarray:
        """Boolean mask: ``values[i] in table`` for a **sorted** table."""
        if table.size == 0:
            return np.zeros(values.shape, dtype=bool)
        idx = np.searchsorted(table, values)
        np.clip(idx, 0, table.size - 1, out=idx)
        return table[idx] == values

    def segment_scatter(self, seg: np.ndarray, lin: np.ndarray, cnt: np.ndarray,
                        n_segs: int, row_size: int) -> np.ndarray:
        """The batched landing kernel: scatter event counts into a
        ``(n_segs, row_size)`` uint64 table at ``[seg[i], lin[i]]``.  Events
        with ``seg >= n_segs`` (after the final report boundary) are dropped.
        """
        table = np.zeros(n_segs * row_size, dtype=np.uint64)
        keep = seg < n_segs
        if not keep.all():
            seg, lin, cnt = seg[keep], lin[keep], cnt[keep]
        if seg.size:
            self.scatter_add_u64(table, seg * row_size + lin, cnt)
        return table.reshape(n_segs, row_size)


class JaxOps(ArrayOps):
    """jit-compiled jax backend, element-identical to :class:`NumpyOps`.

    All ops run under ``jax.enable_x64(True)`` (scoped, not the global
    flag — the serving stack's float32 jax code is untouched) so
    uint64/int64/float64 semantics match NumPy exactly.  The segment
    scatter is the same jitted XLA scatter as :meth:`scatter_add_u64`, with
    events past the final report boundary masked to a zero add.
    """

    name = "jax"

    def __init__(self) -> None:
        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        self._x64 = lambda: jax.enable_x64(True)

        def _scatter(dense, lin, cnt):
            return dense.at[lin].add(cnt)

        def _runsum(values):
            # Left fold via lax.scan: carry is the running prefix, matching
            # np.add.accumulate's strictly-sequential float64 rounding.
            def step(carry, x):
                nxt = carry + x
                return nxt, nxt

            _, ys = jax.lax.scan(step, values[0], values[1:])
            return jnp.concatenate([values[:1], ys], axis=0)

        def _member(values, table):
            idx = jnp.clip(jnp.searchsorted(table, values), 0, table.shape[0] - 1)
            return table[idx] == values

        def _segment(seg, lin, cnt, n_segs, row_size):
            # a masked-out event adds zero at cell 0: shapes stay static
            ok = seg < n_segs
            cell = jnp.where(ok, seg * row_size + lin, 0)
            add = jnp.where(ok, cnt, jnp.uint64(0))
            table = jnp.zeros((n_segs * row_size,), jnp.uint64).at[cell].add(add)
            return table.reshape(n_segs, row_size)

        self._scatter = jax.jit(_scatter)
        self._runsum = jax.jit(_runsum)
        self._member = jax.jit(_member)
        self._segment = jax.jit(_segment, static_argnums=(3, 4))

    def scatter_add_u64(self, dense_flat, lin, cnt):
        with self._x64():
            out = self._scatter(
                self._jnp.asarray(dense_flat), self._jnp.asarray(lin),
                self._jnp.asarray(cnt),
            )
            dense_flat[...] = np.asarray(out)

    def running_sum(self, values):
        values = np.asarray(values)
        if values.shape[0] == 0:
            return values.copy()
        with self._x64():
            return np.asarray(self._runsum(self._jnp.asarray(values)))

    def sorted_membership(self, values, table):
        if table.size == 0:
            return np.zeros(np.asarray(values).shape, dtype=bool)
        with self._x64():
            return np.asarray(
                self._member(self._jnp.asarray(values), self._jnp.asarray(table))
            )

    def segment_scatter(self, seg, lin, cnt, n_segs, row_size):
        seg = np.asarray(seg, dtype=np.int64)
        lin = np.asarray(lin, dtype=np.int64)
        cnt = np.asarray(cnt, dtype=np.uint64)
        if seg.size == 0 or n_segs == 0:
            return np.zeros((n_segs, row_size), dtype=np.uint64)
        with self._x64():
            return np.asarray(
                self._segment(self._jnp.asarray(seg), self._jnp.asarray(lin),
                              self._jnp.asarray(cnt), int(n_segs), int(row_size))
            )


#: materialized backends by name (the numpy reference is always present)
BACKENDS: Dict[str, ArrayOps] = {"numpy": NumpyOps()}


def get_backend(name: str = "numpy") -> ArrayOps:
    """The array-ops backend for ``name`` ("numpy" | "jax"), cached.

    The jax backend imports jax on first use only; a host without jax gets
    an ImportError naming the numpy fallback rather than a bare module
    error."""
    ops = BACKENDS.get(name)
    if ops is not None:
        return ops
    if name == "jax":
        try:
            ops = JaxOps()
        except ImportError as err:  # pragma: no cover - env without jax
            raise ImportError(
                "array_backend='jax' requires jax; install it or use "
                "array_backend='numpy'"
            ) from err
        BACKENDS[name] = ops
        return ops
    raise ValueError(f"unknown array backend {name!r} (want 'numpy' or 'jax')")
