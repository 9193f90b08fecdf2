"""Batched RF inference: one Pallas kernel launch per fleet tick.

Per-job prediction would launch one `rf_predict_pallas` call per job
per tick (J kernel launches, each on a handful of rows). The fleet
instead stacks every job's Table-3 feature rows into a single [R, 6]
batch and launches ONCE — the kernel's grid is over sample blocks, so
R rows from 8 jobs cost the same launch overhead as one job's rows,
and the forest stays resident in VMEM across the whole batch.

`kernel_calls` counts launches; the fleet invariant (asserted in
tests/test_fleet.py) is exactly one per tick regardless of job count.

With a tracer installed (the fleet installs its own when obs is on),
each call records three spans: `rf_launch` (the rows to a float32
device array, the jit dispatch and the request for the result's copy
to the host), `rf_wait` (the host blocked until the kernel's result is
ready) and `rf_fetch` (the rest of the copy back and the 1 Mbps
floor). The copy is requested at launch, as `np.asarray` alone would
request it before blocking, so that it follows the kernel on the device
without waiting for the host to wake from `rf_wait`.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.forest import RandomForest
from repro.obs.registry import MetricsRegistry
from repro.obs.spans import NULL_TRACER


class BatchedRfPredictor:
    """One shared forest, one kernel launch per fleet tick."""

    def __init__(self, forest: RandomForest):
        """`forest` must be fitted; its packed complete-binary-tree
        arrays are transferred to the device once, not per call."""
        if forest.feat is None:
            raise ValueError("forest must be fitted before batching")
        self.forest = forest
        f, t, l = forest.packed()
        self._packed = (jnp.asarray(f), jnp.asarray(t), jnp.asarray(l))
        # launch accounting on the obs registry; `kernel_calls` stays
        # readable as a back-compat property
        self.metrics = MetricsRegistry("predictor")
        self._m_calls = self.metrics.counter(
            "kernel_calls", help="batched RF Pallas launches")
        self._m_rows = self.metrics.counter(
            "rows_total", help="feature rows predicted")
        self.tracer = NULL_TRACER

    def predict_rows(self, X: np.ndarray) -> np.ndarray:
        """Predict runtime BW for stacked feature rows [R, 6] -> [R].

        One Pallas launch regardless of how many jobs contributed rows;
        predictions are floored at 1 Mbps (BW is positive).
        """
        from repro.kernels import ops
        tr = self.tracer
        self._m_calls.inc()
        self._m_rows.inc(int(np.asarray(X).shape[0]))
        with tr.span("rf_launch"):
            vals = ops.rf_predict(*self._packed,
                                  jnp.asarray(X, jnp.float32),
                                  depth=self.forest.depth)
            vals.copy_to_host_async()
        with tr.span("rf_wait"):
            vals.block_until_ready()
        with tr.span("rf_fetch"):
            return np.maximum(np.asarray(vals, np.float64), 1.0)

    @property
    def kernel_calls(self) -> int:
        """Total Pallas launches (registry-backed back-compat alias)."""
        return int(self._m_calls.value)

    @kernel_calls.setter
    def kernel_calls(self, v: int) -> None:
        """Legacy reset path (tests zero the tally between phases)."""
        self._m_calls.reset(int(v))

    def split_rows(self, vals: np.ndarray,
                   row_counts: Sequence[int]) -> list:
        """Un-stack a batched prediction back into per-job vectors."""
        out, ofs = [], 0
        for k in row_counts:
            out.append(vals[ofs:ofs + k])
            ofs += k
        if ofs != len(vals):
            raise ValueError(
                f"row counts {list(row_counts)} != batch size {len(vals)}")
        return out


def default_fleet_forest(n_samples: int = 60, n_trees: int = 8,
                         depth: int = 5, seed: int = 7,
                         cache: Optional[dict] = {}) -> RandomForest:
    """A small, deterministic forest for demos/benchmarks (module-level
    memo keyed by the arguments; pass ``cache=None`` to bypass it).
    Real deployments train via `repro.wan.dataset.train_default_forest`.
    """
    key = (n_samples, n_trees, depth, seed)
    if cache is not None and key in cache:
        return cache[key]
    from repro.wan.dataset import generate_dataset
    X, y = generate_dataset(n_samples=n_samples, seed=seed)
    rf = RandomForest(n_trees=n_trees, depth=depth, seed=seed).fit(X, y)
    if cache is not None:
        cache[key] = rf
    return rf
