"""Runtime-BW prediction (paper §3.1): Table-3 feature assembly + forest
inference. Inference has three interchangeable backends:
  numpy  — RandomForest.predict (training-side)
  jnp    — forest_predict_jnp (jit-able gathers; the kernel's oracle)
  pallas — kernels.rf_predict (TPU kernel; the fleet's sequential and
           fused ticks both run it)
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import RandomForest

FEATURE_NAMES = ("n_dcs", "snapshot_bw", "mem_util", "cpu_load",
                 "retransmissions", "distance_miles")


def assemble_features_loop(n_dcs: int, snap_bw: np.ndarray,
                           mem_util: np.ndarray, cpu_load: np.ndarray,
                           retrans: np.ndarray,
                           dist: np.ndarray) -> np.ndarray:
    """Reference double-loop form of :func:`assemble_features` (the
    historical implementation, kept as the bit-identity test oracle)."""
    N = snap_bw.shape[0]
    rows = []
    for i in range(N):
        for j in range(N):
            if i == j:
                continue
            rows.append([n_dcs, snap_bw[i, j], mem_util[j], cpu_load[i],
                         retrans[i, j], dist[i, j]])
    return np.asarray(rows, np.float32)


def assemble_features(n_dcs: int, snap_bw: np.ndarray, mem_util: np.ndarray,
                      cpu_load: np.ndarray, retrans: np.ndarray,
                      dist: np.ndarray) -> np.ndarray:
    """Vectorize Table 3 into per-pair rows.

    snap_bw/retrans/dist: [N,N]; mem_util (receiver)/cpu_load (sender): [N].
    Returns X [N*(N-1), 6] for all ordered off-diagonal pairs, in
    row-major (i, j) order skipping the diagonal — bit-identical to
    :func:`assemble_features_loop` (this is the per-tick AND harvest
    hot path, so it builds the [N,N,6] block in one shot and masks the
    diagonal instead of appending N*(N-1) Python lists)."""
    snap_bw = np.asarray(snap_bw)
    N = snap_bw.shape[0]
    block = np.empty((N, N, 6), np.float64)
    block[:, :, 0] = float(n_dcs)
    block[:, :, 1] = snap_bw
    block[:, :, 2] = np.asarray(mem_util)[None, :]       # receiver j
    block[:, :, 3] = np.asarray(cpu_load)[:, None]       # sender i
    block[:, :, 4] = np.asarray(retrans)
    block[:, :, 5] = np.asarray(dist)
    off = ~np.eye(N, dtype=bool)
    return block[off].astype(np.float32)


def matrix_from_pairs_loop(vals: np.ndarray, N: int,
                           diag: float = 0.0) -> np.ndarray:
    """Reference loop form of :func:`matrix_from_pairs` (test oracle)."""
    out = np.full((N, N), diag, np.float64)
    k = 0
    for i in range(N):
        for j in range(N):
            if i != j:
                out[i, j] = vals[k]
                k += 1
    return out


def matrix_from_pairs(vals: np.ndarray, N: int,
                      diag: float = 0.0) -> np.ndarray:
    """Inverse of `assemble_features`'s row order: fold N*(N-1)
    per-pair values back into an [N,N] matrix with `diag` filled in
    (one boolean-mask scatter; bit-identical to
    :func:`matrix_from_pairs_loop`, whose row-major order the mask
    indexing reproduces)."""
    out = np.full((N, N), diag, np.float64)
    out[~np.eye(N, dtype=bool)] = np.asarray(vals, np.float64)
    return out


# ----------------------------------------------------------------------
# jit-able forest inference over the complete-binary-tree layout
# ----------------------------------------------------------------------
def forest_predict_jnp(feat: jax.Array, thr: jax.Array, leaf: jax.Array,
                       X: jax.Array, depth: int) -> jax.Array:
    """feat [T, 2^d-1] int32, thr [T, 2^d-1] f32, leaf [T, 2^d] f32,
    X [n, F] -> [n] predictions. `depth` gather steps, no control flow."""
    T = feat.shape[0]
    n = X.shape[0]
    node = jnp.zeros((T, n), jnp.int32)
    tidx = jnp.arange(T)[:, None]
    for _ in range(depth):
        f = feat[tidx, node]                      # [T,n]
        t = thr[tidx, node]
        fx = jnp.where(f < 0, 0, f)
        xv = jnp.take_along_axis(
            jnp.broadcast_to(X.T[None], (T,) + X.T.shape),
            fx[:, None, :], axis=1)[:, 0, :]
        go_right = xv > t
        node = 2 * node + 1 + go_right.astype(jnp.int32)
    leaf_idx = node - (2 ** depth - 1)
    vals = jnp.take_along_axis(leaf, leaf_idx, axis=1)
    return jnp.mean(vals, axis=0)


@dataclass
class BwPredictor:
    """End-to-end: snapshot features -> predicted runtime BW matrix."""
    forest: RandomForest

    def predict_matrix(self, n_dcs: int, snap_bw: np.ndarray,
                       mem_util: np.ndarray, cpu_load: np.ndarray,
                       retrans: np.ndarray, dist: np.ndarray,
                       intra_dc_bw: float = 10000.0,
                       backend: str = "numpy") -> np.ndarray:
        """Snapshot features -> predicted runtime BW matrix [N,N]
        (floored at 1 Mbps, `intra_dc_bw` on the diagonal); `backend`
        picks numpy / jnp / pallas inference."""
        X = assemble_features(n_dcs, snap_bw, mem_util, cpu_load,
                              retrans, dist)
        if backend == "numpy":
            vals = self.forest.predict(X)
        elif backend == "jnp":
            f, t, l = self.forest.packed()
            vals = np.asarray(forest_predict_jnp(
                jnp.asarray(f), jnp.asarray(t), jnp.asarray(l),
                jnp.asarray(X), self.forest.depth))
        elif backend == "pallas":
            from repro.kernels import ops
            f, t, l = self.forest.packed()
            vals = np.asarray(ops.rf_predict(
                jnp.asarray(f), jnp.asarray(t), jnp.asarray(l),
                jnp.asarray(X), depth=self.forest.depth))
        else:
            raise ValueError(backend)
        vals = np.maximum(vals, 1.0)             # BW is positive
        return matrix_from_pairs(vals, snap_bw.shape[0], diag=intra_dc_bw)


@dataclass
class SnapshotPredictor:
    """No-RF ablation backend: trust the 1-second snapshot as-is (the
    paper's no-prediction baseline). Drop-in for :class:`BwPredictor`
    wherever training a forest is overkill — controller tests,
    lightweight serve-side control planes."""

    def predict_matrix(self, n_dcs: int, snap_bw: np.ndarray,
                       mem_util: np.ndarray, cpu_load: np.ndarray,
                       retrans: np.ndarray, dist: np.ndarray,
                       intra_dc_bw: float = 10000.0,
                       backend: str = "numpy") -> np.ndarray:
        """Return the snapshot itself as the 'prediction' (`backend`
        is accepted for interface parity and ignored)."""
        out = np.maximum(np.asarray(snap_bw, np.float64).copy(), 1.0)
        np.fill_diagonal(out, intra_dc_bw)
        return out
