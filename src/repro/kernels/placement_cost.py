"""JAX backend for the batched placement-cost evaluator.

`repro.placement.cost.estimate_cost_batch` prices M candidate
placements in one array-program pass; this module is its accelerator
path, sitting next to `rf_predict` so placement search rides the same
launch style as RF prediction (`REPRO_PLACEMENT_BACKEND=jax` selects
it; the numpy path stays the bit-exact default).

The program is the same packed evaluation the numpy core runs —
einsum-style shuffle volumes ``vol[m,i,j] = held[m,i] * frac[m,j]``,
broadcast bottleneck max over off-diagonal pairs, per-source egress
pricing — jit-compiled under 64-bit mode (`jax.enable_x64`, so
magnitudes match the float64 reference; reductions may still differ in
the last ulp, which is why decisions — not raw metric bytes — are what
the cross-backend tests pin).

Launch shapes are BUCKETED like the controller's plan cache: the
candidate count M is padded up to a power-of-two bucket (min 64) with
copies of row 0, so a greedy search whose per-round move count drifts
by a few candidates reuses one compiled program per (bucket, S, N)
instead of recompiling every round. `compile_count()` exposes the
number of distinct traces for tests/benchmarks.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

_MIN_BUCKET = 64
_TRACES = 0


def bucket(m: int) -> int:
    """Pad a candidate count up to its power-of-two launch bucket."""
    return 1 << max(_MIN_BUCKET.bit_length() - 1, (m - 1).bit_length())


def compile_count() -> int:
    """How many distinct (bucket, S, N) shapes have been traced."""
    return _TRACES


def _eval_core(placements, bw, inputs, speed, price, out_ratio, comp_s,
               waves, rate):
    """The packed evaluator as a jax program (see the numpy reference
    `repro.placement.cost._eval_packed_numpy` for the contract)."""
    global _TRACES
    _TRACES += 1
    M, S, N = placements.shape
    bwc = jnp.maximum(bw, 1e-6)
    off = ~jnp.eye(N, dtype=bool)
    compute_s = waves[:, 0] * (inputs * comp_s[:, 0:1] / speed).max(axis=1)
    held = inputs * out_ratio[:, 0:1]
    net_s = jnp.zeros(1, placements.dtype)
    egress_gb = jnp.zeros(1, placements.dtype)
    egress_usd = jnp.zeros(1, placements.dtype)
    for k in range(1, S + 1):
        frac = placements[:, k - 1, :]
        vol = jnp.einsum("mi,mj->mij", held * jnp.ones_like(frac), frac)
        vol = jnp.where(off, vol, 0.0)
        t = jnp.where(off, vol * 1000.0 / bwc, -jnp.inf)
        st_net = waves[:, k] * t.max(axis=(1, 2))
        new_held = held.sum(axis=1)[:, None] * frac
        st_comp = waves[:, k] * (new_held * comp_s[:, k:k + 1]
                                 / speed).max(axis=1)
        st_gb = waves[:, k] * vol.reshape(M, -1).sum(axis=1) / 8.0
        st_usd = waves[:, k] * ((vol.sum(axis=2) / 8.0
                                 * price).sum(axis=1))
        net_s = net_s + st_net
        compute_s = compute_s + st_comp
        egress_gb = egress_gb + st_gb
        egress_usd = egress_usd + st_usd
        held = new_held * out_ratio[:, k:k + 1]
    makespan = jnp.broadcast_to(net_s + compute_s, (M,))
    instance = makespan / 3600.0 * N * rate
    bc = (makespan, net_s, compute_s, egress_gb, egress_usd, instance)
    return tuple(jnp.broadcast_to(a, (M,)) for a in bc)


_eval_jit = jax.jit(_eval_core)


def _pad_rows(a: np.ndarray, m_pad: int) -> np.ndarray:
    """Pad a per-candidate array out to the launch bucket with copies
    of row 0 (kept valid so padded rows run the same program)."""
    pad = m_pad - a.shape[0]
    if pad <= 0:
        return a
    return np.concatenate(
        [a, np.broadcast_to(a[:1], (pad,) + a.shape[1:])])


def eval_packed_jax(placements: np.ndarray, bw: np.ndarray,
                    inputs: np.ndarray, speed: np.ndarray,
                    price: np.ndarray, out_ratio: np.ndarray,
                    comp_s: np.ndarray, waves: np.ndarray,
                    instance_usd_per_hour) -> Tuple[np.ndarray, ...]:
    """Price a packed batch on the jit path; returns the six metric
    vectors ``(makespan_s, net_s, compute_s, egress_gb, egress_usd,
    instance_usd)``, each [M] float64, matching
    :class:`repro.placement.cost.PlacementCostBatch` field order.

    Shared inputs ([N]/[N,N]/[S+1]) ride along at broadcast size 1;
    per-candidate inputs ([M,...], the fused fleet path) are padded to
    the bucket alongside the placements.
    """
    M = placements.shape[0]
    m_pad = bucket(M)

    def lift(a: np.ndarray, per_cand_ndim: int) -> np.ndarray:
        a = np.asarray(a, np.float64)
        if a.ndim == per_cand_ndim:          # per-candidate: pad rows
            return _pad_rows(a, m_pad)
        return a[None]                       # shared: broadcast dim 1
    with jax.enable_x64(True):
        out = _eval_jit(
            _pad_rows(np.asarray(placements, np.float64), m_pad),
            lift(bw, 3), lift(inputs, 2), lift(speed, 2), lift(price, 2),
            lift(out_ratio, 2), lift(comp_s, 2), lift(waves, 2),
            jnp.float64(instance_usd_per_hour))
    return tuple(np.asarray(a, np.float64)[:M] for a in out)
