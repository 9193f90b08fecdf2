"""JAX backend for the progressive water-fill rate solver.

`repro.wan.simulator.WanSimulator._fill_rates` is the repo's ground-
truth contention model: RTT-biased weighted max-min filling where each
iteration raises every unfrozen pair's per-connection rate along a
shared fill level until some constraint (single-connection ceiling,
parallelism-knee path cap, NIC egress/ingress) binds, then freezes the
binding pairs. The numpy loop is exact but runs one Python iteration
per freeze event — the interpreter cost the fused fleet tick cannot
afford at 100+ jobs x thousand-step scenario sweeps.

This module is the same algorithm as a fixed-bound `lax.while_loop`
over `[B, N, N]` AGGREGATE-connection tensors:

  * the freeze/increment loop becomes mask updates — `frozen`, the
    per-batch `done` flag, and the stall exit are all boolean tensors,
    so one program serves any batch of fills (a fleet tick's probe /
    capture / achieved fills, a scenario grid's B variants);
  * every iteration freezes at least one pair or stalls, so the loop
    provably terminates within ``8 * N * N`` iterations; the actual
    per-fill iteration count and a convergence flag are returned so a
    non-converging fill FAILS LOUDLY instead of returning partial
    rates (mirroring the simulator's `last_fill_iters` contract);
  * arithmetic is float64 under `jax.enable_x64`, so
    rates match the numpy reference to roundoff (the hypothesis
    property in tests/test_waterfill_kernel.py pins atol/rtol);

`fill_rates_loop` is the raw traced function — embed it inside larger
jit programs (the fused fleet tick in `repro.fleet.fused` scans it).
`fill_rates` is the numpy-in/numpy-out wrapper the simulator's
``REPRO_WATERFILL_BACKEND=jax`` dispatch calls; the numpy loop stays
the bit-exact default (all trace goldens are pinned on it).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

EPS_DEN = 1e-12          # weight-denominator clip (matches numpy)
EPS_INC = 1e-9           # smallest meaningful fill-level increment
EPS_SAT = 1e-6           # constraint-saturation slack


def max_fill_iters(n: int) -> int:
    """The provable iteration bound of the progressive fill: each
    iteration freezes >=1 of the N*(N-1) pairs or stalls; 8*N*N is the
    historical (very generous) cap the numpy loop used silently."""
    return 8 * n * n


def fill_rates_loop(c: jax.Array, single: jax.Array, egress: jax.Array,
                    ingress: jax.Array, w: jax.Array, path_cap: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched progressive filling as a traceable jax program.

    c / single / path_cap: [..., N, N] aggregate flow counts, single-
    connection BW, and per-pair path caps (knee x single, already
    min'd with any §3.2.2 throttle); egress / ingress: [..., N] NIC
    caps; w: [N, N] (or broadcastable) per-connection RTT weights.

    Returns ``(rate, iters, converged)``: per-connection rates
    [..., N, N], the per-batch iteration count [...], and a per-batch
    convergence flag [...] (False only if the ``8*N*N`` bound was hit
    with unfrozen pairs left — the caller should raise).
    """
    n = c.shape[-1]
    cap_iters = max_fill_iters(n)
    w = jnp.broadcast_to(w, c.shape)
    cw = c * w
    w_pos = w > 0
    cw_pos = cw > 0
    w_den = jnp.maximum(w, EPS_DEN)
    cw_den = jnp.maximum(cw, EPS_DEN)
    rate0 = jnp.zeros_like(c)
    frozen0 = c <= 0
    done0 = jnp.all(frozen0, axis=(-2, -1))
    iters0 = jnp.zeros(c.shape[:-2], jnp.int32)

    def cond(state):
        _, _, done, _, it = state
        return (it < cap_iters) & jnp.any(~done)

    def body(state):
        rate, frozen, done, iters, it = state
        act = (~frozen) & (~done)[..., None, None]
        cw_act = jnp.where(act, cw, 0.0)
        we = cw_act.sum(-1)                     # active weight per egress
        wi = cw_act.sum(-2)
        load = rate * c
        head_e = egress - load.sum(-1)
        head_i = ingress - load.sum(-2)
        inc_e = jnp.where(we > 0, head_e / jnp.maximum(we, EPS_DEN),
                          jnp.inf)
        inc_i = jnp.where(wi > 0, head_i / jnp.maximum(wi, EPS_DEN),
                          jnp.inf)
        # per-pair bounds in fill-level units (rate grows as t * w)
        inc_conn = jnp.where(act & w_pos, (single - rate) / w_den, jnp.inf)
        inc_path = jnp.where(act & cw_pos, (path_cap - load) / cw_den,
                             jnp.inf)
        inc_pair = jnp.minimum(inc_conn, inc_path)
        inc = jnp.minimum(jnp.minimum(inc_e.min(-1), inc_i.min(-1)),
                          inc_pair.min(axis=(-2, -1)))
        inc = jnp.where(jnp.isfinite(inc) & (inc >= EPS_INC), inc, 0.0)
        rate = jnp.where(act, rate + inc[..., None, None] * w, rate)
        load = rate * c
        hit = act & (((single - rate) < EPS_SAT) |
                     ((path_cap - load) < EPS_SAT))
        sat_e = (egress - load.sum(-1)) < EPS_SAT
        sat_i = (ingress - load.sum(-2)) < EPS_SAT
        hit = hit | (act & (sat_e[..., :, None] | sat_i[..., None, :]))
        frozen = frozen | hit
        stalled = (~jnp.any(hit, axis=(-2, -1))) & (inc == 0.0)
        iters = iters + (~done).astype(jnp.int32)
        done = done | jnp.all(frozen, axis=(-2, -1)) | stalled
        return rate, frozen, done, iters, it + 1

    rate, _, done, iters, _ = lax.while_loop(
        cond, body, (rate0, frozen0, done0, iters0, jnp.int32(0)))
    return rate, iters, done


_fill_jit = jax.jit(fill_rates_loop)


def fill_rates(c: np.ndarray, single: np.ndarray, egress: np.ndarray,
               ingress: np.ndarray, w: np.ndarray, path_cap: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Numpy-in/numpy-out batched fill on the jit path (float64).

    Accepts a single [N, N] fill or any batch [..., N, N]; one compiled
    program per (batch-shape, N). Returns numpy ``(rate, iters,
    converged)`` with the same leading shape.
    """
    with jax.enable_x64(True):
        rate, iters, ok = _fill_jit(
            jnp.asarray(c, jnp.float64), jnp.asarray(single, jnp.float64),
            jnp.asarray(egress, jnp.float64),
            jnp.asarray(ingress, jnp.float64),
            jnp.asarray(w, jnp.float64),
            jnp.asarray(path_cap, jnp.float64))
    return (np.asarray(rate, np.float64), np.asarray(iters),
            np.asarray(ok))
