"""One fused fleet tick — the whole arbitrated closed loop as ONE jit
program, scanned over steps and vmapped over scenario grids.

The sequential :meth:`FleetController.tick` already batches the two
array-heavy stages (one RF launch, one fleet-wide water-fill), but the
glue between them — per-job Algorithm-1 relations, Eq. 2-3 connection
ranges, the budget/capacity arbitration, AIMD — runs as Python between
kernel launches, so a thousand-step scenario sweep pays interpreter
overhead per job per tick. This module expresses the ENTIRE tick as a
single traced program over stacked job tensors:

  stacked snapshot capture (one batched water-fill credits every
  tenant) -> Table-3 feature rows -> stacked RF predict (the exact
  one-hot Pallas kernel `rf_predict_pallas`, the one the sequential
  tick launches) -> Algorithm-1 relations -> Eq. 2-3 ranges +
  §3.2.2 throttle -> priority-weighted budget split & link shares ->
  AIMD clamp -> register -> ONE fleet water-fill with per-tenant
  crediting

`lax.scan` drives T ticks in one launch (`FusedFleet.run`), and
`jax.vmap` over precomputed WAN schedules sweeps B scenario variants
x T steps in one launch (`FusedFleet.sweep`) — the monitoring-cost
story of §3.2 at fleet scale: the control loop is only worth running
at high frequency if a tick is nearly free.

Determinism contract: the fused program reproduces the sequential tick
on a DETERMINISTIC simulator — ``fluct_sigma`` may be nonzero (the
AR(1) draws are consumed while precomputing the schedule, exactly as
``sim.advance`` would), but ``snapshot_sigma`` and ``host_sigma`` must
be 0 so captures draw no observation/host noise. Under that contract
`tests/test_fused_tick.py` pins fused == sequential per-tick integer
connection totals and budgets exactly and achieved BW to roundoff.
The numpy path stays the repo's byte-identical default; the fused
engine is the opt-in fast path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.local_opt import SIGNIFICANT_MBPS
from repro.kernels.rf_predict import SAMPLE_BLOCK, rf_predict_pallas
from repro.kernels.waterfill import fill_rates_loop
from repro.scenarios.events import (CrossTraffic, DiurnalCycle, LinkDegrade,
                                    LinkRestore, Timed)
from repro.wan.topology import INTRA_DC_BW

D_DEFAULT = 100.0          # Algorithm-1 minimum significant BW difference

# WAN-state events a fused schedule can replay (job churn / priority
# shifts change the stacked tensor shapes and are rejected)
SCHEDULE_EVENTS = (LinkDegrade, LinkRestore, CrossTraffic, DiurnalCycle)


# ----------------------------------------------------------------------
# jax ports of the per-tick Python stages (all float64 under x64)
# ----------------------------------------------------------------------
def relations_jnp(bw: jax.Array, D: float) -> jax.Array:
    """Algorithm 1 (INFER_DC_RELATIONS) as fixed-shape array ops.

    The reverse-traversal unique filter keeps value v[k] iff it is the
    smallest unique value or sits >= D above its ORIGINAL sorted-unique
    neighbour (deleting an entry never changes later comparisons), so
    the data-dependent Python loop collapses to one mask; closeness
    lookup is a searchsorted into the kept values padded with +inf.
    Matches `repro.core.relations.infer_dc_relations` exactly.
    """
    n = bw.shape[0]
    v = jnp.sort(bw.reshape(-1))
    k_tot = v.shape[0]
    first = jnp.arange(k_tot) == 0
    prev = jnp.concatenate([v[:1], v[:-1]])
    uniq = first | (v != prev)
    keep = uniq & (first | (v - prev >= D))
    kv = jnp.sort(jnp.where(keep, v, jnp.inf))
    n_u = keep.sum()
    val = bw.reshape(-1)
    k = jnp.searchsorted(kv, val)
    found = (k < n_u) & (kv[jnp.clip(k, 0, k_tot - 1)] == val)
    lo = jnp.maximum(k - 1, 0)
    hi = jnp.minimum(k, n_u - 1)
    pick = jnp.where(jnp.abs(val - kv[lo]) <= jnp.abs(kv[hi] - val), lo, hi)
    rel = jnp.where(found, n_u - k, n_u - pick).reshape(n, n)
    return jnp.where(jnp.eye(n, dtype=bool), 1, rel).astype(jnp.int32)


def forest_rows(feat: jax.Array, thr: jax.Array, leaf: jax.Array,
                X: jax.Array, depth: int) -> jax.Array:
    """The tick's stacked RF predict: float32 rows [R, 6] -> [R], before
    the 1 Mbps floor. The Pallas kernel's row block is R rounded up to
    whole sublanes (at most one `SAMPLE_BLOCK`), so a fleet's rows fill
    one grid cell; under `jax.vmap` each variant becomes a grid cell of
    its own. Each row's sum over trees runs in the order the sequential
    tick's `BatchedRfPredictor` runs it, so both predict the same bits."""
    block = min(-(-X.shape[0] // 8) * 8, SAMPLE_BLOCK)
    return rf_predict_pallas(feat, thr, leaf, X, depth=depth, block=block)


def global_ranges_jnp(bw: jax.Array, M: jax.Array, ws_pair: jax.Array,
                      link_cap: jax.Array, D: float = D_DEFAULT
                      ) -> Dict[str, jax.Array]:
    """Eq. 2-3 connection ranges + §3.2.2 throttle as a traced program
    (the `global_optimize` fleet path: no provider refactor, skew pair
    weights precomputed, arbitrated `link_cap` joins the throttle)."""
    n = bw.shape[0]
    eye = jnp.eye(n, dtype=bool)
    off = ~eye
    rel = relations_jnp(bw, D).astype(bw.dtype)
    M = M.astype(bw.dtype)

    sum_all = rel.sum() - n                        # skip closeness-1 diag
    max_r = rel.max(axis=1)
    min_cons = jnp.maximum(jnp.floor(rel / sum_all * (M - 1)), 1.0) * ws_pair
    max_cons = jnp.ceil(M * rel / max_r[:, None]) * ws_pair
    min_cons = jnp.where(eye, 1.0, min_cons)
    max_cons = jnp.where(eye, 1.0, max_cons)
    min_cons = jnp.clip(jnp.round(min_cons), 1, 2 * M)
    max_cons = jnp.clip(jnp.round(max_cons), 1, 2 * M)
    max_cons = jnp.maximum(max_cons, min_cons)

    capped = jnp.isfinite(link_cap) & off
    cap_cons = jnp.ceil(link_cap / jnp.maximum(bw, 1e-9))
    cap_cons = jnp.maximum(jnp.where(capped, cap_cons, max_cons), 1)
    cap_cons = jnp.minimum(cap_cons, 2 * M)
    max_cons = jnp.maximum(jnp.minimum(max_cons, cap_cons), 1)
    min_cons = jnp.minimum(min_cons, max_cons)

    min_bw = bw * min_cons
    max_bw = bw * max_cons
    T = jnp.where(off, max_bw, 0.0).sum(axis=1) / (n - 1)
    throttle = jnp.where(off & (max_bw > T[:, None]), T[:, None], jnp.inf)
    throttle = jnp.where(off, jnp.minimum(throttle, link_cap), throttle)
    return {"min_cons": min_cons.astype(jnp.int32),
            "max_cons": max_cons.astype(jnp.int32),
            "min_bw": min_bw, "max_bw": max_bw,
            "unit_bw": bw, "throttle": throttle}


def split_budget_jnp(m_total: int, w: jax.Array, present: jax.Array
                     ) -> jax.Array:
    """Masked port of `core.global_opt.split_budget`: largest-remainder
    shares of `m_total` over the PRESENT jobs (floor 1, repayment of
    floor bumps); absent jobs return `m_total` so a min-reduction over
    DCs ignores them."""
    n_present = present.sum()
    wp = jnp.where(present, jnp.maximum(w, 1e-9), 0.0)
    quota = jnp.where(present,
                      m_total * wp / jnp.maximum(wp.sum(), 1e-300), 0.0)
    share = jnp.floor(quota)
    # absent jobs rank last (frac -1) so floor bumps stay with the
    # present; stable argsort ties break toward the earlier tenant
    frac = jnp.where(present, quota - share, -1.0)
    order = jnp.argsort(-frac, stable=True)
    rank = jnp.argsort(order)
    leftover = m_total - share.sum()
    share = share + (rank < leftover)
    share = jnp.where(present, jnp.maximum(share, 1.0), 0.0)

    def cond(s):
        over = jnp.where(present, s, 0.0).sum() > m_total
        return over & (jnp.max(jnp.where(present, s, 0.0)) > 1)

    def body(s):
        rich = jnp.argmax(jnp.where(present, s, -1.0))
        return s.at[rich].add(-1.0)

    share = lax.while_loop(cond, body, share)
    share = jnp.where(m_total <= n_present, 1.0, share)
    return jnp.where(present, share, float(m_total))


def connection_budgets_jnp(presence: jax.Array, weights: jax.Array,
                           m_total: int) -> jax.Array:
    """Per-job scalar budgets [J]: min over the job's DCs of its
    largest-remainder share at that DC (`fleet.arbiter` port)."""
    shares = jax.vmap(lambda p: split_budget_jnp(m_total, weights, p))(
        presence.T)                                        # [N,J]
    budgets = jnp.minimum(shares.min(axis=0), float(m_total))
    return jnp.maximum(budgets, 1.0)


def link_shares_jnp(presence: jax.Array, weights: jax.Array,
                    cap_est: jax.Array) -> jax.Array:
    """Per-job per-link caps [J,N,N] (`fleet.arbiter.link_shares`
    port): pairs contended by >1 job split `cap_est` by priority
    weight; sole-tenant and unused pairs stay uncapped."""
    pres = presence.astype(cap_est.dtype)                  # [J,N]
    wpres = weights[:, None] * pres
    weight_sum = jnp.einsum("ja,jb->ab", wpres, pres)
    count = jnp.einsum("ja,jb->ab", pres, pres)
    on_pair = pres[:, :, None] * pres[:, None, :] > 0      # [J,N,N]
    mask = (count > 1)[None] & on_pair
    split = cap_est[None] * weights[:, None, None] \
        / jnp.maximum(weight_sum, 1e-12)[None]
    return jnp.where(mask, split, jnp.inf)


def aimd_step_jnp(cons: jax.Array, target: jax.Array,
                  ranges: Dict[str, jax.Array], monitored: jax.Array,
                  delta: float = SIGNIFICANT_MBPS
                  ) -> Tuple[jax.Array, jax.Array]:
    """`AimdAgent.step` for every source row at once ([..., P, P]
    elementwise; the diagonal — each agent's own DC — is untouched)."""
    n = cons.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    cap = jnp.minimum(ranges["max_bw"], ranges["throttle"])
    dec = monitored < target - delta
    inc = jnp.abs(monitored - target) <= delta
    new_cons = jnp.where(
        dec, jnp.maximum(ranges["min_cons"], cons // 2),
        jnp.where(inc, jnp.minimum(ranges["max_cons"], cons + 1), cons))
    new_t = jnp.where(
        dec, jnp.maximum(ranges["min_bw"], target / 2),
        jnp.where(inc, jnp.minimum(cap, target + ranges["unit_bw"]),
                  target))
    new_t = jnp.clip(new_t, ranges["min_bw"], cap)
    return (jnp.where(eye, cons, new_cons),
            jnp.where(eye, target, new_t))


# ----------------------------------------------------------------------
# WAN schedule precomputation (the numpy side of the contract)
# ----------------------------------------------------------------------
class _ScheduleShim:
    """The tiny engine surface WAN events mutate while a schedule is
    precomputed (`event.apply(eng)` wants `.sim`, `.link`, `.diurnal`,
    `.step`)."""

    def __init__(self, sim):
        self.sim = sim
        self.diurnal: Optional[Tuple[float, int, int]] = None
        self.step = 0

    def link(self, pair: Sequence[str]) -> Tuple[int, int]:
        """Resolve a (region, region) pair to simulator indices."""
        a, b = pair
        return self.sim.regions.index(a), self.sim.regions.index(b)


def make_schedule(sim, steps: int, events: Tuple[Timed, ...] = ()
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Precompute the WAN inputs of `steps` fused ticks:
    ``(single[T,N,N], background[T,N,N])``.

    MUTATES `sim` exactly as `steps` sequential fleet ticks would
    (events applied at their step, diurnal modulation, one
    ``advance()`` per tick), so a `FusedFleet.run` leaves the shared
    simulator where the sequential engine would have left it and
    sequential ticks can continue afterwards. Only WAN-state events
    (`SCHEDULE_EVENTS`) are accepted — job churn changes tensor shapes.
    """
    import math
    shim = _ScheduleShim(sim)
    timeline: Dict[int, List[Timed]] = {}
    for t in events:
        if not isinstance(t.event, SCHEDULE_EVENTS):
            raise ValueError(
                f"{type(t.event).__name__} is not replayable in a fused "
                f"schedule; accepted: "
                f"{[e.__name__ for e in SCHEDULE_EVENTS]}")
        if getattr(t.event, "notify", False):
            raise ValueError("notify=True is a single-job-engine concept")
        timeline.setdefault(t.step, []).append(t)
    n = sim.N
    single = np.empty((steps, n, n))
    bg = np.zeros((steps, n, n))
    for k in range(steps):
        shim.step = k
        for t in timeline.get(k, ()):
            t.event.apply(shim)
        if shim.diurnal is not None:
            amp, period, start = shim.diurnal
            phase = 2.0 * math.pi * (k - start) / max(period, 1)
            sim.modulation = 1.0 + amp * math.sin(phase)
        sim.advance()
        single[k] = sim.link_bw_now()
        if sim.background_conns is not None:
            b = np.asarray(sim.background_conns, np.float64).copy()
            np.fill_diagonal(b, 0.0)
            bg[k] = np.maximum(b, 0.0)
    return single, bg


# ----------------------------------------------------------------------
# The fused engine
# ----------------------------------------------------------------------
@dataclass
class FusedState:
    """The persistent cross-tick state: each job's in-force connection
    matrix and AIMD target BW at slice scale."""
    cons: np.ndarray          # [J,P,P] int32
    target: np.ndarray        # [J,P,P] float64


class FusedFleet:
    """A :class:`FleetController`'s job set compiled into one tick
    program (see module docstring for the determinism contract)."""

    def __init__(self, fleet):
        """Snapshot the fleet's static spec and live AIMD state.
        Requires a deterministic capture path (``snapshot_sigma == 0``,
        ``host_sigma == 0``), a fixed job set with equal slice sizes,
        and no attached deferred planners (their `search_many` flush is
        host-side Python)."""
        sim = fleet.sim
        if sim.snapshot_sigma != 0 or sim.host_sigma != 0:
            raise ValueError(
                "fused ticks need a deterministic capture path: build "
                "the simulator with snapshot_sigma=0 and host_sigma=0")
        if fleet._planners:
            raise ValueError("fused ticks do not flush deferred "
                             "placement planners; detach them first")
        jobs = list(fleet.jobs.values())
        if not jobs:
            raise ValueError("fused fleet needs at least one job")
        sizes = {len(j.spec.dcs) for j in jobs}
        if len(sizes) != 1:
            raise ValueError(f"fused fleet needs equal slice sizes, "
                             f"got {sorted(sizes)}")
        self.fleet = fleet
        self.sim = sim
        self.jobs = jobs
        self.J = len(jobs)
        self.N = sim.N
        self.P = sizes.pop()
        self.m_total = int(fleet.m_total)
        self.ix = np.stack([np.asarray(j.spec.dcs, np.int64)
                            for j in jobs])                # [J,P]
        self.presence = np.zeros((self.J, self.N), bool)
        for j, row in enumerate(self.ix):
            self.presence[j, row] = True
        self.priorities = np.array([max(j.priority, 1e-9) for j in jobs])
        # §3.3.1 pair weights, precomputed numpy-side for exact parity
        from repro.core.global_opt import _pair_weights
        self.ws_pair = np.stack([
            _pair_weights(self.P, j.skew()) for j in jobs])  # [J,P,P]
        self.dists = np.stack([sim.dist[np.ix_(r, r)] for r in self.ix])
        forest = fleet.predictor.forest
        f, t, l = forest.packed()
        self._forest = (jnp.asarray(f), jnp.asarray(t), jnp.asarray(l))
        self._depth = forest.depth
        self._tick_fn = None
        self._scan_cache: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    def state(self) -> FusedState:
        """Read the live controllers' AIMD state into stacked tensors."""
        cons = np.zeros((self.J, self.P, self.P), np.int32)
        target = np.zeros((self.J, self.P, self.P))
        for j, job in enumerate(self.jobs):
            cons[j] = job.controller.current_conns().astype(np.int32)
            for i, ag in enumerate(job.controller._agents):
                target[j, i] = ag.target_bw
        return FusedState(cons=cons, target=target)

    # ------------------------------------------------------------------
    def _build_tick(self):
        """Trace-time closure: one full arbitrated tick, stacked over
        jobs. Inputs `(carry, (single, bg))`; outputs per-tick stats
        plus the ranges needed to sync agents back after a run."""
        J, P, N = self.J, self.P, self.N
        ix = jnp.asarray(self.ix)
        jidx = jnp.arange(J)
        idx_i, idx_j = np.nonzero(~np.eye(P, dtype=bool))   # static
        n_pairs = len(idx_i)
        eye_p = jnp.eye(P, dtype=bool)
        off_p = ~eye_p
        eye_n = jnp.eye(N, dtype=bool)
        off_n = ~eye_n
        presence = jnp.asarray(self.presence)
        weights = jnp.asarray(self.priorities)
        ws_pair = jnp.asarray(self.ws_pair)
        dists = jnp.asarray(self.dists)
        knee = float(self.sim.knee)
        m_total = self.m_total
        vms = self.sim.vms_per_dc if self.sim.vms_per_dc is not None \
            else np.ones(N)
        egress = jnp.asarray(self.sim.nic_cap * np.asarray(vms, float))
        ingress = egress
        w_rtt = jnp.asarray(np.asarray(self.sim.rtt_weight()))
        feat, thr, leaf = self._forest
        depth = self._depth

        def embed(mats):
            """[J,P,P] -> [J,N,N] (zero elsewhere, diagonal zeroed)."""
            m = jnp.where(off_p, mats, 0.0)
            return jnp.zeros((J, N, N), mats.dtype).at[
                jidx[:, None, None], ix[:, :, None], ix[:, None, :]].set(m)

        def extract(full):
            """[N,N] or [J,N,N] -> [J,P,P] per-job slices."""
            if full.ndim == 2:
                full = jnp.broadcast_to(full, (J, N, N))
            return full[jidx[:, None, None], ix[:, :, None], ix[:, None, :]]

        def fill(aggregates, single):
            """Batched water-fill at this step's link state."""
            b = aggregates.shape[0]
            sb = jnp.broadcast_to(single, (b, N, N))
            rate, iters, ok = fill_rates_loop(
                aggregates, sb, jnp.broadcast_to(egress, (b, N)),
                jnp.broadcast_to(ingress, (b, N)), w_rtt, sb * knee)
            return rate, iters, ok

        def tick(carry, x):
            cons, target = carry                  # [J,P,P] int32/f64
            single, bg = x                        # [N,N]
            reg = embed(cons.astype(single.dtype))            # [J,N,N]
            total = reg.sum(0) + bg

            # probe (capacity estimate) + capture fills share a launch
            ones_off = jnp.where(off_n, 1.0, 0.0)
            rate2, it2, ok2 = fill(
                jnp.stack([ones_off + total, total]), single)
            probe_bw = jnp.where(eye_n, INTRA_DC_BW, rate2[0] * ones_off)
            cap_est = probe_bw * knee

            # arbitration: budgets + per-link caps at slice scale
            budgets = connection_budgets_jnp(presence, weights, m_total)
            caps = link_shares_jnp(presence, weights, cap_est)
            env_cap = extract(caps)                           # [J,P,P]

            # capture: per-tenant credited snapshot at in-force conns
            snap = extract(jnp.where(eye_n, INTRA_DC_BW, rate2[1] * reg))

            # deterministic Table-3 host metrics (host_sigma == 0)
            c_off = jnp.where(off_p, cons.astype(single.dtype), 0.0)
            mem = jnp.clip(0.15 + 0.02 * c_off.sum(-2), 0.05, 0.98)
            cpu = jnp.clip(0.10 + 0.015 * c_off.sum(-1), 0.02, 0.98)
            solo = extract(single)
            squeeze = jnp.maximum(
                0.0, 1.0 - snap / jnp.maximum(solo * c_off, 1e-9))
            retr = jnp.where(off_p, jnp.round(squeeze * 40.0), 0.0)

            # stacked RF predict: one forest pass for the whole fleet
            X = jnp.stack([
                jnp.full((J, n_pairs), float(P), single.dtype),
                snap[:, idx_i, idx_j], mem[:, idx_j], cpu[:, idx_i],
                retr[:, idx_i, idx_j], dists[:, idx_i, idx_j],
            ], axis=-1).reshape(J * n_pairs, 6).astype(jnp.float32)
            vals = forest_rows(feat, thr, leaf, X, depth)
            vals = jnp.maximum(vals.astype(single.dtype), 1.0)
            pred = jnp.full((J, P, P), INTRA_DC_BW, single.dtype).at[
                :, idx_i, idx_j].set(vals.reshape(J, n_pairs))

            # Eq. 2-3 ranges inside each job's envelope, then AIMD
            ranges = jax.vmap(
                lambda bw_j, m_j, ws_j, lc_j:
                global_ranges_jnp(bw_j, m_j, ws_j, lc_j))(
                    pred, budgets, ws_pair, env_cap)
            new_cons, new_target = aimd_step_jnp(cons, target, ranges,
                                                 snap)

            # register + ONE fleet fill, credited and envelope-clamped
            reg_new = embed(new_cons.astype(single.dtype))
            rate1, it1, ok1 = fill((reg_new.sum(0) + bg)[None], single)
            ach = extract(jnp.where(eye_n, INTRA_DC_BW, rate1[0] * reg_new))
            ach = jnp.where(off_p, jnp.minimum(ach, env_cap), ach)

            ach_off = ach[:, idx_i, idx_j]
            out = {
                "achieved_min": ach_off.min(-1),
                "achieved_mean": ach_off.mean(-1),
                "conns_total": new_cons[:, idx_i, idx_j].sum(-1),
                "budget": budgets,
                "cap_min": env_cap[:, idx_i, idx_j].min(-1),
                "fill_iters": jnp.concatenate([it2, it1]),
                "converged": jnp.all(ok2) & jnp.all(ok1),
                "ranges": ranges,
                "pred": pred,
                "env_cap": env_cap,
            }
            return (new_cons, new_target), out

        return tick

    def _scan_fn(self, detail: bool):
        """jit'd `(carry0, singles, bgs) -> (carry, outs)` over T steps
        (`detail=False` drops the per-tick ranges/pred tensors — the
        shape the B-scenario sweep vmaps)."""
        key = bool(detail)
        if key in self._scan_cache:
            return self._scan_cache[key]
        tick = self._tick_fn or self._build_tick()
        self._tick_fn = tick

        def step(carry, x):
            carry, out = tick(carry, x)
            if not detail:
                out = {k: v for k, v in out.items()
                       if k not in ("ranges", "pred", "env_cap")}
            return carry, out

        fn = jax.jit(lambda carry, singles, bgs:
                     lax.scan(step, carry, (singles, bgs)))
        self._scan_cache[key] = fn
        return fn

    def _sweep_fn(self):
        """jit'd `(carry0, singles[B,T], bgs[B,T]) -> (carry, outs)`: the
        per-tick scan vmapped over B variants from one shared state."""
        if "sweep" not in self._scan_cache:
            self._scan_cache["sweep"] = jax.jit(jax.vmap(
                self._scan_fn(detail=False), in_axes=(None, 0, 0)))
        return self._scan_cache["sweep"]

    # ------------------------------------------------------------------
    def run(self, steps: int, events: Tuple[Timed, ...] = ()
            ) -> List[Dict[str, Any]]:
        """Run `steps` arbitration epochs in ONE scanned launch, sync
        the resulting AIMD state back into the live controllers (so
        sequential ticks can continue), and return per-tick records
        (the fleet-trace row body minus plan signatures, which are a
        host-side concept)."""
        single, bg = make_schedule(self.sim, steps, events)
        st = self.state()
        with jax.enable_x64(True):
            (cons, target), outs = self._scan_fn(detail=True)(
                (jnp.asarray(st.cons), jnp.asarray(st.target)),
                jnp.asarray(single), jnp.asarray(bg))
        outs = jax.tree_util.tree_map(np.asarray, outs)
        if not outs["converged"].all():
            from repro.wan.simulator import WaterfillDivergence
            conv = np.asarray(outs["converged"]).reshape(-1)
            bad = int(np.argmax(~conv))
            raise WaterfillDivergence(
                f"a fused-tick water-fill hit its iteration bound at "
                f"tick {bad + 1} of {len(conv)}")
        self._sync_back(np.asarray(cons), np.asarray(target), outs)
        return self._records(steps, outs)

    def sweep(self, singles: np.ndarray, bgs: np.ndarray
              ) -> Dict[str, np.ndarray]:
        """Sweep B scenario variants x T steps in ONE launch from the
        CURRENT fleet state (vmapped scan; state is not written back —
        a sweep is analysis, not execution). `singles`/`bgs`:
        [B,T,N,N] schedules from :func:`make_schedule` over variant
        simulators. Returns stacked per-tick stats [B,T,...]."""
        st = self.state()
        with jax.enable_x64(True):
            _, outs = self._sweep_fn()(
                (jnp.asarray(st.cons), jnp.asarray(st.target)),
                jnp.asarray(singles), jnp.asarray(bgs))
        return jax.tree_util.tree_map(np.asarray, outs)

    # ------------------------------------------------------------------
    def _sync_back(self, cons: np.ndarray, target: np.ndarray,
                   outs: Dict[str, Any]) -> None:
        """Install the post-run state into the live fleet: agent conns
        and targets, the final tick's Eq. 2-3 bounds, registered flows,
        and each job's last arbitrated envelope."""
        from repro.control import BudgetEnvelope
        ranges = outs["ranges"]
        for j, job in enumerate(self.jobs):
            ctl = job.controller
            for i, ag in enumerate(ctl._agents):
                ag.cons = cons[j, i].astype(np.int64)
                ag.target_bw = target[j, i].astype(np.float64)
                ag.min_cons = ranges["min_cons"][-1, j, i].astype(np.int64)
                ag.max_cons = ranges["max_cons"][-1, j, i].astype(np.int64)
                ag.min_bw = ranges["min_bw"][-1, j, i]
                ag.max_bw = ranges["max_bw"][-1, j, i]
                ag.unit_bw = ranges["unit_bw"][-1, j, i]
                ag.throttle = ranges["throttle"][-1, j, i]
            ctl.set_envelope(BudgetEnvelope(
                max_conns=int(outs["budget"][-1, j]),
                link_cap=np.asarray(outs["env_cap"][-1, j], np.float64)))
            job.view.register(ctl.current_conns())
        self.fleet.tick_count += len(outs["budget"])

    def _records(self, steps: int, outs: Dict[str, Any]
                 ) -> List[Dict[str, Any]]:
        """Per-tick record dicts compatible with the sequential tick's
        row body (minus `plan_sig`/`kernel_calls`)."""
        base = self.fleet.tick_count - steps
        recs = []
        for t in range(steps):
            rows = [{
                "name": job.name,
                "priority": float(self.priorities[j]),
                "budget": int(outs["budget"][t, j]),
                "cap_min": float(outs["cap_min"][t, j]),
                "achieved_min": float(outs["achieved_min"][t, j]),
                "achieved_mean": float(outs["achieved_mean"][t, j]),
                "conns_total": int(outs["conns_total"][t, j]),
            } for j, job in enumerate(self.jobs)]
            recs.append({"tick": base + t + 1, "n_jobs": self.J,
                         "fill_iters": outs["fill_iters"][t].tolist(),
                         "jobs": rows})
        return recs
