"""WANify cross-pod gradient synchronization.

The paper's all-to-all shuffle maps onto a DIRECT (flat) all-reduce over
the `pod` mesh axis: reduce-scatter + all-gather built from offset-phase
``lax.ppermute`` exchanges, so every pod-pair link carries traffic
simultaneously — exactly the contention regime WANify gauges. The
heterogeneous "parallel connections" become per-offset-class CHUNK
multiplicities: a phase whose links are weak is split into more
independently pipelined collective-permutes (more in-flight streams on
the weak link), and its payload is quantized to the bits the predicted
link BW affords (SAGQ analogue).

Must be called inside shard_map with the pod axis manual
(axis_names={"pod"}); data/model axes stay auto so XLA keeps each
transfer shard-local.

Offset classes: phase `o` exchanges pod i <-> pod (i+o)%P. On a
geo-ring of pods, offset correlates with distance, mirroring
Algorithm 1's closeness classes.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.control.schedule import offset_schedule, wire_decode, wire_encode
from repro.core.plan import WanPlan


def _permute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


# ----------------------------------------------------------------------
# Direct (flat) all-reduce with WANify schedule — per leaf
# ----------------------------------------------------------------------
def _pad_to(x: jax.Array, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, cfg)
    return x, pad


def _leaf_wan_allreduce(g: jax.Array, sched, P: int, axis: str,
                        rank: jax.Array, compress: bool) -> jax.Array:
    """Direct all-reduce of one gradient leaf over the pod axis.

    Segments along axis 0 (layer-stacked dim — unsharded within a pod,
    so slicing never reshards data/model)."""
    orig_shape, orig_dtype = g.shape, g.dtype
    if g.ndim == 0:
        g = g[None]
    cmax = max(ph["chunks"] for ph in sched) if sched else 1
    g, pad = _pad_to(g, P * cmax)
    seg = g.shape[0] // P

    def segment(x, idx):
        """Slice one chunk segment out of a flat leaf."""
        return jax.lax.dynamic_slice_in_dim(x, idx * seg, seg, axis=0)

    # ---- reduce-scatter: after this, every pod holds the reduced segment
    # for ALL indices it will later need? No — direct RS: pod r reduces
    # segment r. Phase o: send segment ((rank + o) % P) to pod rank+o.
    acc = segment(g, rank)                        # own contribution
    for ph in sched:
        o, chunks, bits = ph["offset"], ph["chunks"], ph["bits"]
        if not compress:
            bits = 32
        perm = [(i, (i + o) % P) for i in range(P)]
        dest_idx = (rank + o) % P
        payload = segment(g, dest_idx)
        parts = jnp.split(payload, chunks, axis=0) if chunks > 1 else [payload]
        recvd = []
        for part in parts:                        # parallel "connections"
            enc, scale = wire_encode(part, bits)
            enc_r = _permute(enc, axis, perm)
            scale_r = _permute(scale, axis, perm) if scale is not None else None
            recvd.append(wire_decode(enc_r, scale_r, g.dtype, bits))
        acc = acc + jnp.concatenate(recvd, axis=0) if chunks > 1 \
            else acc + recvd[0]

    # ---- all-gather: broadcast my reduced segment to every pod ---------
    gathered = {0: acc}                           # my own segment
    for ph in sched:
        o, chunks, bits = ph["offset"], ph["chunks"], ph["bits"]
        if not compress:
            bits = 32
        perm = [(i, (i + o) % P) for i in range(P)]
        parts = jnp.split(acc, chunks, axis=0) if chunks > 1 else [acc]
        recvd = []
        for part in parts:
            enc, scale = wire_encode(part, bits)
            enc_r = _permute(enc, axis, perm)
            scale_r = _permute(scale, axis, perm) if scale is not None else None
            recvd.append(wire_decode(enc_r, scale_r, g.dtype, bits))
        gathered[o] = jnp.concatenate(recvd, axis=0) if chunks > 1 else recvd[0]

    # Phase o delivered pod (rank-o)'s reduced segment, i.e. absolute
    # segment (rank-o) % P. Ordering [gathered[0], gathered[P-1], ...,
    # gathered[1]] lays segments out as [rank, rank+1, ..., rank+P-1];
    # a roll by rank*seg rotates them into absolute order.
    ordered = [gathered[0]] + [gathered[o] for o in range(P - 1, 0, -1)]
    out = jnp.concatenate(ordered, axis=0)
    out = jnp.roll(out, shift=rank * seg, axis=0)
    if pad:
        out = out[:orig_shape[0] if orig_shape else 1]
    out = out.reshape(orig_shape).astype(orig_dtype)
    return out


def wan_allreduce(tree: Any, plan: WanPlan, *, axis: str = "pod",
                  compress: bool = False, mean: bool = True) -> Any:
    """WANify-scheduled all-reduce of a pytree over the pod axis.
    Call inside shard_map(axis_names={axis})."""
    P = plan.n_pods
    if P <= 1:
        return tree
    sched = offset_schedule(plan)
    rank = jax.lax.axis_index(axis)
    scale = 1.0 / P if mean else 1.0

    def per_leaf(g):
        """Apply the phase schedule to one gradient leaf."""
        out = _leaf_wan_allreduce(g, sched, P, axis, rank, compress)
        return out * scale if mean else out

    return jax.tree.map(per_leaf, tree)


def psum_allreduce(tree: Any, *, axis: str = "pod", mean: bool = True) -> Any:
    """Baseline: XLA's own all-reduce (single logical connection — the
    paper's 'vanilla' transfer)."""
    n = jax.lax.axis_size(axis)

    def per_leaf(g):
        """Apply the phase schedule to one gradient leaf."""
        s = jax.lax.psum(g, axis)
        return s / n if mean else s

    return jax.tree.map(per_leaf, tree)


# ======================================================================
# BATCHED (vmap-over-pods) formulation — no manual mesh axes.
#
# Gradients carry an explicit leading pod dim sharded over "pod";
# jnp.roll along that dim lowers to collective-permute, so the offset-
# phase schedule below emits exactly the same wire pattern as the
# shard_map version. The Trainer uses this form, so the whole step stays
# one auto-sharded program with no manual mesh axes.
# ======================================================================
def wan_allreduce_batched(tree: Any, plan: WanPlan, *,
                          compress: bool = False, mean: bool = True) -> Any:
    """tree leaves: [P, ...] per-pod values (dim 0 sharded over pod).
    Returns the synchronized tree, every pod slice holding the sum/mean.

    Direct exchange: phase o rolls pod p's contribution to pod p+o —
    every pod-pair link is active simultaneously (the paper's all-to-all
    shuffle regime). Per-offset chunk multiplicity + wire bits implement
    the heterogeneous parallel connections / SAGQ compression."""
    P = plan.n_pods
    if P <= 1:
        return tree
    sched = offset_schedule(plan)
    out_scale = 1.0 / P if mean else 1.0

    def per_leaf(g):
        """Apply the phase schedule to one gradient leaf."""
        # f32 accumulation only when lossy wire compression is active;
        # a blanket f32 copy of 236B-scale grads costs GiBs of HBM
        any_lossy = compress and any(ph["bits"] < 32 for ph in sched)
        acc = g.astype(jnp.float32) if any_lossy else g
        for ph in sched:
            o, chunks, bits = ph["offset"], ph["chunks"], ph["bits"]
            if not compress:
                bits = 32
            if g.ndim > 1 and chunks > 1 and g.shape[1] % chunks == 0:
                parts = jnp.split(g, chunks, axis=1)
            else:
                parts = [g]
            rec = []
            for part in parts:
                # per-pod-slice scales (rolled along with the payload)
                enc, scl = wire_encode(part, bits,
                                       axes=tuple(range(1, part.ndim)))
                enc_r = jnp.roll(enc, o, axis=0)          # -> ppermute
                scl_r = jnp.roll(scl, o, axis=0) if scl is not None else None
                rec.append(wire_decode(enc_r, scl_r, jnp.float32, bits))
            got = jnp.concatenate(rec, axis=1) if len(rec) > 1 else rec[0]
            acc = acc + got
        return (acc * out_scale).astype(g.dtype)

    return jax.tree.map(per_leaf, tree)


def psum_allreduce_batched(tree: Any, n_pods: int, *, mean: bool = True
                           ) -> Any:
    """Baseline in the batched formulation: mean over the pod dim
    broadcast back — XLA inserts its own all-reduce."""
    def per_leaf(g):
        """Apply the phase schedule to one gradient leaf."""
        s = jnp.sum(g, axis=0, keepdims=True)
        if mean:
            s = s / n_pods
        return jnp.broadcast_to(s, g.shape).astype(g.dtype)
    return jax.tree.map(per_leaf, tree)
