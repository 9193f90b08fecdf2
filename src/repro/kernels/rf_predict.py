"""Pallas TPU kernel: Random-Forest ensemble inference.

TPU adaptation of tree traversal (DESIGN.md §2): trees live in a
COMPLETE-binary-tree array layout, so level-order descent is pure index
arithmetic (node -> 2*node+1+go_right) — no pointers, no data-dependent
control flow. Gathers are expressed as ONE-HOT SELECTS reduced along
the lanes (TPU Pallas has no efficient dynamic row gather):

  thr[t, node_s]  ==  sum_k where(k == node_s, thr[t, k], 0)

Exactly one term is nonzero, so the gather is exact in f32 (a matmul
contraction would round thresholds through the MXU's bf16 passes).

Grid: one cell per sample block; the whole forest (feat/thr/leaf) is
resident in VMEM per cell (e.g. 100 trees x depth 8 ~= 0.4 MB). The
node axis is padded to whole 128-lane tiles, and each block writes its
predictions as a [block, 1] column of a 2-D output. Index maps and
loop bounds are typed int32, so the kernel also lowers for the TPU when
it is traced under `jax.enable_x64` (as the fused fleet tick is), where
Python ints would become i64 and Mosaic refuses them.

``interpret=None`` (the default) runs the interpreter only on the CPU
backend (`repro.kernels.interpret_default`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

SAMPLE_BLOCK = 128
LANES = 128


def _rf_kernel(feat_ref, thr_ref, leaf_ref, x_ref, out_ref, *, depth: int,
               n_trees: int, n_nodes: int):
    X = x_ref[...].astype(jnp.float32)            # [BS, F]
    BS, F = X.shape
    node_k = jax.lax.broadcasted_iota(jnp.int32, (BS, thr_ref.shape[1]), 1)
    feat_k = jax.lax.broadcasted_iota(jnp.int32, (BS, F), 1)
    leaf_k = jax.lax.broadcasted_iota(jnp.int32, (BS, leaf_ref.shape[1]), 1)

    def pick(hot, row):
        """Exact one-hot gather: [BS, K] mask x [1|BS, K] -> [BS, 1]."""
        return jnp.sum(jnp.where(hot, row, 0.0), axis=1, keepdims=True)

    def tree_body(t, acc):
        """Descend all samples through tree `t`; add its leaf values."""
        feat_t = feat_ref[pl.ds(t, 1), :].astype(jnp.float32)  # [1, NN]
        thr_t = thr_ref[pl.ds(t, 1), :]                        # [1, NN]
        leaf_t = leaf_ref[pl.ds(t, 1), :]                      # [1, NL]
        node = jnp.zeros((BS, 1), jnp.int32)
        for _ in range(depth):
            hot = node == node_k
            f_i = jnp.maximum(pick(hot, feat_t), 0.0).astype(jnp.int32)
            x_s = pick(f_i == feat_k, X)
            go_right = (x_s > pick(hot, thr_t)).astype(jnp.int32)
            node = 2 * node + 1 + go_right
        return acc + pick(node - n_nodes == leaf_k, leaf_t)

    acc = jax.lax.fori_loop(np.int32(0), np.int32(n_trees), tree_body,
                            jnp.zeros((BS, 1), jnp.float32))
    out_ref[...] = acc / n_trees


def _whole(i):
    """Index map of a block that spans the whole array (int32 under x64)."""
    return jnp.int32(0), jnp.int32(0)


def _rows(i):
    """Index map of the i-th row block (int32 under x64)."""
    return i, jnp.int32(0)


def _pad_lanes(a: jax.Array) -> jax.Array:
    """Pad the node axis to whole lane tiles (padding is never selected)."""
    return jnp.pad(a, ((0, 0), (0, (-a.shape[1]) % LANES)))


@functools.partial(jax.jit,
                   static_argnames=("depth", "block", "interpret"))
def rf_predict_pallas(feat: jax.Array, thr: jax.Array, leaf: jax.Array,
                      X: jax.Array, depth: int, block: int = SAMPLE_BLOCK,
                      interpret: bool = None) -> jax.Array:
    """feat/thr [T, 2^d-1], leaf [T, 2^d], X [n, F] -> [n] predictions."""
    if interpret is None:
        interpret = interpret_default()
    n, F = X.shape
    T, n_nodes = feat.shape
    feat, thr, leaf = _pad_lanes(feat), _pad_lanes(thr), _pad_lanes(leaf)
    pad = (-n) % block
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    npad = X.shape[0]
    out = pl.pallas_call(
        functools.partial(_rf_kernel, depth=depth, n_trees=T,
                          n_nodes=n_nodes),
        grid=(npad // block,),
        in_specs=[
            pl.BlockSpec(feat.shape, _whole),
            pl.BlockSpec(thr.shape, _whole),
            pl.BlockSpec(leaf.shape, _whole),
            pl.BlockSpec((block, F), _rows),
        ],
        out_specs=pl.BlockSpec((block, 1), _rows),
        out_shape=jax.ShapeDtypeStruct((npad, 1), jnp.float32),
        interpret=interpret,
        name="rf_predict",
    )(feat, thr, leaf, X)
    return out[:n, 0]
