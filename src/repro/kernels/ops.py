"""jit'd public wrappers for the Pallas kernels.

Each kernel compiles through the Pallas TPU lowering, and runs in
interpret mode only on the CPU backend (`repro.kernels.interpret_default`).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import quantize as _q
from repro.kernels import rf_predict as _rf
from repro.kernels import ssd_scan as _ssd


def quantize(x: jax.Array, bits: int = 8, block: int = _q.BLOCK
             ) -> Tuple[jax.Array, jax.Array]:
    """Block-symmetric quantize x -> (payload, per-tile scales)."""
    return _q.quantize_pallas(x, bits=bits, block=block)


def dequantize(q: jax.Array, scale: jax.Array, block: int = _q.BLOCK,
               out_dtype=jnp.float32) -> jax.Array:
    """Invert :func:`quantize` back to `out_dtype`."""
    return _q.dequantize_pallas(q, scale, block=block, out_dtype=out_dtype)


def rf_predict(feat: jax.Array, thr: jax.Array, leaf: jax.Array,
               X: jax.Array, depth: int) -> jax.Array:
    """Forest inference over packed trees: X [n, F] -> [n]."""
    return _rf.rf_predict_pallas(feat, thr, leaf, X, depth=depth)


def ssd_chunk(xq: jax.Array, Bq: jax.Array, Cq: jax.Array, da: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """One SSD chunk scan step (see kernels/ssd_scan.py)."""
    return _ssd.ssd_chunk_pallas(xq, Bq, Cq, da)
