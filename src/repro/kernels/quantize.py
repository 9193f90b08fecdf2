"""Pallas TPU kernel: block-wise symmetric quantize / dequantize.

Used by the WANify gradient-compression stage (SAGQ analogue, paper
§5.6): gradients are tiled (block x block), each tile gets an f32 scale
and int8/int4 payload before crossing the inter-pod "WAN" hop.

TPU adaptation: tiles are (256, 256) — multiples of the (8,128) VREG
lane layout; abs-max reduction and scaling run on the VPU entirely in
VMEM; one tile per grid cell. A tile's scale travels as one (8, 128)
vreg tile filled with that scale, since a block's last two dimensions
must be whole (8, 128) tiles; the wrappers take and give the compact
[n/block, d/block] scale grid.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

BLOCK = 256
SCALE_TILE = (8, 128)


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.full(s_ref.shape, scale, jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref, *, out_dtype):
    scale = jnp.max(s_ref[...])             # every entry holds the scale
    x_ref[...] = (q_ref[...].astype(jnp.float32) * scale).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def quantize_pallas(x: jax.Array, bits: int = 8, block: int = BLOCK,
                    interpret: bool = None) -> Tuple[jax.Array, jax.Array]:
    """x [n, d] (n, d multiples of block) -> (q int8, scale [n/b, d/b])."""
    if interpret is None:
        interpret = interpret_default()
    n, d = x.shape
    qmax = float((1 << (bits - 1)) - 1)
    grid = (n // block, d // block)
    sr, sc = SCALE_TILE
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[pl.BlockSpec((block, block), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block, block), lambda i, j: (i, j)),
            pl.BlockSpec(SCALE_TILE, lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((grid[0] * sr, grid[1] * sc), jnp.float32),
        ],
        interpret=interpret,
        name="quantize",
    )(x)
    return q, s[::sr, ::sc]


@functools.partial(jax.jit, static_argnames=("block", "interpret", "out_dtype"))
def dequantize_pallas(q: jax.Array, scale: jax.Array, block: int = BLOCK,
                      out_dtype=jnp.float32, interpret: bool = None
                      ) -> jax.Array:
    """Invert :func:`quantize_pallas` (per-tile scales broadcast back)."""
    if interpret is None:
        interpret = interpret_default()
    n, d = q.shape
    grid = (n // block, d // block)
    sr, sc = SCALE_TILE
    scale_tiles = jnp.repeat(jnp.repeat(scale.astype(jnp.float32), sr, 0),
                             sc, 1)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, block), lambda i, j: (i, j)),
            pl.BlockSpec(SCALE_TILE, lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        interpret=interpret,
        name="dequantize",
    )(q, scale_tiles)
