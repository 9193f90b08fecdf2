"""Pallas TPU kernel: Mamba-2 SSD within-chunk scan (the compute hot-spot
of the ssm/hybrid architectures).

Per grid cell: one (batch, chunk, head-block) computes
  * cumulative log-decay, the [Q,Q] decay mask L (VPU exp/cumsum)
  * cb = Cq @ Bq^T on the MXU
  * y_diag = (cb * L) @ (dt*x)  and the chunk-boundary states

The cross-chunk linear recurrence is O(S/Q) and stays outside (lax.scan
in the caller) — it is bandwidth-trivial.

VMEM budget per cell (Q=256, BH=8, P=64, N=128, f32):
  seg/L: 8*256*256*4 = 2 MB, xq: 256*8*64*4 = 0.5 MB, rest < 1 MB.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_default

HEAD_BLOCK = 8


def _ssd_kernel(xq_ref, bq_ref, cq_ref, da_ref, y_ref, st_ref):
    xq = xq_ref[0, 0].astype(jnp.float32)         # [Q, BH, P]
    Bq = bq_ref[0, 0].astype(jnp.float32)         # [Q, N]
    Cq = cq_ref[0, 0].astype(jnp.float32)         # [Q, N]
    da = da_ref[0, 0].astype(jnp.float32)         # [BH, Q]
    Q = xq.shape[0]

    cum = jnp.cumsum(da, axis=-1)                 # [BH, Q]
    seg = cum[:, :, None] - cum[:, None, :]       # [BH, Q, Q]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.exp(jnp.where((qi >= ki)[None], seg, -1e30))

    cb = jnp.dot(Cq, Bq.T, preferred_element_type=jnp.float32)  # [Q, Q]
    scores = cb[None] * L                          # [BH, Q, Q]
    # y[q,h,p] = sum_k scores[h,q,k] * xq[k,h,p]
    y = jax.lax.dot_general(
        scores, xq.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [BH, Q, P]
    y_ref[0, 0] = y.transpose(1, 0, 2)

    dec_r = jnp.exp(cum[:, -1:] - cum)             # [BH, Q]
    xw = xq.transpose(1, 0, 2) * dec_r[:, :, None]  # [BH, Q, P]
    st = jax.lax.dot_general(
        xw, jnp.broadcast_to(Bq[None], (xw.shape[0],) + Bq.shape),
        (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [BH, P, N]
    st_ref[0, 0] = st


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def ssd_chunk_pallas(xq: jax.Array, Bq: jax.Array, Cq: jax.Array,
                     da: jax.Array, head_block: int = HEAD_BLOCK,
                     interpret: bool = None) -> Tuple[jax.Array, jax.Array]:
    """Batched over (B, nC): xq [B,nC,Q,H,P], Bq/Cq [B,nC,Q,N],
    da [B,nC,H,Q] -> (y_diag [B,nC,Q,H,P], states [B,nC,H,P,N])."""
    if interpret is None:
        interpret = interpret_default()
    B, nC, Q, H, P = xq.shape
    N = Bq.shape[-1]
    BH = min(head_block, H)
    assert H % BH == 0
    grid = (B, nC, H // BH)
    y, st = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, BH, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, BH, Q), lambda b, c, h: (b, c, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, BH, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, BH, P, N), lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nC, Q, H, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nC, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(xq, Bq, Cq, da)
    return y, st
