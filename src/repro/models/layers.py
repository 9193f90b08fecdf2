"""Shared layers: norms, SwiGLU MLP, RoPE, sharding helpers, init."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ----------------------------------------------------------------------
# Shard context: models are mesh-agnostic; the launcher passes axis names.
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class ShardCtx:
    """Axis names for sharding constraints; all None => no constraints
    (single-device smoke tests)."""
    batch_axes: Tuple[str, ...] = ()     # e.g. ("pod", "data")
    model_axis: Optional[str] = None     # e.g. "model"
    # sequence-parallel layer boundaries (Megatron-SP analogue): shard the
    # seq dim of [B,S,D] activations over model_axis between blocks.
    seq_shard_activations: bool = True
    remat: str = "full"                  # "none" | "full" | "dots"
    flash_block: int = 512
    moe_capacity_factor: Optional[float] = None  # override config cf

    @property
    def enabled(self) -> bool:
        return bool(self.batch_axes) or self.model_axis is not None

    def batch_spec(self) -> P:
        return P(self.batch_axes if self.batch_axes else None)


def shard(x: jax.Array, ctx: ShardCtx, *spec) -> jax.Array:
    """with_sharding_constraint if ctx has a mesh; no-op otherwise."""
    if not ctx.enabled:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_act(x: jax.Array, ctx: ShardCtx) -> jax.Array:
    """Layer-boundary [B,S,D] activation sharding: batch over DP axes and,
    when sequence-parallel is on, seq over the model axis."""
    if not ctx.enabled:
        return x
    b = ctx.batch_axes if ctx.batch_axes else None
    s = ctx.model_axis if (ctx.seq_shard_activations and x.shape[1] > 1) else None
    return shard(x, ctx, b, s, None)


# ----------------------------------------------------------------------
# f32-accumulating einsum: the MXU takes bf16 inputs and accumulates f32
# (preferred_element_type); XLA-CPU runs the same dot.
# ----------------------------------------------------------------------
def einsum_f32(spec: str, *ops: jax.Array) -> jax.Array:
    return jnp.einsum(spec, *ops, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# Norms / activations
# ----------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Stats in f32, VALUE path in the compute dtype: a full-f32 value
    path makes every activation gradient f32, doubling the bytes of all
    TP/SP collectives touching [B,S,d] tensors."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def head_rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """qk-norm over the head_dim (last axis) — qwen3-style."""
    return rms_norm(x, scale, eps)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array,
           ctx: ShardCtx) -> jax.Array:
    h = jax.nn.silu(x @ w1) * (x @ w3)
    h = shard(h, ctx, ctx.batch_axes or None, None, ctx.model_axis)
    return h @ w2


def gelu_mlp(x: jax.Array, w1: jax.Array, b1: jax.Array, w2: jax.Array,
             b2: jax.Array, ctx: ShardCtx) -> jax.Array:
    h = jax.nn.gelu(x @ w1 + b1)
    h = shard(h, ctx, ctx.batch_axes or None, None, ctx.model_axis)
    return h @ w2 + b2


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, D] (D even), positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                # [D/2]
    ang = positions.astype(jnp.float32)[..., None] * inv      # [..., S, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings [S, D]."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10000.0 ** (jnp.arange(dim // 2, dtype=jnp.float32) / (dim // 2 - 1)))
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------
def dense_init(key: jax.Array, shape: Sequence[int], dtype,
               scale: Optional[float] = None) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, tuple(shape), jnp.float32) * s).astype(dtype)


class KeyGen:
    """Deterministic sub-key dispenser so init is order-stable."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


# ----------------------------------------------------------------------
# Cross-entropy with V-sharded logits
# ----------------------------------------------------------------------
def softmax_xent(logits: jax.Array, targets: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """logits [.., V] f32-upcast stable CE; targets [..] int32."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tl = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = lse - tl
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_xent(h: jax.Array, lm_head: jax.Array, targets: jax.Array,
                 ctx: "ShardCtx", chunk: int = 1024) -> jax.Array:
    """Sequence-chunked CE: logits [B,chunk,V] are (re)computed per chunk
    inside a rematerialized scan, so the full [B,S,V] f32 logits tensor
    (GiBs at 128k vocab) never exists. h: [B,S,d], lm_head: [d,V]."""
    B, S, d = h.shape
    if S % chunk or S <= chunk:
        return softmax_xent(h @ lm_head, targets)
    nC = S // chunk
    hc = h.reshape(B, nC, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nC, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(tot, xs):
        hh, tt = xs
        logits = hh @ lm_head
        # keep V sharded over the model axis: the lm_head shard stays
        # local (no 1-GiB table all-gather per chunk)
        logits = shard(logits, ctx, ctx.batch_axes or None, None,
                       ctx.model_axis)
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        tl = jnp.take_along_axis(lf, tt[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - tl), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc))
    return tot / (B * S)
