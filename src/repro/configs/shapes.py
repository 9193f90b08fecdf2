"""Assigned input shapes and ShapeDtypeStruct input_specs per (arch, shape).

Shapes (LM transformer: seq_len x global_batch):
  train_4k     seq=4096    gb=256  -> train_step
  prefill_32k  seq=32768   gb=32   -> prefill (inference)
  decode_32k   seq=32768   gb=128  -> serve_step (1 new token, KV cache of seq)
  long_500k    seq=524288  gb=1    -> serve_step; sub-quadratic archs only

``input_specs`` allocates nothing: pure ShapeDtypeStructs (the
shannon/kernels pattern), weak-type-correct and shardable.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

SHAPE_NAMES = list(SHAPES)


def applicable(cfg: ModelConfig, shape_name: str) -> Optional[str]:
    """None if the (arch, shape) cell runs; otherwise the skip reason."""
    spec = SHAPES[shape_name]
    if spec.name == "long_500k" and not cfg.subquadratic:
        return ("full quadratic attention: 512k-token decode cache/attention "
                "is out of scope per assignment (sub-quadratic archs only)")
    if spec.kind == "decode" and not cfg.has_decoder:
        return "encoder-only arch has no decode step"
    return None


def input_specs(cfg: ModelConfig, shape_name: str,
                tp: int = 16) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of the lowered step.

    train  -> tokens/targets (+ modality stub embeddings)
    prefill-> tokens (+ stubs)
    decode -> cache + single-token batch + position
    """
    spec = SHAPES[shape_name]
    B, S = spec.global_batch, spec.seq_len
    f = jnp.dtype(cfg.dtype)
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if spec.kind in ("train", "prefill"):
        out["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if spec.kind == "train":
            out["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.is_encdec:
            e = cfg.encoder
            out["enc_frames"] = jax.ShapeDtypeStruct((B, e.source_len, e.d_model), f)
        if cfg.is_vlm:
            e = cfg.encoder
            out["patch_embeds"] = jax.ShapeDtypeStruct((B, e.source_len, cfg.d_model), f)
    else:  # decode
        from repro.models.registry import cache_spec  # lazy: avoid cycle
        out["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
        out["cache"] = cache_spec(cfg, B, S, tp=tp)
    return out
