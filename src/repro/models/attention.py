"""Attention: GQA / MLA / SWA / qk-norm; flash (online-softmax) for
train & prefill; cached decode with KV-head replication for TP>n_kv and
XLA-partitionable softmax over sharded cache sequence dims.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (KeyGen, ShardCtx, apply_rope, dense_init,
                                 einsum_f32, head_rms_norm, shard)

NEG_INF = -1e30


# ======================================================================
# Flash attention — pure-jnp online softmax with a CUSTOM VJP: the
# backward recomputes per-block probabilities from saved (q,k,v,out,lse)
# (the classic flash backward), so AD never stores the per-block
# residuals of the forward scan. O(S) memory both directions. The TPU
# production path is a Pallas kernel; this is the dry-run/oracle path.
# ======================================================================
def _mask_for(i, bk, Sq, Sk, q_offset, causal, window):
    # qpos/kpos are built HERE so no tracer is closed over by the
    # custom_vjp fwd/bwd (jnp.arange stages a tracer under jit)
    qpos = q_offset + jnp.arange(Sq)
    kpos = i * bk + jnp.arange(bk)
    mask = jnp.broadcast_to(kpos[None, :] < Sk, (Sq, bk))
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    return mask


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_k: int = 512,
                    scale: Optional[float] = None) -> jax.Array:
    """q: [B,K,G,Sq,Dq]  k: [B,K,Sk,Dq]  v: [B,K,Sk,Dv] -> [B,K,G,Sq,Dv].

    K = kv heads, G = query group size (Hq = K*G). Scans over key blocks
    with a running (m, l, acc) softmax state; never materializes the
    [Sq, Sk] score matrix.
    """
    B, K, G, Sq, Dq = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    sc = scale if scale is not None else Dq ** -0.5
    bk = min(block_k, Sk)
    if Sk % bk:                                # pad keys; masked out below
        pad = bk - Sk % bk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nb = k.shape[2] // bk

    def _blocks(k, v):
        kb = k.reshape(B, K, nb, bk, Dq).transpose(2, 0, 1, 3, 4)
        vb = v.reshape(B, K, nb, bk, Dv).transpose(2, 0, 1, 3, 4)
        return kb, vb

    def _fwd_impl(q, k, v):
        kb, vb = _blocks(k, v)

        def body(carry, xs):
            m, l, acc = carry
            i, kblk, vblk = xs
            s = einsum_f32("bkgsd,bktd->bkgst", q, kblk) * sc
            s = jnp.where(_mask_for(i, bk, Sq, Sk, q_offset, causal, window),
                          s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + einsum_f32(
                "bkgst,bktd->bkgsd", p.astype(v.dtype), vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
        a0 = jnp.zeros((B, K, G, Sq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (jnp.arange(nb), kb, vb))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).astype(v.dtype)
        lse = m + jnp.log(l_safe)
        return out, lse

    @jax.custom_vjp
    def _flash(q, k, v):
        return _fwd_impl(q, k, v)[0]

    def _vjp_fwd(q, k, v):
        out, lse = _fwd_impl(q, k, v)
        return out, (q, k, v, out, lse)

    def _vjp_bwd(res, g):
        q, k, v, out, lse = res
        g32 = g.astype(jnp.float32)
        delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # [B,K,G,Sq]
        kb, vb = _blocks(k, v)

        def body(dq, xs):
            i, kblk, vblk = xs
            s = einsum_f32("bkgsd,bktd->bkgst", q, kblk) * sc
            s = jnp.where(_mask_for(i, bk, Sq, Sk, q_offset, causal, window),
                          s, NEG_INF)
            p = jnp.exp(s - lse[..., None])                      # exact probs
            dv_b = einsum_f32("bkgst,bkgsd->bktd", p, g32)
            dp = einsum_f32("bkgsd,bktd->bkgst", g32, vblk)
            ds = p * (dp - delta[..., None])
            dq = dq + einsum_f32("bkgst,bktd->bkgsd", ds, kblk) * sc
            dk_b = einsum_f32("bkgst,bkgsd->bktd", ds, q) * sc
            return dq, (dk_b, dv_b)

        dq0 = jnp.zeros((B, K, G, Sq, Dq), jnp.float32)
        dq, (dk_b, dv_b) = jax.lax.scan(body, dq0, (jnp.arange(nb), kb, vb))
        # cotangents match the (possibly padded) operands of _flash; the
        # outer jnp.pad's own VJP slices back to the caller's Sk.
        dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(B, K, nb * bk, Dq)
        dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(B, K, nb * bk, Dv)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    _flash.defvjp(_vjp_fwd, _vjp_bwd)
    return _flash(q, k, v)


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int, scale: Optional[float] = None) -> jax.Array:
    """Banded local attention, O(S * 2W): q/k/v blocked by the window size;
    block i attends to blocks {i-1, i} with an exact band mask.
    q: [B,K,G,S,D] k,v: [B,K,S,D]."""
    B, K, G, S, Dq = q.shape
    Dv = v.shape[-1]
    W = window
    if S <= W:
        return flash_attention(q, k, v, causal=True, window=W, scale=scale)
    assert S % W == 0, f"S={S} not divisible by window={W}"
    nb = S // W
    sc = scale if scale is not None else Dq ** -0.5

    qb = q.reshape(B, K, G, nb, W, Dq)
    kb = k.reshape(B, K, nb, W, Dq)
    vb = v.reshape(B, K, nb, W, Dv)
    # previous block (block -1 is zeros and fully masked)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :, :1]), kb[:, :, :-1]], axis=2)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :, :1]), vb[:, :, :-1]], axis=2)
    k2 = jnp.concatenate([kprev, kb], axis=3)          # [B,K,nb,2W,Dq]
    v2 = jnp.concatenate([vprev, vb], axis=3)
    s = einsum_f32("bkgnsd,bkntd->bkgnst", qb * sc, k2)
    qpos = jnp.arange(W)[:, None]                       # within-block
    kpos = jnp.arange(2 * W)[None, :] - W               # relative to block start
    band = (qpos >= kpos) & ((qpos - kpos) < W)
    first = jnp.arange(nb) == 0                         # block -1 invalid for block 0
    valid_prev = (~first)[:, None, None] | (kpos[None] >= 0)
    mask = band[None] & valid_prev
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = einsum_f32("bkgnst,bkntd->bkgnsd", p.astype(v.dtype), v2)
    return out.reshape(B, K, G, S, Dv).astype(v.dtype)


# ======================================================================
# GQA (with optional qk-norm, SWA)
# ======================================================================
def init_gqa_params(kg: KeyGen, cfg: ModelConfig, dtype) -> Dict:
    d, H, KV, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": dense_init(kg(), (d, H * D), dtype),
        "wk": dense_init(kg(), (d, KV * D), dtype),
        "wv": dense_init(kg(), (d, KV * D), dtype),
        "wo": dense_init(kg(), (H * D, d), dtype),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((D,), dtype)
        p["k_scale"] = jnp.ones((D,), dtype)
    return p


def _split_heads(x, n, d):
    B, S, _ = x.shape
    return x.reshape(B, S, n, d).transpose(0, 2, 1, 3)      # [B,n,S,d]


def gqa_forward(p: Dict, x: jax.Array, ctx: ShardCtx, cfg: ModelConfig,
                positions: jax.Array, *, cross_kv: Optional[Tuple] = None,
                causal: bool = True) -> jax.Array:
    """Full-sequence GQA used in train/prefill. positions: [S]."""
    B, S, d = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = _split_heads(x @ p["wq"], H, D)
    if cross_kv is None:
        k = _split_heads(x @ p["wk"], KV, D)
        v = _split_heads(x @ p["wv"], KV, D)
    else:
        k, v = cross_kv
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_scale"])
        k = head_rms_norm(k, p["k_scale"]) if cross_kv is None else k
    if cfg.rope_theta > 0 and cross_kv is None:
        q = apply_rope(q, positions[None, None, :], cfg.rope_theta)
        k = apply_rope(k, positions[None, None, :], cfg.rope_theta)
    elif cfg.rope_theta > 0:
        q = apply_rope(q, positions[None, None, :], cfg.rope_theta)
    ma = ctx.model_axis
    q = shard(q, ctx, ctx.batch_axes or None, ma, None, None)
    # Expand KV heads to the full query-head count before attention: the
    # grouped [B,KV,G,S,*] layout cannot shard KV(<TP) over the model
    # axis, and XLA then REPLICATES every per-block score tensor in the
    # flash scans (~2 GiB x layers x blocks of all-gather traffic).
    # Expanded [B,H,S,*] shards H/TP cleanly; the repeat's VJP sums dk/dv
    # back over groups.
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
    k = shard(k, ctx, ctx.batch_axes or None, ma, None, None)
    v = shard(v, ctx, ctx.batch_axes or None, ma, None, None)
    qg = q[:, :, None]                                     # [B,H,1,S,D]
    if cfg.sliding_window and causal:
        o = swa_attention(qg, k, v, window=cfg.sliding_window)
    else:
        o = flash_attention(qg, k, v, causal=causal, block_k=ctx.flash_block)
    o = o[:, :, 0].transpose(0, 2, 1, 3).reshape(B, S, H * D)
    return o @ p["wo"]


def gqa_make_cache(p: Dict, x: jax.Array, cfg: ModelConfig, ctx: ShardCtx,
                   positions: jax.Array, S_max: int, kv_eff: int) -> Tuple:
    """Build a decode cache from prefill activations; pads to S_max and
    replicates KV heads to kv_eff (TP > n_kv)."""
    B, S, _ = x.shape
    KV, D = cfg.n_kv_heads, cfg.resolved_head_dim
    k = _split_heads(x @ p["wk"], KV, D)
    v = _split_heads(x @ p["wv"], KV, D)
    if cfg.qk_norm:
        k = head_rms_norm(k, p["k_scale"])
    if cfg.rope_theta > 0:
        k = apply_rope(k, positions[None, None, :], cfg.rope_theta)
    r = kv_eff // KV
    if r > 1:
        k = jnp.repeat(k, r, axis=1)
        v = jnp.repeat(v, r, axis=1)
    pad = S_max - S
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return k, v


def gqa_decode(p: Dict, cache_k: jax.Array, cache_v: jax.Array, x: jax.Array,
               pos: jax.Array, cfg: ModelConfig, ctx: ShardCtx,
               window: int = 0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: [B,1,d]; cache: [B,KV_eff,S,D] (S may be a ring
    buffer of size `window` for SWA archs). Returns (out, new_k, new_v)."""
    B, _, d = x.shape
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    KVe, S = cache_k.shape[1], cache_k.shape[2]
    r = KVe // KV
    q = _split_heads(x @ p["wq"], H, D)                     # [B,H,1,D]
    k = _split_heads(x @ p["wk"], KV, D)
    v = _split_heads(x @ p["wv"], KV, D)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_scale"])
        k = head_rms_norm(k, p["k_scale"])
    if cfg.rope_theta > 0:
        pp = pos[None, None, None] if pos.ndim == 0 else pos
        q = apply_rope(q, pp, cfg.rope_theta)
        k = apply_rope(k, pp, cfg.rope_theta)
    if r > 1:
        k, v = jnp.repeat(k, r, axis=1), jnp.repeat(v, r, axis=1)
    slot = pos % S if window else jnp.minimum(pos, S - 1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=2)
    G = H // KVe
    qg = q.reshape(B, KVe, G, 1, D)
    s = einsum_f32("bkgqd,bksd->bkgqs", qg * (D ** -0.5), ck)
    idx = jnp.arange(S)
    if window:
        valid = (idx <= (pos % S)) | (pos >= S)             # ring buffer: all valid once wrapped
    else:
        valid = idx <= pos
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = einsum_f32("bkgqs,bksd->bkgqd", pr.astype(cv.dtype), cv)
    o = o.reshape(B, H, 1, D).transpose(0, 2, 1, 3).reshape(B, 1, H * D)
    return (o @ p["wo"]).astype(x.dtype), ck, cv


# ======================================================================
# MLA — Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3)
# ======================================================================
def init_mla_params(kg: KeyGen, cfg: ModelConfig, dtype) -> Dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    p: Dict = {}
    if m.q_lora_rank > 0:
        p["wq_a"] = dense_init(kg(), (d, m.q_lora_rank), dtype)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
        p["wq_b"] = dense_init(kg(), (m.q_lora_rank, H * qd), dtype)
    else:
        p["wq"] = dense_init(kg(), (d, H * qd), dtype)
    p["wkv_a"] = dense_init(kg(), (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype)
    p["kv_norm"] = jnp.ones((m.kv_lora_rank,), dtype)
    p["wkv_b"] = dense_init(
        kg(), (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)), dtype)
    p["wo"] = dense_init(kg(), (H * m.v_head_dim, d), dtype)
    return p


def _mla_q(p, x, cfg, positions):
    m, H = cfg.mla, cfg.n_heads
    nd, rd = m.qk_nope_head_dim, m.qk_rope_head_dim
    B, S, _ = x.shape
    if "wq_a" in p:
        from repro.models.layers import rms_norm
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, nd + rd).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions[None, None, :], cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, positions):
    from repro.models.layers import rms_norm
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:].transpose(0, 2, 1, 3),
                        positions[None, None, :], cfg.rope_theta)    # [B,1,S,rd]
    return c_kv, k_rope


def mla_forward(p: Dict, x: jax.Array, ctx: ShardCtx, cfg: ModelConfig,
                positions: jax.Array) -> jax.Array:
    """Full-sequence MLA: expand k_nope/v from the latent and run flash
    with KV == H (MHA over expanded heads)."""
    m, H = cfg.mla, cfg.n_heads
    nd, rd, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_ckv(p, x, cfg, positions)
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, nd + vd)
    k_nope = einsum_f32("bsr,rhd->bhsd", c_kv, wkv_b[..., :nd])
    v = jnp.einsum("bsr,rhd->bhsd", c_kv, wkv_b[..., nd:])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, H, S, rd))], axis=-1)
    ma = ctx.model_axis
    q = shard(q, ctx, ctx.batch_axes or None, ma, None, None)
    k = shard(k, ctx, ctx.batch_axes or None, ma, None, None)
    v = shard(v, ctx, ctx.batch_axes or None, ma, None, None)
    o = flash_attention(q[:, :, None], k, v, causal=True,
                        block_k=ctx.flash_block, scale=(nd + rd) ** -0.5)
    o = o[:, :, 0].transpose(0, 2, 1, 3).reshape(B, S, H * vd)
    return o @ p["wo"]


def mla_make_cache(p: Dict, x: jax.Array, cfg: ModelConfig,
                   positions: jax.Array, S_max: int) -> Tuple:
    """MLA decode cache = compressed latent (+ shared rope key): the memory
    win that makes deepseek-v2 32k decode cheap."""
    B, S, _ = x.shape
    c_kv, k_rope = _mla_ckv(p, x, cfg, positions)
    k_rope = k_rope[:, 0]                                   # [B,S,rd]
    pad = S_max - S
    if pad > 0:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return c_kv, k_rope


def mla_decode(p: Dict, c_kv: jax.Array, k_rope: jax.Array, x: jax.Array,
               pos: jax.Array, cfg: ModelConfig, ctx: ShardCtx) -> Tuple:
    """Absorbed-matmul MLA decode: attends directly over the latent cache,
    never materializing per-head K/V."""
    m, H = cfg.mla, cfg.n_heads
    nd, rd, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B = x.shape[0]
    S = c_kv.shape[1]
    q_nope, q_rope = _mla_q(p, x, cfg, jnp.broadcast_to(pos, (1,)))
    new_ckv, new_krope = _mla_ckv(p, x, cfg, jnp.broadcast_to(pos, (1,)))
    c_kv = jax.lax.dynamic_update_slice_in_dim(c_kv, new_ckv, pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(k_rope, new_krope[:, 0], pos, axis=1)
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, nd + vd)
    # absorb W_uk into q:   [B,H,1,nd] x [R,H,nd] -> [B,H,R]
    q_abs = jnp.einsum("bhqd,rhd->bhr", q_nope, wkv_b[..., :nd])
    sc = (nd + rd) ** -0.5
    s = (jnp.einsum("bhr,bsr->bhs", q_abs, c_kv)
         + einsum_f32("bhqd,bsd->bhs", q_rope, k_rope)) * sc
    valid = jnp.arange(S) <= pos
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o_lat = einsum_f32("bhs,bsr->bhr", pr.astype(c_kv.dtype), c_kv).astype(x.dtype)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, wkv_b[..., nd:])  # absorb W_uv
    o = o.reshape(B, 1, H * vd)
    return (o @ p["wo"]).astype(x.dtype), c_kv, k_rope
