"""Plain reference of one data-parallel training step of a dense GQA
decoder (H2O-Danube-1.8B's block) across pods, in float32.

Written from the published description alone and importing nothing of
``repro``: token embedding, pre-norm RMSNorm, rotary embedding (the
rotate-half form, base `rope_theta`), grouped-query attention under a
causal mask and a `window`-key sliding window, a SwiGLU MLP, a final
RMSNorm, an untied output head and the mean token cross-entropy;
``jax.grad`` for the gradients; the cross-pod sync as the offset-phase
schedule defines it; AdamW with global-norm clipping and a linear
warm-up into a cosine decay.

The sync: for each offset class o = 1 .. P-1 of the schedule, each pod's
gradient is cut into `chunks` parts along its second axis (where that
axis divides; else one part), each part is quantized for the wire to the
class's bits with one scale per pod (int8 container, rounded to nearest,
clipped to +-(2^(bits-1) - 1); 16 bits is a bfloat16 rounding, 32 exact)
and decoded; pod p adds its own exact gradient and the decoded payload
of pod p - o for every o, then divides by P.

Layout: every array carries a leading pod axis, sharded one pod a chip
by the caller, so each chip computes its own pod. Attention is computed
in query blocks and the cross-entropy in row chunks, each rematerialized,
so a chip holds one block's scores at a time. Every matrix product runs
at ``Precision.HIGHEST``; ``mode="fp8"`` rounds every product's operands
to float8 (e4m3, per-tensor scale) and its output cotangent to float8
(e5m2), the next precision below the configuration's bfloat16 compute:
the control. ``moments="bfloat16"`` keeps AdamW's moments rounded to
bfloat16, one precision below the configuration's float32 state.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # queries per attention block
CE_ROWS = 1024         # rows per cross-entropy chunk


# ----------------------------------------------------------------------
# sizes and initial weights
# ----------------------------------------------------------------------
def shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "sliding_window", "rope_theta", "norm_eps")
    return {k: cfg[k] for k in keys}


def param_shapes(sh: Dict[str, Any]) -> Dict[str, Any]:
    """Every weight's shape, layers stacked on a leading axis."""
    L, d, f, V = sh["n_layers"], sh["d_model"], sh["d_ff"], sh["vocab"]
    q = sh["n_heads"] * sh["head_dim"]
    kv = sh["n_kv_heads"] * sh["head_dim"]
    return {"embed": (V, d), "final_norm": (d,), "lm_head": (d, V),
            "blocks": {"ln1": (L, d), "ln2": (L, d),
                       "attn": {"wq": (L, d, q), "wk": (L, d, kv),
                                "wv": (L, d, kv), "wo": (L, q, d)},
                       "mlp": {"w1": (L, d, f), "w3": (L, d, f),
                               "w2": (L, f, d)}}}


def init_params(sh: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """float32 weights from `key`: every matrix normal with standard
    deviation fan_in^-0.5 (the embedding 0.02), norm scales 1. One key
    per leaf, folded in by the leaf's place in the tree."""
    tree = param_shapes(sh)
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, leaves)):
        if "ln" in path or "norm" in path:
            out.append(jnp.ones(shape, jnp.float32))
            continue
        std = 0.02 if "embed" in path else shape[-2] ** -0.5
        out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                           shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


# ----------------------------------------------------------------------
# matrix products: float32 at HIGHEST, or the float8 control
# ----------------------------------------------------------------------
def _round8(x: jax.Array, dtype) -> jax.Array:
    """`x` rounded to float8 `dtype` under one per-tensor scale that maps
    its largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fwd8(x):
    return _round8(x, jnp.float8_e4m3fn)


_fwd8.defvjp(lambda x: (_round8(x, jnp.float8_e4m3fn), None),
             lambda _, g: (g,))


@jax.custom_vjp
def _bwd8(x):
    return x


_bwd8.defvjp(lambda x: (x, None),
             lambda _, g: (_round8(g, jnp.float8_e5m2),))


def make_dot(mode: str):
    """einsum at HIGHEST (`mode` "f32") or with float8 operands and
    float8 output cotangents ("fp8")."""
    if mode == "f32":
        return functools.partial(jnp.einsum, precision=HIGHEST)
    if mode != "fp8":
        raise ValueError(mode)

    def dot(spec, a, b):
        return _bwd8(jnp.einsum(spec, _fwd8(a), _fwd8(b), precision=HIGHEST))
    return dot


# ----------------------------------------------------------------------
# one pod's loss
# ----------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of x [B, S, heads, D] at `pos` [S]."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv            # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window, dot):
    """q [B, S, KV, G, D], k/v [B, S, KV, D] -> [B, S, KV, G, D]; query
    i attends keys j with j <= i < j + window, a block of queries at a
    time."""
    B, S, KV, G, D = q.shape
    bq = min(Q_BLOCK, S)
    nb = S // bq
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        i, qb = args                                       # [B, bq, KV, G, D]
        s = dot("bqkgd,bskd->bkgqs", qb, k) * D ** -0.5
        qpos = i * bq + jnp.arange(bq)
        ok = (kpos[None, :] <= qpos[:, None]) & \
            (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return dot("bkgqs,bskd->bqkgd", p, v)

    qb = q.reshape(B, nb, bq, KV, G, D).swapaxes(0, 1)
    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.swapaxes(0, 1).reshape(B, S, KV, G, D)


def loss(params, tokens, targets, sh, dot, rows_w=None) -> jax.Array:
    """Mean token cross-entropy of one pod's rows [B, S] (of the rows
    whose weight in `rows_w` [B] is 1, where given)."""
    B, S = tokens.shape
    H, KV, D = sh["n_heads"], sh["n_kv_heads"], sh["head_dim"]
    eps, W = sh["norm_eps"], sh["sliding_window"]
    pos = jnp.arange(S)
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, blk):
        a, m = blk["attn"], blk["mlp"]
        h = _rms(x, blk["ln1"], eps)
        q = _rope(dot("bsd,de->bse", h, a["wq"]).reshape(B, S, H, D), pos,
                  sh["rope_theta"])
        k = _rope(dot("bsd,de->bse", h, a["wk"]).reshape(B, S, KV, D), pos,
                  sh["rope_theta"])
        v = dot("bsd,de->bse", h, a["wv"]).reshape(B, S, KV, D)
        o = _attention(q.reshape(B, S, KV, H // KV, D), k, v, W, dot)
        x = x + dot("bse,ed->bsd", o.reshape(B, S, H * D), a["wo"])
        h = _rms(x, blk["ln2"], eps)
        g = jax.nn.silu(dot("bsd,df->bsf", h, m["w1"])) * \
            dot("bsd,df->bsf", h, m["w3"])
        return x + dot("bsf,fd->bsd", g, m["w2"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    h = _rms(x, params["final_norm"], eps)
    rows = min(CE_ROWS, S)

    w = jnp.ones((B,), jnp.float32) if rows_w is None else rows_w

    @jax.checkpoint
    def chunk(tot, xs):
        hc, tc = xs                                        # [B, rows, d]
        logits = dot("brd,dv->brv", hc, params["lm_head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(w[:, None] * (lse - tl)), None

    hc = h.reshape(B, S // rows, rows, -1).swapaxes(0, 1)
    tc = targets.reshape(B, S // rows, rows).swapaxes(0, 1)
    tot, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (hc, tc))
    return tot / (jnp.sum(w) * S)


# ----------------------------------------------------------------------
# the sync and the optimizer, over the pod axis
# ----------------------------------------------------------------------
def _wire(x: jax.Array, bits: int, qmax) -> jax.Array:
    """What the far pod decodes of `x` [P, ...] sent at `bits`; integer
    codes (bits < 16) run from -qmax to qmax."""
    if bits >= 32:
        return x
    if bits == 16:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)), keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def qmax_of(schedule, int_bits: int = 0) -> jax.Array:
    """Each class's largest integer code, 2^(bits-1) - 1; integer classes
    at `int_bits` where given (a planted fault)."""
    return jnp.asarray([2.0 ** ((int_bits if int_bits and b < 16 else b)
                                - 1) - 1 for _, _, b in schedule],
                       jnp.float32)


def sync(grads, schedule: Sequence[Tuple[int, int, int]], qmax,
         exchange=1.0):
    """Each pod's synced gradient: its own plus every other pod's as
    decoded from the wire, over P. `schedule`: (offset, chunks, bits) of
    each class, `qmax` its largest integer code. `exchange` 0 leaves the
    other pods out (a planted fault)."""
    def leaf(g):
        P = g.shape[0]
        acc = g
        for (o, chunks, bits), q in zip(schedule, qmax):
            split = g.ndim > 1 and chunks > 1 and g.shape[1] % chunks == 0
            parts = jnp.split(g, chunks, axis=1) if split else [g]
            got = jnp.concatenate([_wire(p, bits, q) for p in parts],
                                  axis=1) if split else _wire(g, bits, q)
            acc = acc + exchange * jnp.roll(got, o, axis=0)  # from pod p - o
        return acc / (1 + exchange * (P - 1))
    return jax.tree.map(leaf, grads)


def lr_at(opt: Dict[str, Any], t):
    """Linear warm-up to `lr` over `warmup_steps`, then cosine decay to
    `min_lr_frac` of it at `total_steps`."""
    t = jnp.asarray(t, jnp.float32)
    w, T = opt["warmup_steps"], opt["total_steps"]
    prog = jnp.clip((t - w) / max(T - w, 1), 0.0, 1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * \
        (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * jnp.minimum(t / max(w, 1), cos)


def adamw(opt, params, grads, m, v, t):
    """AdamW step `t` (1-based) of every pod, gradients clipped to a
    global norm of `clip_norm` per pod."""
    sq = sum(jnp.sum(g * g, axis=tuple(range(1, g.ndim)))
             for g in jax.tree.leaves(grads))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(jnp.sqrt(sq),
                                                           1e-9))
    lr = lr_at(opt, t)
    b1, b2 = opt["b1"], opt["b2"]

    def one(p, g, m, v):
        g = g * clip.reshape((-1,) + (1,) * (g.ndim - 1))
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + opt["eps"]) +
                         opt["weight_decay"] * p), m, v

    leaves, treedef = jax.tree.flatten(params)
    out = [one(*x) for x in zip(leaves, jax.tree.leaves(grads),
                                jax.tree.leaves(m), jax.tree.leaves(v))]
    return tuple(jax.tree.unflatten(treedef, [o[i] for o in out])
                 for i in range(3))


def leaf_norms(tree) -> jax.Array:
    """[leaves, P]: each leaf's L2 norm per pod."""
    return jnp.stack([jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
                      for x in jax.tree.leaves(tree)])


# ----------------------------------------------------------------------
# the compared steps
# ----------------------------------------------------------------------
def programs(sh, opt, schedule, sharding, mode: str = "f32",
             moments: str = "float32") -> Dict[str, Any]:
    """The reference's jitted programs over the pod axis of `sharding`:
    ``init(key)`` (the weights `key` makes, on every pod), ``step`` (one
    training step of every pod), ``first_grad`` (each leaf's norm per pod
    of the clipped gradient a first moment holds) and ``change(p, key)``
    (each leaf's norm per pod of the parameters' change since
    ``init(key)``).

    ``step(p, m, v, tokens, targets, t, rows_w, exchange, qmax)``: the
    row weights [P, B], the exchange switch and each class's largest
    code are arguments, so planted faults run the same program. AdamW's
    moments are held at `moments` precision."""
    n_pods = sharding.mesh.devices.size
    dot = make_dot(mode)
    pod = jax.tree.map(lambda _: sharding, param_shapes(sh),
                       is_leaf=lambda x: isinstance(x, tuple))

    @functools.partial(jax.jit, out_shardings=pod)
    def init(key):
        p = init_params(sh, key)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_pods,) +
                                                       x.shape), p)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, tok, tgt, t, rows_w, exchange, qmax):
        with jax.default_matmul_precision("highest"):
            ls, g = jax.vmap(jax.value_and_grad(
                lambda p, a, b, w: loss(p, a, b, sh, dot, w)))(
                    p, tok, tgt, rows_w)
        g = sync(g, schedule, qmax, exchange)
        p, m, v = adamw(opt, p, g, m, v, t)
        if moments != "float32":
            # an explicit rounding: the compiler may fold a float32 ->
            # bfloat16 -> float32 convert pair away
            fi = jnp.finfo(moments)
            m, v = (jax.tree.map(lambda x: jax.lax.reduce_precision(
                x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant), s)
                for s in (m, v))
        return p, m, v, jnp.mean(ls)

    return {"init": init, "step": step,
            "first_grad": jax.jit(lambda m: leaf_norms(m) / (1 - opt["b1"])),
            "change": jax.jit(lambda p, key: leaf_norms(
                jax.tree.map(jnp.subtract, p, init(key))))}


def run_steps(sh, opt, schedule, key, batches: List[Dict[str, Any]],
              sharding, mode: str = "f32", faults: Sequence[str] = ("none",),
              moments: str = "float32") -> Dict[str, Dict[str, Any]]:
    """Steps 1 .. len(batches) of every pod (``programs``), each pod's
    rows [B_pod, S] one slice of a batch's [P * B_pod, S], once for each
    of `faults`. Returns, for each, the mean loss over pods of each step,
    and ``first_grad`` after the first step and ``change`` after the
    last, [leaves, P] each.

    A fault plants one for the readings a limit is set from: "half" (a
    pod's second half of rows left out, the mean over the rest),
    "no_sync" (no exchange) or "wire<k>" (integer wire classes at k
    bits); "none" plants none."""
    n_pods = sharding.mesh.devices.size
    prog = programs(sh, opt, schedule, sharding, mode, moments)
    B = batches[0]["tokens"].shape[0] // n_pods
    out = {}
    for fault in faults:
        rows_w = jnp.ones((n_pods, B), jnp.float32)
        if fault == "half":
            rows_w = rows_w.at[:, B // 2:].set(0.0)
        knobs = (jax.device_put(rows_w, sharding),
                 jnp.float32(fault != "no_sync"),
                 qmax_of(schedule, int(fault[4:])
                         if fault.startswith("wire") else 0))
        p = prog["init"](key)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, g1 = [], None
        for t, b in enumerate(batches, start=1):
            tok, tgt = (jax.device_put(
                b[k].reshape((n_pods, -1) + b[k].shape[1:]), sharding)
                for k in ("tokens", "targets"))
            p, m, v, ls = prog["step"](p, m, v, tok, tgt, jnp.float32(t),
                                       *knobs)
            losses.append(float(ls))
            if t == 1:
                g1 = jax.device_get(prog["first_grad"](m))
        out[fault] = {"losses": losses, "grad_norms": g1,
                      "change_norms": jax.device_get(prog["change"](p, key))}
        del p, m, v
    return out
