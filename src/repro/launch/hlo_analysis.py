"""Trip-count-aware HLO cost analysis.

XLA's ``compiled.cost_analysis()`` counts a while-loop BODY ONCE — with
scan-over-layers (and microbatch scans) that under-weights flops, bytes
and collective traffic by the trip count. This analyzer parses the
optimized HLO text, builds the computation call graph (while bodies,
fusions, calls, conditionals), weights every computation by the product
of enclosing ``known_trip_count``s, and accumulates:

  * dot FLOPs (2 x result x contracting) — the MXU work
  * HBM byte proxy — operand+result bytes of top-level (non-fused)
    instructions; fusion internals cost 0 bytes (VMEM/registers)
  * collective bytes by kind, split intra-pod (ICI) / inter-pod (DCI)

All weighted by loop multiplicity. This feeds `repro.launch.roofline`.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DT = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
       "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
       "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\([^)]*\)\s*->", re.M)
_SHAPE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                    r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
                    r"([\w\-]+)\(", re.M)
_TRIP = re.compile(r"known_trip_count[^0-9]*(\d+)")
_CALLEE = {
    "while": re.compile(r"body=%?([\w.\-]+)"),
    "cond": re.compile(r"condition=%?([\w.\-]+)"),
    "fusion": re.compile(r"calls=%?([\w.\-]+)"),
    "call": re.compile(r"to_apply=%?([\w.\-]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
    "reducer": re.compile(r"to_apply=%?([\w.\-]+)"),
}
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_GROUPS_LIT_RE = re.compile(r"replica_groups=\{((?:\{[\d,]+\},?)+)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")

COLLECTIVES = {"all-gather", "all-gather-start", "all-reduce",
               "all-reduce-start", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-permute-start"}

# alias/structural ops: no HBM traffic of their own
_NO_BYTES = {"parameter", "tuple", "get-tuple-element", "while",
             "conditional", "call", "bitcast", "constant", "iota",
             "after-all", "opt-barrier", "partition-id", "replica-id"}


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _all_shape_bytes(text: str) -> int:
    return sum(_shape_elems(m.group(2)) * _DT[m.group(1)]
               for m in _SHAPE.finditer(text))


_DEF_RE = re.compile(r"^\s+(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*"
                     r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                     r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]", re.M)
_DOT_OPS = re.compile(r"dot\((%[\w.\-]+)(?:,\s*(%[\w.\-]+))?\)")


def _dot_flops(line: str, shapes: Dict[str, List[int]]) -> float:
    """2 x prod(result) x prod(lhs contracting dims); operand shapes come
    from the symbol table (HLO operands are bare names)."""
    head = line.split("dot(")[0]
    rm = _SHAPE.search(head)
    if not rm:
        return 0.0
    result = _shape_elems(rm.group(2))
    om = _DOT_OPS.search(line)
    lhs_dims = shapes.get(om.group(1), []) if om else []
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    contract = 1
    if m and m.group(1):
        for i in m.group(1).split(","):
            if int(i) < len(lhs_dims):
                contract *= lhs_dims[int(i)]
    return 2.0 * result * contract


def _is_interpod(line: str, pod_stride: int) -> bool:
    m = _PAIRS_RE.search(line)
    if m:
        pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1))
        return any(abs(int(a) - int(b)) >= pod_stride for a, b in pairs)
    m = _GROUPS_LIT_RE.search(line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in re.findall(r"\d+", grp)]
            if ids and max(ids) - min(ids) >= pod_stride:
                return True
        return False
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        import numpy as np
        g, k = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = [int(x) for x in m.group(4).split(",")] if m.group(4) \
            else list(range(len(dims)))
        ids = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm)
        ids = ids.reshape(g, k)
        return bool((ids.max(1) - ids.min(1) >= pod_stride).any())
    return False


@dataclass
class Costs:
    dot_flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0
    dci_bytes: float = 0.0
    coll_by_kind: Dict[str, float] = field(default_factory=dict)
    n_collectives: int = 0
    n_whiles: int = 0


def analyze(hlo_text: str, pod_stride: int = 1 << 60) -> Costs:
    # ---- split into computations -------------------------------------
    # headers look like:  [ENTRY ]%name (args...) -> type {   — arg lists
    # can contain nested parens (tuple types), so match loosely.
    comps: Dict[str, List[str]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        s = line.rstrip()
        if s.endswith("{") and "->" in s and not line.startswith(" "):
            tok = s.split()[1] if s.startswith("ENTRY") else s.split()[0]
            cur = tok.lstrip("%")
            comps[cur] = []
            if s.startswith("ENTRY"):
                entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)

    # ---- symbol table: %name -> dims (global; names are unique-ish,
    # collisions across computations resolve to identical shapes in
    # practice for the operands we care about) ------------------------
    shapes: Dict[str, List[int]] = {}
    for m in _DEF_RE.finditer(hlo_text):
        shapes[m.group(1)] = [int(x) for x in m.group(3).split(",") if x]

    # ---- per-computation raw costs + call edges ----------------------
    edges: Dict[str, List[Tuple[str, float, bool]]] = defaultdict(list)
    # edge: (callee, multiplier, passes_bytes) — fusion internals get no
    # byte accounting
    local = {}
    n_whiles = 0
    for name, lines in comps.items():
        c = Costs()
        for line in lines:
            mi = _INSTR.match(line)
            if not mi:
                continue
            result_part, op = mi.group(1), mi.group(2)
            if op == "dot":
                c.dot_flops += _dot_flops(line, shapes)
            if op in COLLECTIVES:
                kind = op.replace("-start", "")
                b = _all_shape_bytes(result_part)
                c.coll_by_kind[kind] = c.coll_by_kind.get(kind, 0.0) + b
                c.n_collectives += 1
                if _is_interpod(line, pod_stride):
                    c.dci_bytes += b
                else:
                    c.ici_bytes += b
            # HBM byte proxy: operands + results of instructions that
            # actually MOVE data. Structural ops (tuple plumbing, loop
            # headers re-listing the whole carry, parameters, bitcasts)
            # are aliases — counting them charges scan carries per
            # iteration (~1000x phantom bytes for decode caches).
            if op in _NO_BYTES:
                pass
            elif op == "dynamic-slice":
                c.hbm_bytes += 2 * _all_shape_bytes(result_part)
            elif op == "dynamic-update-slice":
                # in-place: traffic ~ the update slice, not the buffer
                all_b = _all_shape_bytes(line)
                big = max((_shape_elems(m.group(2)) * _DT[m.group(1)]
                           for m in _SHAPE.finditer(line)), default=0)
                c.hbm_bytes += max(all_b - 2 * big, 0)
            else:
                c.hbm_bytes += _all_shape_bytes(line)
            # call edges
            if op == "while":
                n_whiles += 1
                trip = 1.0
                mt = _TRIP.search(line)
                if mt:
                    trip = float(mt.group(1))
                for key in ("while", "cond"):
                    mb = _CALLEE[key].search(line)
                    if mb:
                        edges[name].append((mb.group(1), trip, True))
            elif op == "fusion":
                mb = _CALLEE["fusion"].search(line)
                if mb:
                    edges[name].append((mb.group(1), 1.0, False))
            elif op in ("call", "async-start", "custom-call", "reduce",
                        "reduce-window", "sort", "scatter", "select-and-scatter",
                        "map", "all-reduce", "reduce-scatter"):
                mb = _CALLEE["call"].search(line)
                if mb:
                    edges[name].append((mb.group(1), 1.0, False))
            elif op == "conditional":
                mb = _CALLEE["branches"].search(line)
                if mb:
                    for b in re.findall(r"%?([\w.\-]+)", mb.group(1)):
                        edges[name].append((b, 1.0, True))
        local[name] = c

    # ---- weight propagation ------------------------------------------
    weights: Dict[str, float] = defaultdict(float)
    byte_weights: Dict[str, float] = defaultdict(float)
    if entry is None:
        entry = next(iter(comps), None)
    if entry is None:
        return Costs()
    stack = [(entry, 1.0, 1.0)]
    seen_guard = 0
    while stack:
        seen_guard += 1
        if seen_guard > 200000:
            break
        name, w, bw = stack.pop()
        weights[name] += w
        byte_weights[name] += bw
        for callee, mult, passes in edges.get(name, ()):  # noqa: B007
            if callee in comps:
                stack.append((callee, w * mult, bw * mult if passes else 0.0))

    total = Costs(n_whiles=n_whiles)
    for name, c in local.items():
        w = weights.get(name, 0.0)
        bw = byte_weights.get(name, 0.0)
        total.dot_flops += c.dot_flops * w
        total.hbm_bytes += c.hbm_bytes * bw
        total.ici_bytes += c.ici_bytes * w
        total.dci_bytes += c.dci_bytes * w
        total.n_collectives += int(c.n_collectives * max(w, 1.0)) \
            if c.n_collectives else 0
        for k, v in c.coll_by_kind.items():
            total.coll_by_kind[k] = total.coll_by_kind.get(k, 0.0) + v * w
    return total
