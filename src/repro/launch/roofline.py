"""Roofline analysis from dry-run artifacts.

Terms (seconds, per chip — cost_analysis of the SPMD module is already
per-partition):
  compute    = HLO_FLOPs / PEAK_FLOPS
  memory     = HLO_bytes / HBM_BW
  collective = ici_bytes / ICI_BW  +  dci_bytes / DCI_BW

collective bytes are parsed from the compiled HLO: operand+result bytes
of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, classified inter-pod (device-id stride >= pod size)
vs intra-pod from replica_groups / source_target_pairs.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link (intra-pod)
DCI_BW = 25e9                # B/s inter-pod ("WAN" hop of the paper)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\S+?))\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(", re.M)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
# literal groups: replica_groups={{0,256},{1,257},...}
_GROUPS_LIT_RE = re.compile(r"replica_groups=\{((?:\{[\d,]+\},?)+)\}")
# iota v2 format: replica_groups=[G,K]<=[d0,d1,...]T(p0,p1,...)
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _is_interpod(line: str, pod_stride: int) -> bool:
    """True when participants span device ids >= pod_stride apart."""
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
        return bool((ids.max(axis=1) - ids.min(axis=1) >= pod_stride).any())
    return False


@dataclass
class CollectiveStats:
    ici_bytes: int = 0
    dci_bytes: int = 0
    by_kind: Dict[str, int] = field(default_factory=dict)
    count: int = 0


def collective_bytes(hlo_text: str, pod_stride: int = 1 << 60
                     ) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in the HLO."""
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3).replace("-start", "")
        # result shape(s) precede the op name on the line
        head = line[:m.end(3)]
        nbytes = _shape_bytes(head.split("=")[1])
        st.count += 1
        st.by_kind[kind] = st.by_kind.get(kind, 0) + nbytes
        if _is_interpod(line, pod_stride):
            st.dci_bytes += nbytes
        else:
            st.ici_bytes += nbytes
    return st


@dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    ici_bytes: float
    dci_bytes: float
    model_flops_per_chip: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.ici_bytes / ICI_BW + self.dci_bytes / DCI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops_per_chip / max(self.flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Useful model FLOP/s achieved at the bound, vs chip peak:
        (MODEL_FLOPS / t_bound) / PEAK."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return (self.model_flops_per_chip / t) / PEAK_FLOPS

    def to_dict(self) -> Dict:
        return {
            "flops": self.flops, "bytes": self.bytes_accessed,
            "ici_bytes": self.ici_bytes, "dci_bytes": self.dci_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "dominant": self.dominant,
            "model_flops_per_chip": self.model_flops_per_chip,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops(cfg, shape_kind: str, global_tokens: int, n_chips: int,
                param_count: int, active_param_count: int) -> float:
    """MODEL_FLOPS = 6*N*D (train, N_active for MoE) or 2*N*D (fwd-only
    prefill/decode), per chip."""
    n = active_param_count
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n * global_tokens / n_chips
