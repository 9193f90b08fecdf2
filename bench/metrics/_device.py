"""Shared arithmetic of the device readers."""
from __future__ import annotations

from typing import Any, Dict, Optional


def idle_share(obs: Dict[str, Any]) -> Optional[float]:
    """1 - busy / window, in %, averaged over the devices traced."""
    import tracereduce
    tr, win = obs.get("trace"), obs.get("trace_window")
    if tr is None or win is None or not tr.ops:
        return None
    busy = tracereduce.busy_ns(tr, win)
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / (win[1] - win[0]))


def roofline(ops: float, nbytes: float, seconds: float, kind: str
             ) -> Optional[float]:
    """Least time of the work (the larger of ops over peak FLOP/s and
    bytes over peak bytes/s) over the measured time, in %."""
    from harness import peak_for
    if seconds <= 0:
        return None
    peak = peak_for(kind)
    least = max(ops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds

