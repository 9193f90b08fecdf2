"""Shared arithmetic of the training cell's trace readers: the steps the
driver's ``bench.step`` annotations put inside the traced window, and
the collective-permute time a chip spends per step."""
from __future__ import annotations

from typing import Any, Dict, Optional

STEP = "bench.step"


def steps_in_window(obs: Dict[str, Any]) -> int:
    """Number of ``bench.step`` host spans wholly inside the window."""
    tr, win = obs.get("trace"), obs.get("trace_window")
    if tr is None or win is None:
        return 0
    return sum(1 for ev in tr.host
               if ev.name == STEP and ev.start >= win[0] and ev.end <= win[1])


def sync_ms_per_step(obs: Dict[str, Any]) -> Optional[float]:
    """Collective-permute op time in ms per chip per step, or None
    without steps or devices."""
    import tracereduce
    tr, steps = obs.get("trace"), steps_in_window(obs)
    if not steps or not tr.ops:
        return None
    total, _ = tracereduce.collective_ns(tr, window=obs["trace_window"])
    return total * 1e-6 / (len(tr.ops) * steps)
