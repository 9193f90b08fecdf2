"""Shared arithmetic of the span readers."""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


def _sim_before(iv: np.ndarray, cum: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Simulator seconds elapsed before each time in `t`, given its
    sorted, disjoint intervals `iv` [K, 2] and their running total."""
    k = np.searchsorted(iv[:, 0], t, side="right")       # intervals begun
    done = np.where(k > 0, cum[np.maximum(k - 1, 0)], 0.0)
    last = iv[np.maximum(k - 1, 0)]
    # the interval begun last may still be open at t
    over = np.where(k > 0, np.maximum(last[:, 1] - t, 0.0), 0.0)
    return done - over


def ms_per_tick(obs: Dict[str, Any], name: str) -> Optional[float]:
    """Mean per tick, in ms, of the fleet controller's own span `name`
    (host wall time, recorded with ``obs="on"`` in the traced run), less
    the time the WAN simulator ran inside it."""
    spans = obs.get("spans")
    if not spans:
        return None
    ticks = sum(1 for s in spans if s["name"] == "tick")
    mine = [s for s in spans if s["name"] == name]
    if not ticks or not mine:
        return None
    t0 = obs["span_t0"] + np.array([s["t"] for s in mine])
    t1 = t0 + np.array([s["dur_s"] for s in mine])
    total = float(np.sum(t1 - t0))
    iv = obs.get("sim_intervals")
    if iv is not None and len(iv):
        iv = iv[np.argsort(iv[:, 0])]
        cum = np.cumsum(iv[:, 1] - iv[:, 0])
        total -= float(np.sum(_sim_before(iv, cum, t1)
                              - _sim_before(iv, cum, t0)))
    return 1e3 * total / ticks
