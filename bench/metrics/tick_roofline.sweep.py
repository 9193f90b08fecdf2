"""Roofline share of the fused tick: the least time of each tick-step's
algorithmic work (``work/fleet_tick.py``, from shapes alone) over the
device time the sweep program took per tick-step in the trace, in %."""
import os

import tracereduce
from harness import load_module, work

_device = load_module(os.path.join(os.path.dirname(__file__), "_device.py"))


def read(obs):
    """Share of the roofline in %."""
    tr, sh = obs.get("trace"), obs.get("shapes")
    if tr is None or sh is None or "program" not in obs:
        return None
    dev_ns = tracereduce.module_ns(tr, obs["program"], obs["trace_window"])
    if dev_ns <= 0:
        return None
    w = work("fleet_tick").per_tick_step(**sh)
    steps = obs["work_ticks"]
    return _device.roofline(w["ops"] * steps, w["bytes"] * steps,
                            dev_ns / 1e9, obs["device_kind"])
