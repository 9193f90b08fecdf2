"""Roofline share of the RF kernel: the least time of each launch's
algorithmic work (``work/rf.py``) over the summed device time of the
kernel's events in the trace, in %."""
import os

import tracereduce
from harness import load_module, work

_device = load_module(os.path.join(os.path.dirname(__file__), "_device.py"))


def read(obs):
    """Share of the roofline in %."""
    tr, sh = obs.get("trace"), obs.get("shapes")
    if tr is None or sh is None or "kernel" not in obs:
        return None
    win = obs["trace_window"]
    dev_ns = tracereduce.kernel_ns(tr, obs["kernel"], win)
    launches = tracereduce.kernel_count(tr, obs["kernel"], win)
    if dev_ns <= 0 or launches == 0:
        return None
    w = work("rf").per_launch(sh["rows"], sh["trees"], sh["depth"],
                              sh["features"])
    return _device.roofline(w["ops"] * launches, w["bytes"] * launches,
                            dev_ns / 1e9, obs["device_kind"])
