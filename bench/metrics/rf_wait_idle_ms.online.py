"""The device's idle time inside the RF predictor's ``rf_wait`` spans,
in ms per tick: the spans, carried onto the trace's clock
(``_timeline.py``), less the union of device op intervals. Near 0, the
wait is the kernel's own time; near the ``rf_wait`` span's own length,
the host waits on launch latency or transfers, not on compute."""
import os

from harness import load_module

_timeline = load_module(os.path.join(os.path.dirname(__file__),
                                     "_timeline.py"))


def read(obs):
    """Device idle ms per tick inside `rf_wait`."""
    return _timeline.idle_ms_per_tick(obs, "rf_wait")
