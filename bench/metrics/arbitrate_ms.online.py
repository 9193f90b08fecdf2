"""Mean per tick of the fleet controller's own ``arbitrate`` span (host
wall time, ``obs="on"`` in the traced run) less the WAN simulator's
time inside it, in ms."""
import os

from harness import load_module

_spans = load_module(os.path.join(os.path.dirname(__file__), "_spans.py"))


def read(obs):
    """ms per tick the control plane spends in `arbitrate`."""
    return _spans.ms_per_tick(obs, "arbitrate")
