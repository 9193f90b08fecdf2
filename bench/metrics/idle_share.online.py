"""Device idle share of the traced window: 1 - (union of device op
intervals / window), in %."""
import os

from harness import load_module

_device = load_module(os.path.join(os.path.dirname(__file__), "_device.py"))


def read(obs):
    """Idle share in %."""
    return _device.idle_share(obs)
