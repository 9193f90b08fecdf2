"""Cross-pod sync time: collective-permute op time on a chip per step
(device trace), in ms."""
import os

from harness import load_module

_train = load_module(os.path.join(os.path.dirname(__file__), "_train.py"))


def read(obs):
    """ms per chip per step."""
    return _train.sync_ms_per_step(obs)
