"""Median of every window tick's decision time (the tick on the host
clock less the WAN simulator's time inside it), in ms: the steadier
companion of the p95."""
import numpy as np


def read(obs):
    """p50 decision time in ms."""
    t = obs.get("decision_s")
    if t is None or len(t) == 0:
        return None
    return float(np.median(np.asarray(t) * 1e3))
