"""95th percentile of every window tick's decision time, in ms: the
``FleetController.tick()`` call on the host clock less the WAN
simulator's time inside it."""
import numpy as np


def read(obs):
    """p95 decision time in ms."""
    t = obs.get("decision_s")
    if t is None or len(t) == 0:
        return None
    return float(np.percentile(np.asarray(t) * 1e3, 95))
