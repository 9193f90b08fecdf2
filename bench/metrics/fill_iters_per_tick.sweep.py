"""Water-fill iterations per tick-step: the mean over every tick-step
of the window of the summed ``fill_iters`` the fused program returns for
its three fills. A count."""
import numpy as np


def read(obs):
    """Mean fill iterations per tick-step."""
    it = obs.get("fill_iters")
    if it is None or len(it) == 0:
        return None
    return float(np.mean(it))
