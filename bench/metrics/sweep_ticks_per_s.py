"""What-if throughput: tick-steps (variants x ticks) of every request
completed in the window, over the window's seconds, host clock."""


def read(obs):
    """Tick-steps per second."""
    if "work_ticks" not in obs or not obs.get("window_s"):
        return None
    return obs["work_ticks"] / obs["window_s"]
