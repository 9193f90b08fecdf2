"""Set-up time: process start to the window's start (imports, forest,
fleet, warm-up and any compile), host clock."""


def read(obs):
    """Seconds of set-up."""
    return obs.get("setup_s")
