"""Training throughput: tokens of every pod in every step completed in
the window, over the window's seconds, host clock."""


def read(obs):
    """Tokens per second."""
    if "tokens" not in obs or not obs.get("window_s"):
        return None
    return obs["tokens"] / obs["window_s"]
