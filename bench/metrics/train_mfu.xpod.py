"""The whole training step's share of the chips' bf16 peak: operations
the forward and backward passes need per token (``work/lm_train.py``,
from shapes alone; recomputation does not count) times the tokens of the
steps inside the traced window, over the window times the chips times
the peak, in %."""
import os

from harness import load_module, peak_for, work

_train = load_module(os.path.join(os.path.dirname(__file__), "_train.py"))


def read(obs):
    """Model FLOP/s utilization in %."""
    steps = _train.steps_in_window(obs)
    if not steps or "shapes" not in obs:
        return None
    win = obs["trace_window"]
    flops = work("lm_train").flops_per_token(**obs["shapes"]) * \
        obs["tokens_per_step"] * steps
    peak = peak_for(obs["device_kind"])["flops_bf16"]
    return 100.0 * flops / ((win[1] - win[0]) / 1e9 * obs["n_devices"] * peak)
