"""Work of one fused fleet tick-step (one tick of one variant), from
shapes alone; never from iteration counts or the program's own ops.

* forest: the node visits and leaf adds of its R = J x P x (P-1) rows;
* water-fill: three fills (probe, capture, achieved), each counted as one
  pass over the N x N pairs at FILL_OPS_PER_PAIR operations, the least
  any progressive fill must do;
* bytes: the tick's schedule inputs (two float64 N x N matrices), its
  per-job outputs (four float64 numbers and one int32 per job, three
  int32 fill counts and a flag), and the forest tables once per launch,
  shared by the launch's variants x ticks tick-steps.
"""
import os

from harness import load_module

_rf = load_module(os.path.join(os.path.dirname(__file__), "rf.py"))

# one filling pass per pair: weight products and masks, row/column
# sums, the three head-room quotients, their minimum, the rate update,
# and the two saturation tests
FILL_OPS_PER_PAIR = 20
FILLS_PER_TICK = 3


def per_tick_step(jobs: int, slice: int, dcs: int, trees: int, depth: int,
                  features: int, variants: int, ticks: int) -> dict:
    """{"ops", "bytes"} of one tick-step."""
    rows = jobs * slice * (slice - 1)
    rf = _rf.per_launch(rows, trees, depth, features)
    tables = rf["bytes"] - rows * features * 4 - rows * 4
    ops = rf["ops"] + FILLS_PER_TICK * dcs * dcs * FILL_OPS_PER_PAIR
    nbytes = (2 * dcs * dcs * 8 + jobs * (4 * 8 + 4) + 3 * 4 + 1
              + tables / (variants * ticks))
    return {"ops": ops, "bytes": nbytes}
