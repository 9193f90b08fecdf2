"""Read a cell's compared numbers for the program and for its control on
several seeds in one process: the readings its limits are set from.

  python3 bench/controls.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed this runs the cell as ``bench/run.py`` does (a short
window), then puts the control in the program's place: the plain
reference computed in the next lower precision than the configuration
states (float32 for its float64 control plane, bfloat16 for its float32
forest), read by the same comparison. Prints one JSON line per seed and
one with the largest readings of each side.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def readings(bench, cell: str, seed: int, seconds: float, devices,
             overrides=None):
    """(program readings, control readings) of one seed."""
    import harness
    keep = {}
    line = harness.run_cell(bench, cell, seed, seconds, False,
                            time.perf_counter(), devices,
                            overrides=overrides, keep=keep)
    run = keep["run"]
    driver = bench.driver(run.traffic["driver"])
    prog = {k: v["value"] for k, v in line["checks"].items()}
    return prog, driver.control(run, keep["records"])


def main(argv=None) -> int:
    """Run the seeds and print the readings."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import harness
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    bench = harness.Benchmark.load()
    worst = {"program": {}, "control_min": {}}
    for seed in [int(s) for s in args.seeds.split(",")]:
        prog, ctl = readings(bench, args.workload, seed, args.seconds,
                             jax.devices())
        print(json.dumps({"seed": seed, "program": prog, "control": ctl}),
              flush=True)
        for k, v in prog.items():
            worst["program"][k] = max(worst["program"].get(k, 0.0), v)
        for k, v in ctl.items():
            worst["control_min"][k] = min(worst["control_min"].get(k, v), v)
    print(json.dumps(worst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
