"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and every piece of it under
``bench/`` by name (see ``bench/harness.py``), sets up and warms up the
cell's shapes, measures for ``--seconds``, compares what the timed path
produced with the plain reference, and prints one JSON line as the last
line of standard output (the compared numbers and their limits also go
to standard error). ``--trace 1`` traces the window with the JAX
profiler and reports the cell's per-layer metrics instead of its
end-to-end ones.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the system under test is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# the TPU runtime's own logs would otherwise go to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))


def _plain(x):
    """JSON-safe: numpy scalars to Python numbers, non-finite to 1e300."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if hasattr(x, "item") and not isinstance(x, (str, bytes)):
        x = x.item()
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300 if x > 0 or math.isnan(x) else -1e300
    return x


def main(argv=None) -> int:
    """Parse the arguments, refuse without a TPU, run, print."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    bench = harness.Benchmark.load()
    cell = bench.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench/run.py: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench/run.py: {args.workload} needs {cell['chips']} "
                 f"chips, JAX finds {len(devices)}")
    # the compile cache lives inside the checkout, at the fixed path
    # use_compile_cache() picks when no directory is given, whatever the
    # environment says: two checkouts never share one
    from repro.launch.compile_cache import DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = _plain(harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START,
                                  devices))
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
