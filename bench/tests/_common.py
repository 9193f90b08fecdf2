"""Shared by the benchmark's tests: import paths and a CPU-sized cell."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SWEEP = "fleet8-aws.sweep-runtime"
ONLINE = "fleet8-aws.online-runtime"
_SMALL_FOREST = {}


def small_forest() -> dict:
    """A forest small enough for the CPU backend, fitted once per
    process by the same maker as the committed tables."""
    if not _SMALL_FOREST:
        import tempfile
        from harness import load_module
        maker = load_module(os.path.join(BENCH, "fixtures", "make_forest.py"))
        path = os.path.join(tempfile.mkdtemp(prefix="bench-forest-"),
                            "small.npz")
        maker.write_tables(path, samples=60, seed=7, trees=8, depth=5)
        _SMALL_FOREST.update(n_trees=8, depth=5, dataset_samples=60,
                             dataset_seed=7, bootstrap_seed=0, tables=path)
    return dict(_SMALL_FOREST)


def small(cell: str) -> dict:
    """Overrides that shrink `cell` to a CPU test's size; every other
    setting is the cell's own."""
    ov = {"config": {"forest": small_forest()}}
    if cell == SWEEP:
        ov["traffic"] = {"variants": 4, "ticks": 8, "check_requests": 1}
    else:
        ov["traffic"] = {"warmup_ticks": 4}
    return ov


def run_small(cell: str, seed: int = 2 ** 31 + 11, seconds: float = 1.0,
              keep=None, trace: bool = False):
    """Run `cell` once on this process's devices at the test size,
    skipping only the harness's look for a chip."""
    import time
    import jax
    import harness
    bench = harness.Benchmark.load()
    return harness.run_cell(bench, cell, seed, seconds, trace,
                            time.perf_counter(), jax.devices(),
                            overrides=small(cell), keep=keep)
