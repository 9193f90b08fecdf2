"""Write a configuration's trained forest as a committed table file.

  python3 bench/fixtures/make_forest.py fleet8-aws

The deployment's model is a random forest fitted once, on the host, by
the repo's trainer (``train_default_forest``) on the configured dataset
and seeds. Its tables (a feature index and a threshold per internal node,
a value per leaf) are committed under ``bench/fixtures/`` and loaded by
both the program and the reference, so no run trains or depends on a
cache of its own.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_tables(path: str, samples: int, seed: int, trees: int,
                 depth: int) -> None:
    """Fit the forest and save its tables to `path` (.npz)."""
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
    from repro.wan.dataset import train_default_forest
    rf = train_default_forest(n_samples=samples, seed=seed, n_trees=trees,
                              depth=depth)[0]
    assert rf.feat.min() >= -1 and rf.feat.max() < 127
    np.savez_compressed(path, feat=rf.feat.astype(np.int8),
                        thr=rf.thr.astype(np.float32),
                        leaf=rf.leaf.astype(np.float32))


def main(argv=None) -> int:
    """Write the tables named by a configuration's ``forest`` block."""
    name = (argv or sys.argv[1:])[0]
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        fc = json.load(f)["forest"]
    write_tables(os.path.join(BENCH_DIR, fc["tables"]),
                 fc["dataset_samples"], fc["dataset_seed"], fc["n_trees"],
                 fc["depth"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
