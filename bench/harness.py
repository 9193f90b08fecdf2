"""The benchmark harness: finds every piece of a cell by name, runs it,
and assembles the result line.

Layout under ``bench/`` (each found by the name ``BENCHMARK.json`` gives):

  configs/<config>.json    a deployment: topology, jobs, constants
  fixtures/                what a configuration loads (its forest tables)
  traffic/<traffic>.json   a traffic mix: the driver that runs it, its
                           parameters and the limits of its checks
  drivers/<driver>.py      how a window drives the system (set-up, the
                           timed loop, the comparison with the reference)
  metrics/<metric>.py      one reader per metric: ``read(obs)`` returns a
                           number or None when the run has nothing to read
  work/<name>.py           operations and bytes from shapes alone
  reference/<name>.py      the plain reference a configuration names
  peaks.json               published peaks, keyed by device kind

Adding a configuration, a traffic mix or a metric is adding files and
``BENCHMARK.json`` entries; nothing here is edited for it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
ANNOTATION_PREFIX = "bench."


def load_module(path: str, name: Optional[str] = None):
    """Import a Python file by path (file names may hold '.' and '-')."""
    name = name or "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Any:
    """Parse one JSON file."""
    with open(path) as f:
        return json.load(f)


# ----------------------------------------------------------------------
# discovery
# ----------------------------------------------------------------------
@dataclass
class Benchmark:
    """``BENCHMARK.json`` and the directory its files are found in."""
    spec: Dict[str, Any]
    root: str = REPO_ROOT

    @classmethod
    def load(cls, root: str = REPO_ROOT) -> "Benchmark":
        """Read ``<root>/BENCHMARK.json``."""
        return cls(read_json(os.path.join(root, "BENCHMARK.json")), root)

    @property
    def bench_dir(self) -> str:
        """Directory holding configs/, traffic/, metrics/, ..."""
        return os.path.join(self.root, "bench")

    def cell(self, name: str) -> Dict[str, Any]:
        """The workload entry called `name`."""
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r}; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration file of configuration `name`."""
        for c in self.spec["configs"]:
            if c["name"] == name:
                return read_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r}")

    def traffic(self, name: str) -> Dict[str, Any]:
        """The traffic mix file ``traffic/<name>.json``."""
        return read_json(os.path.join(self.bench_dir, "traffic",
                                      name + ".json"))

    def driver(self, name: str):
        """The driver module ``drivers/<name>.py``."""
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        name + ".py"))

    def reader(self, metric: str) -> Callable[[Dict[str, Any]],
                                              Optional[float]]:
        """The reader ``metrics/<metric>.py``'s ``read``."""
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py")).read

    def metrics_for(self, cell: str, trace: bool) -> List[Dict[str, Any]]:
        """The metrics a run of `cell` reports: its end-to-end metrics
        with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell in m["workloads"] or
                ("workloads" not in m and m["moves"] in names)] \
            if "per_layer" in self.spec else []

    def listing(self) -> Dict[str, List[str]]:
        """Every configuration, traffic mix, driver and reader on disk."""
        def names(sub, ext):
            d = os.path.join(self.bench_dir, sub)
            if not os.path.isdir(d):
                return []
            return sorted(f[:-len(ext)] for f in os.listdir(d)
                          if f.endswith(ext) and not f.startswith("_"))
        return {"configs": names("configs", ".json"),
                "traffic": names("traffic", ".json"),
                "drivers": names("drivers", ".py"),
                "metrics": names("metrics", ".py"),
                "work": names("work", ".py")}


def peak_for(kind: str, path: Optional[str] = None) -> Dict[str, Any]:
    """The published peaks of `kind`; an unknown kind is an error."""
    table = read_json(path or os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"peaks.json; have {sorted(table['devices'])}")
    return table["devices"][kind]


def work(name: str):
    """The work-counter module ``work/<name>.py``."""
    return load_module(os.path.join(BENCH_DIR, "work", name + ".py"))


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
@dataclass
class Run:
    """What a driver gets: the cell's files, the run's arguments, and the
    window's instrumentation."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float
    reference: Any = None
    devices: List[Any] = field(default_factory=list)
    obs: Dict[str, Any] = field(default_factory=dict)
    _tmp: Optional[tempfile.TemporaryDirectory] = None

    def annotate(self, name: str):
        """A host span on the profiler's clock in a traced run."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window: records the set-up time at its start;
        traces it with ``--trace 1``; reads the device memory peak at its
        end."""
        import jax
        if self.trace:
            self._tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
        self.obs["setup_s"] = time.perf_counter() - self.t_start
        try:
            with self.annotate("window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            self.obs["memory_peak_bytes"] = memory_peak(self.devices)
        if self.trace:
            self._reduce_trace()

    def _reduce_trace(self) -> None:
        import glob
        import tracereduce
        files = glob.glob(os.path.join(self._tmp.name, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        tr = tracereduce.Trace()
        for path in files:
            part = tracereduce.load(path, prefix=ANNOTATION_PREFIX)
            tr.ops.update(part.ops)
            tr.modules.update(part.modules)
            tr.host.extend(part.host)
        self._tmp.cleanup()
        self.obs["trace"] = tr
        self.obs["trace_window"] = tracereduce.annotation_window(
            tr, ANNOTATION_PREFIX + "window")


def memory_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of `devices`, where reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(bench: Benchmark, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, devices,
             overrides: Optional[Dict[str, Dict[str, Any]]] = None,
             keep: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell once and return its result line as a dict.

    `overrides` merges keys into the config / traffic dicts (tests run a
    cell at a size the CPU can hold); `keep`, where given, receives the
    run's observations (what the controls read)."""
    cell = bench.cell(name)
    cfg = dict(bench.config(cell["config"]))
    traffic = dict(bench.traffic(cell["traffic"]))
    for key, src in (("config", cfg), ("traffic", traffic)):
        src.update((overrides or {}).get(key, {}))
    ref = None
    if "reference" in cfg:
        ref = load_module(os.path.join(bench.bench_dir, "reference",
                                       cfg["reference"] + ".py"))
    run = Run(cell=cell, config=cfg, traffic=traffic, seed=int(seed),
              seconds=float(seconds), trace=bool(trace), t_start=t_start,
              reference=ref, devices=list(devices)[:cell["chips"]])
    bench.driver(traffic["driver"]).run(run)
    obs = run.obs
    if keep is not None:
        keep.update(obs, run=run)
    d0 = run.devices[0]
    obs["device_kind"] = d0.device_kind
    obs["n_devices"] = len(run.devices)

    metrics = {}
    for m in bench.metrics_for(name, trace):
        value = bench.reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": obs.get("memory_peak_bytes")}
    line: Dict[str, Any] = {}
    if trace and obs.get("trace") is not None:
        import tracereduce
        tr, win = obs["trace"], obs["trace_window"]
        busy = tracereduce.busy_ns(tr, win)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        device["window_s"] = (win[1] - win[0]) / 1e9 if win else None
        line["breakdown"] = {
            "device_ops": [list(r) for r in tracereduce.top_ops(tr, 10, win)],
            "idle_gaps": [list(r) for r in tracereduce.idle_gaps(tr, win)]
            if win else []}
    checks = obs.get("checks", [])
    correct = bool(checks) and obs.get("error") is None and \
        all(c["value"] <= c["limit"] for c in checks)
    out = {"correct": correct, "attempted": obs.get("attempted", 0),
           "failed": obs.get("failed", 0), "metrics": metrics,
           "device": device}
    out.update(line)
    if obs.get("error"):
        out["error"] = obs["error"]
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out
