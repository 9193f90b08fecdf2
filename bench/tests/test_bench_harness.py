"""Discovery by name, and refusals of bench/run.py."""
import json
import os
import shutil
import subprocess
import sys

from _common import BENCH, REPO, SWEEP, ONLINE
import harness


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_added_files_are_found_without_edits(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = _digest(root / "bench")
    b = root / "bench"
    cfg = json.loads((b / "configs" / "fleet8-aws.json").read_text())
    cfg["m_total"] = 4
    (b / "configs" / "fleet4-test.json").write_text(json.dumps(cfg))
    (b / "traffic" / "sweep-test.json").write_text(json.dumps(
        dict(json.loads((b / "traffic" / "sweep-runtime.json").read_text()),
             variants=2)))
    (b / "metrics" / "requests_done.sweep.py").write_text(
        "def read(obs):\n    return obs.get('attempted')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="fleet4-test",
                                file="bench/configs/fleet4-test.json"))
    spec["workloads"].append({"name": "fleet4-test.sweep-test",
                              "config": "fleet4-test",
                              "traffic": "sweep-test", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "requests_done.sweep", "unit": "n",
                              "better": "higher",
                              "source": "program_counter", "layer": "t",
                              "moves": "sweep_ticks_per_s",
                              "workloads": ["fleet4-test.sweep-test"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digest(root / "bench")
    assert all(after[k] == v for k, v in before.items())  # nothing edited
    bench = harness.Benchmark.load(str(root))
    listing = bench.listing()
    assert "fleet4-test" in listing["configs"]
    assert "sweep-test" in listing["traffic"]
    assert "requests_done.sweep" in listing["metrics"]
    assert bench.config("fleet4-test")["m_total"] == 4
    assert bench.traffic("sweep-test")["variants"] == 2
    assert bench.cell("fleet4-test.sweep-test")["config"] == "fleet4-test"
    names = [m["name"] for m in
             bench.metrics_for("fleet4-test.sweep-test", trace=True)]
    assert names == ["requests_done.sweep"]
    assert bench.reader("requests_done.sweep")({"attempted": 3}) == 3


def test_metrics_for_each_cell():
    bench = harness.Benchmark.load()
    e2e = {c: [m["name"] for m in bench.metrics_for(c, False)]
           for c in (SWEEP, ONLINE)}
    assert e2e == {SWEEP: ["sweep_ticks_per_s", "setup_s"],
                   ONLINE: ["decision_ms_p95", "setup_s"]}
    for c in (SWEEP, ONLINE):
        per_layer = bench.metrics_for(c, True)
        assert per_layer and all(c in m["workloads"] for m in per_layer)
        for m in per_layer + bench.metrics_for(c, False):
            assert callable(bench.reader(m["name"]))


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SWEEP, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_refuses_without_tpu():
    r = _run(REPO, {})
    assert r.returncode != 0
    assert "no TPU" in r.stderr and r.stdout.strip() == ""


def test_refuses_without_the_system(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    r = _run(str(tmp_path), {})
    assert r.returncode != 0 and r.stdout.strip() == ""
