"""The online cell end to end on the CPU at a test size: the program
passes its check, the lower-precision control fails it, and so does the
program with a fault planted underneath the timed path."""
import numpy as np
import pytest

from _common import ONLINE, run_small


def test_program_is_correct():
    keep = {}
    line = run_small(ONLINE, keep=keep)
    assert line["correct"], line["checks"]
    assert 0 < line["attempted"] < len(keep["records"][1])
    assert set(line["metrics"]) == {"decision_ms_p95", "setup_s"}
    import harness
    bench = harness.Benchmark.load()
    ctl = bench.driver("online").control(keep["run"], keep["records"])
    lim = keep["run"].traffic["limits"]
    assert any(ctl[k] > lim[k] for k in lim), ctl


def test_traced_run_reads_the_spans():
    # the CPU has no device ops: the device readers find nothing and
    # stay out of the line; the span and host-clock readers report
    line = run_small(ONLINE, trace=True)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {
        "decision_ms_p50.online", "arbitrate_ms.online", "capture_ms.online",
        "predict_ms.online", "replan_ms.online"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["window_s"] > 0


def _state_unchanged(monkeypatch):
    from repro.core.local_opt import AimdAgent
    monkeypatch.setattr(AimdAgent, "step", lambda self, *a, **k: None)


def _answer_altered(monkeypatch):
    from repro.fleet.predictor import BatchedRfPredictor
    predict = BatchedRfPredictor.predict_rows
    monkeypatch.setattr(BatchedRfPredictor, "predict_rows",
                        lambda self, X: predict(self, X) * 1.01)


def _half_batch(monkeypatch):
    from repro.fleet.predictor import BatchedRfPredictor
    predict = BatchedRfPredictor.predict_rows

    def half(self, X):
        h = len(X) // 2
        return np.concatenate([predict(self, X[:h])] * 2)
    monkeypatch.setattr(BatchedRfPredictor, "predict_rows", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered,
                                   _half_batch])
def test_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    line = run_small(ONLINE)
    assert not line["correct"], line["checks"]


def test_span_time_excludes_the_simulator():
    import os
    from harness import BENCH_DIR, load_module
    spans = load_module(os.path.join(BENCH_DIR, "metrics", "_spans.py"))
    obs = {"span_t0": 10.0,
           "spans": [{"name": "tick", "t": 0.0, "dur_s": 1.0},
                     {"name": "capture", "t": 0.1, "dur_s": 0.4},
                     {"name": "tick", "t": 1.0, "dur_s": 1.0},
                     {"name": "capture", "t": 1.1, "dur_s": 0.4}],
           # 0.3 s of the first capture and 0.1 s of the second are the
           # simulator's; the interval past the second is not
           "sim_intervals": np.array([[10.0, 10.4], [11.4, 11.6],
                                      [11.7, 11.9]])}
    assert abs(spans.ms_per_tick(obs, "capture") - 1e3 * 0.4 / 2) < 1e-9
    assert spans.ms_per_tick(obs, "replan") is None
