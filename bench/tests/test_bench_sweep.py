"""The sweep cell end to end on the CPU at a test size: the program
passes its check, the lower-precision control fails it, and so does the
program with a fault planted underneath the timed path."""
import numpy as np
import pytest

from _common import SWEEP, run_small


def test_program_is_correct():
    keep = {}
    line = run_small(SWEEP, keep=keep)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"sweep_ticks_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    # the control: the reference in float32 / bfloat16 in its place
    import harness
    bench = harness.Benchmark.load()
    ctl = bench.driver("sweep").control(keep["run"], keep["records"])
    lim = keep["run"].traffic["limits"]
    assert any(ctl[k] > lim[k] for k in lim), ctl


def _state_unchanged(monkeypatch):
    import repro.fleet.fused as fused
    monkeypatch.setattr(fused, "aimd_step_jnp",
                        lambda cons, target, *a, **k: (cons, target))


def _answer_altered(monkeypatch):
    import repro.fleet.fused as fused
    fill = fused.fill_rates_loop

    def altered(*a):
        rate, it, ok = fill(*a)
        return rate * 1.001, it, ok
    monkeypatch.setattr(fused, "fill_rates_loop", altered)


def _half_batch(monkeypatch):
    from repro.fleet.fused import FusedFleet
    sweep = FusedFleet.sweep

    def half(self, singles, bgs):
        h = len(singles) // 2
        outs = sweep(self, singles[:h], bgs[:h])
        return {k: np.concatenate([v, v]) for k, v in outs.items()}
    monkeypatch.setattr(FusedFleet, "sweep", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered,
                                   _half_batch])
def test_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    line = run_small(SWEEP)
    assert not line["correct"], line["checks"]
