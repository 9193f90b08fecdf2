"""The trace reduction, on a recorded TPU v5e trace and on hand-made
intervals."""
import os

import pytest

from _common import BENCH
import tracereduce as tr_

FIXTURE = os.path.join(BENCH, "fixtures", "fleet-small.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    # two RF launches (bench.tick) and one fused sweep launch
    # (bench.request) inside bench.window, recorded on one TPU v5 lite
    return tr_.load(FIXTURE)


def test_fixture_planes(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    assert sorted({e.name for e in trace.host}) == [
        "bench.request", "bench.tick", "bench.window"]


def test_fixture_kernel_and_programs(trace):
    # the whole recording: both RF launches, 7594 + 7593 ns
    assert tr_.kernel_count(trace, "rf_predict") == 2
    assert tr_.kernel_ns(trace, "rf_predict") == 7594 + 7593
    # the consumer of %rf_predict.1 is not the kernel
    assert tr_.kernel_count(trace, "slice_reduce_fusion") == 2
    assert tr_.module_ns(trace, "jit_rf_predict_pallas") == 8916 + 8922
    assert tr_.module_ns(trace, "jit__lambda") == 1038763


def test_fixture_window(trace):
    win = tr_.annotation_window(trace, "bench.window")
    assert win == (45202058.0, 45202058.0 + 13754799.0)
    busy = tr_.busy_ns(trace, win)["/device:TPU:0"]
    # every op is nested in or follows the launches: busy lies between
    # the sweep program alone and the whole recording's op union
    assert 1038763 <= busy <= tr_.busy_ns(trace)["/device:TPU:0"]
    gaps = dict(tr_.idle_gaps(trace, win))
    assert set(gaps) <= {"bench.window", "bench.tick", "bench.request",
                         "none"}
    assert sum(gaps.values()) * 1e9 == pytest.approx(
        (win[1] - win[0]) - busy, rel=1e-9)
    top = tr_.top_ops(trace, 3, win)
    assert top[0][0].startswith("while.") and len(top) == 3


def test_union_clip_minus():
    assert tr_.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr_.clip([(0, 4), (5, 7)], (3, 6)) == [(3, 4), (5, 6)]
    assert tr_.minus([(0, 10), (12, 15)], [(2, 3), (5, 13)]) == [
        (0, 2), (3, 5), (13, 15)]
    assert tr_.length([(0, 4), (5, 7)]) == 6


def _ev(name, start, dur):
    return tr_.Event(name, float(start), float(dur))


def test_collective_and_busy_synthetic():
    t = tr_.Trace(ops={
        "/device:TPU:0": [_ev("%collective-permute-start.1 = x", 0, 10),
                          _ev("%fusion.2 = y", 4, 2),
                          _ev("%rf_predict.3 = z", 20, 5)],
        "/device:TPU:1": [_ev("%collective-permute-done.1 = x", 0, 4)]})
    total, exposed = tr_.collective_ns(t)
    assert (total, exposed) == (14, 12)
    assert tr_.busy_ns(t, (0, 30)) == {"/device:TPU:0": 15,
                                       "/device:TPU:1": 4}
    assert tr_.kernel_ns(t, "rf_predict") == 5
    assert tr_.kernel_ns(t, "rf_pred") == 0


def test_idle_gaps_by_host_annotation():
    t = tr_.Trace(ops={"/device:TPU:0": [_ev("%a.1 = x", 10, 10)]},
                  host=[_ev("bench.window", 0, 40), _ev("bench.tick", 0, 9),
                        _ev("bench.tick", 25, 10)])
    gaps = dict(tr_.idle_gaps(t, (0, 40)))
    # [0,10) mid 5 in the first tick; [20,40) mid 30 in the second
    assert gaps == {"bench.tick": pytest.approx(30e-9)}
