"""The program's spans carried onto the trace's clock (``_timeline.py``)
and the device idle time inside them (``rf_wait_idle_ms.online``), on
a hand-made trace with a known answer."""
import os

import pytest

from _common import BENCH
import tracereduce as tr_
from harness import Benchmark, load_module

_timeline = load_module(os.path.join(BENCH, "metrics", "_timeline.py"))

# the trace's clock runs 5 s (5e9 ns) ahead of the program's perf_counter
OFFSET_NS = 5e9
SPAN_T0 = 100.0                          # perf_counter seconds


def _ev(name, start, dur):
    return tr_.Event(name, float(start), float(dur))


def _span(name, t_us, dur_us):
    return {"name": name, "t": t_us * 1e-6, "dur_s": dur_us * 1e-6}


# the profiler puts the device's ops this much early on the host clock
DEVICE_LEAD_NS = 1e6


def _obs(n_ticks=2, jitter_ns=None, ops=True, wake_us=None):
    """`n_ticks` ticks 1000 us apart: a tick span of 900 us holding an
    rf_wait span of 300 us at 100 us. The RF op runs 200 us and ends
    `wake_us[k]` (default 20 us) before the tick's wait ends, leaving
    100 us idle inside a wait it lies in. Each ``bench.tick`` annotation
    opens 2 us (plus the tick's jitter) before its program tick span;
    the device's ops are recorded :data:`DEVICE_LEAD_NS` early."""
    jitter_ns = jitter_ns or [0.0] * n_ticks
    wake_us = wake_us or [20.0] * n_ticks
    spans, host, dev = [], [], []
    for k in range(n_ticks):
        t = 1000.0 * k
        spans += [_span("rf_wait", t + 100, 300), _span("tick", t, 900)]
        start = OFFSET_NS + 1e9 * SPAN_T0 + 1e3 * t
        host.append(_ev("bench.tick", start - 2e3 + jitter_ns[k], 950e3))
        end = start + 1e3 * (400 - wake_us[k]) - DEVICE_LEAD_NS
        dev.append(_ev("%rf_predict.1 = x", end - 200e3, 200e3))
    win = (OFFSET_NS + 1e9 * SPAN_T0 - 10e3,
           OFFSET_NS + 1e9 * SPAN_T0 + 1e3 * 1000 * n_ticks)
    host.append(_ev("bench.window", win[0], win[1] - win[0]))
    tr = tr_.Trace(ops={"/device:TPU:0": dev} if ops else {}, host=host)
    return {"spans": spans, "span_t0": SPAN_T0, "trace": tr,
            "trace_window": win, "kernel": "rf_predict"}


def test_clock_map_finds_the_offset():
    offset, spread, n = _timeline.clock_map(_obs(2))
    assert n == 2 and spread == pytest.approx(0.0)
    assert offset == pytest.approx(OFFSET_NS - 2e3)
    # the median of the per-tick offsets, with their spread
    offset, spread, n = _timeline.clock_map(_obs(2, jitter_ns=(0.0, 4e3)))
    assert offset == pytest.approx(OFFSET_NS)
    assert spread > 0


def test_spans_on_the_trace_clock():
    ivs = _timeline.on_trace(_obs(2), "rf_wait")
    base = OFFSET_NS - 2e3 + 1e9 * SPAN_T0
    assert ivs == [pytest.approx((base + 100e3, base + 400e3)),
                   pytest.approx((base + 1100e3, base + 1400e3))]
    assert _timeline.on_trace(_obs(2), "replan") is None


def test_device_shift_from_causality():
    # the latest shift at which every kernel still ends before its
    # tick's mapped wait ends: the tick that woke soonest (5 us) sets it
    obs = _obs(3, wake_us=[20.0, 5.0, 40.0])
    mapped_lead = 2e3                    # the annotation's lead on the span
    assert _timeline.device_shift(obs) == pytest.approx(
        DEVICE_LEAD_NS + 5e3 - mapped_lead)
    # one launch more than waits: no pairing, no shift
    obs["trace"].ops["/device:TPU:0"].append(
        _ev("%rf_predict.1 = x", 9e18, 1.0))
    assert _timeline.device_shift(obs) is None


def test_idle_inside_rf_wait():
    # 300 us of wait less the op's 200 us: 100 us = 0.1 ms a tick, with
    # the op recorded a millisecond early and moved back by the shift
    obs = _obs(3)
    assert _timeline.idle_ms_per_tick(obs, "rf_wait") == pytest.approx(0.1)
    read = Benchmark.load().reader("rf_wait_idle_ms.online")
    assert read(obs) == pytest.approx(0.1)
    # the tick span is 900 us, the op 200 us of it
    stages = _timeline.idle_by_stage(obs)
    assert stages == {"rf_wait": pytest.approx(0.1),
                      "tick": pytest.approx(0.7)}
    # a kernel the trace does not hold: no shift, so no reading
    obs["kernel"] = "no_such_kernel"
    assert _timeline.idle_ms_per_tick(obs, "rf_wait") is None


def test_none_where_counts_differ():
    obs = _obs(2)
    obs["trace"].host = [e for e in obs["trace"].host
                         if not (e.name == "bench.tick" and
                                 e.start > OFFSET_NS + 1e9 * SPAN_T0)]
    assert _timeline.clock_map(obs) is None
    assert _timeline.idle_ms_per_tick(obs, "rf_wait") is None
    assert Benchmark.load().reader("rf_wait_idle_ms.online")(obs) is None


def test_none_where_offsets_spread():
    big = 2 * _timeline.SPREAD_LIMIT_NS
    obs = _obs(4, jitter_ns=(0.0, big, 0.0, big))
    assert _timeline.clock_map(obs) is None
    assert _timeline.idle_by_stage(obs) is None


def test_none_without_device_ops_or_spans():
    # the CPU backend traces no device ops; a run of a program without
    # the RF spans has none to map
    assert _timeline.idle_ms_per_tick(_obs(2, ops=False), "rf_wait") is None
    obs = _obs(2)
    obs["spans"] = [s for s in obs["spans"] if s["name"] != "rf_wait"]
    assert _timeline.idle_ms_per_tick(obs, "rf_wait") is None
    assert _timeline.clock_map({"spans": [], "trace": None}) is None
