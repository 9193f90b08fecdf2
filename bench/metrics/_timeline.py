"""The program's spans and the device's ops on the host trace's clock.

The fleet controller's spans (``obs["spans"]``, ``obs="on"`` in the
traced run) are timed on ``time.perf_counter``, in seconds from
``obs["span_t0"]``. The trace reduction keeps only the benchmark's own
``bench.`` host annotations, so the spans are carried onto the trace's
host clock through the benchmark's per-tick ``bench.tick`` annotation: the
k-th program ``tick`` span is paired with the k-th ``bench.tick`` event,
and one offset, the median over ticks of the annotation's start less the
span's start, maps every span. The annotation opens a few microseconds
before the program's ``tick`` span (the timed loop's call into ``tick()``),
so mapped spans sit that much early (about 4 us on a TPU v5 lite host).

The profiler places the device's ops on the host clock itself, and on a
TPU v5 lite that placement is off by 0.5 to 2 ms, differently in each
recording: the host's own enqueue and completion events put every RF
kernel inside its tick's ``rf_wait`` span, where the profiler put some
kernels before the host had dispatched them, and some recordings' kernels
outside ``predict`` altogether. So the ops get one shift of their own,
read off causality: the k-th launch of the cell's kernel ends before
the host's k-th ``rf_wait`` span ends. The shift is the latest at which
that holds for every tick. It places each kernel at most the host's
shortest wake-up (tens of microseconds) later than it ran, which, for
a kernel that lies inside ``rf_wait``, moves no idle time out of it.
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

import tracereduce

TICK_ANNOTATION = "bench.tick"
# The most the per-tick offsets may spread (q3 - q1, ns) for the mapping
# to stand. On a TPU v5 lite host they spread 0.24-0.44 us over runs of
# 600-2,800 ticks; the limit is over twenty times that, and about a
# hundredth of the span read (rf_wait, 0.8-1 ms a tick there).
SPREAD_LIMIT_NS = 10_000.0


def clock_map(obs: Dict[str, Any]) -> Optional[Tuple[float, float, int]]:
    """(offset in ns, spread of the per-tick offsets in ns, ticks paired),
    with ``trace_ns = 1e9 * (span_t0 + t) + offset``; None where the run
    has no spans or no trace, where the counts of program ``tick`` spans
    and ``bench.tick`` annotations differ, or where the offsets spread
    by more than :data:`SPREAD_LIMIT_NS`."""
    spans, tr = obs.get("spans"), obs.get("trace")
    if not spans or tr is None:
        return None
    ticks = sorted(s["t"] for s in spans if s["name"] == "tick")
    anns = sorted(e.start for e in tr.host if e.name == TICK_ANNOTATION)
    if not ticks or len(ticks) != len(anns):
        return None
    offsets = [a - 1e9 * (obs["span_t0"] + t) for a, t in zip(anns, ticks)]
    if len(offsets) > 1:
        q1, _, q3 = statistics.quantiles(offsets, n=4)
        spread = q3 - q1
    else:
        spread = 0.0
    if spread > SPREAD_LIMIT_NS:
        return None
    return statistics.median(offsets), spread, len(offsets)


def on_trace(obs: Dict[str, Any], name: str
             ) -> Optional[List[tracereduce.Interval]]:
    """The intervals of the program's spans called `name` on the trace's
    clock, sorted; None where the clock cannot be mapped or the run has
    no such span."""
    cmap = clock_map(obs)
    if cmap is None:
        return None
    offset = cmap[0]
    t0 = 1e9 * obs["span_t0"] + offset
    out = [(t0 + 1e9 * s["t"], t0 + 1e9 * (s["t"] + s["dur_s"]))
           for s in obs["spans"] if s["name"] == name]
    return sorted(out) or None


def device_shift(obs: Dict[str, Any]) -> Optional[float]:
    """ns to add to the trace's device op times to put them on its host
    clock: the least, over ticks, of the k-th ``rf_wait`` span's end less
    the k-th launch of the kernel ``obs["kernel"]``'s end. None where
    the trace has no device ops, the run no ``rf_wait`` spans, or their
    count differs from the kernel's launches."""
    tr, kernel = obs.get("trace"), obs.get("kernel")
    if tr is None or not tr.ops or kernel is None:
        return None
    waits = on_trace(obs, "rf_wait")
    ends = sorted(e.end for evs in tr.ops.values() for e in evs
                  if tracereduce.is_op(e.name, kernel))
    if waits is None or len(ends) != len(waits):
        return None
    return min(w[1] - end for w, end in zip(waits, ends))


def idle_ms_per_tick(obs: Dict[str, Any], name: str) -> Optional[float]:
    """The device's idle time inside the program's spans called `name`
    (the spans less the union of the shifted op intervals), in ms per
    tick, averaged over the devices traced; None where the spans or the
    ops cannot be placed on the host clock."""
    shift = device_shift(obs)
    spans = on_trace(obs, name)
    if shift is None or spans is None:
        return None
    tr, win = obs["trace"], obs.get("trace_window")
    spans = tracereduce.union(tracereduce.clip(spans, win))
    idle = 0.0
    for evs in tr.ops.values():
        busy = tracereduce.union(tracereduce.clip(
            [(e.start + shift, e.end + shift) for e in evs], win))
        idle += tracereduce.length(tracereduce.minus(spans, busy))
    return idle / len(tr.ops) / clock_map(obs)[2] / 1e6


def idle_by_stage(obs: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """:func:`idle_ms_per_tick` for every span name the run recorded (a
    stage's figure holds its nested stages' idle time too)."""
    if clock_map(obs) is None:
        return None
    names = sorted({s["name"] for s in obs["spans"]})
    out = {n: idle_ms_per_tick(obs, n) for n in names}
    return None if any(v is None for v in out.values()) else out
