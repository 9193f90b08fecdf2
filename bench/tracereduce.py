"""Reduce a JAX profiler trace (``.xplane.pb``) to per-layer numbers.

Device planes are those named ``/device:<KIND>:<n>``; on each, the ops
line (``XLA Ops``) holds one event per device operation and the modules
line (``XLA Modules``) one event per launched program. Host planes hold
the benchmark's own ``TraceAnnotation`` spans. All times are read in
nanoseconds on the trace's one clock.

What is computed, each as a plain function of event intervals:

* busy: the union of op intervals, per device, inside the window;
* kernel time: the summed durations of ops whose instruction name is a
  stable kernel name (``rf_predict`` matches ``%rf_predict.1 = ...``);
* program time: the summed durations of module events whose name
  contains a stable program name;
* collective-permute time, and the part of it during which no other
  op runs on that device;
* idle gaps: the device's idle time inside the window, attributed to
  the host annotation that was open at the middle of each gap.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class Event:
    """One trace event."""
    name: str
    start: float                        # ns
    dur: float                          # ns

    @property
    def end(self) -> float:
        """End time in ns."""
        return self.start + self.dur


@dataclass
class Trace:
    """The events a reduction needs, grouped by where they ran."""
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # device
    host: List[Event] = field(default_factory=list)  # annotations


def is_device_plane(name: str) -> bool:
    """True for ``/device:TPU:0``-style planes (not host planes)."""
    return name.startswith("/device:") and "CPU" not in name


def from_profile(pd, prefix: str = "bench.") -> Trace:
    """Collect device op/module events, and the host annotations whose
    names start with `prefix`, from a ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                dst = tr.ops if line.name == OPS_LINE else tr.modules
                dst[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
            elif not dev:
                tr.host.extend(Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events
                               if e.name.startswith(prefix))
    return tr


def load(path: str, prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb`` file (or a gzipped one) into a Trace."""
    import gzip
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()),
                                prefix)
    return from_profile(ProfileData.from_file(path), prefix)


# ----------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Optional[Interval]
         ) -> List[Interval]:
    """Intersect intervals with `window` (None keeps them whole)."""
    if window is None:
        return list(intervals)
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    """Total length of disjoint intervals."""
    return sum(e - s for s, e in intervals)


def minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of disjoint sorted `a` not covered by disjoint sorted `b`."""
    out = []
    k = 0
    for s, e in a:
        while k < len(b) and b[k][1] <= s:
            k += 1
        cur, i = s, k
        while i < len(b) and b[i][0] < e:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < e:
            out.append((cur, e))
    return out


def _iv(events: Iterable[Event]) -> List[Interval]:
    return [(ev.start, ev.end) for ev in events]


def op_name(name: str) -> str:
    """The instruction name of an op event: ``%while.3 = (...) ...`` ->
    ``while.3``."""
    return name.split(" ", 1)[0].lstrip("%")


def is_op(name: str, kernel: str) -> bool:
    """True where the op's instruction is `kernel` or `kernel.<n>`."""
    op = op_name(name)
    return op == kernel or op.startswith(kernel + ".")


# ----------------------------------------------------------------------
# the reductions
# ----------------------------------------------------------------------
def busy_ns(tr: Trace, window: Optional[Interval] = None
            ) -> Dict[str, float]:
    """Per device: length of the union of op intervals in `window`."""
    return {dev: length(union(clip(_iv(evs), window)))
            for dev, evs in tr.ops.items()}


def kernel_ns(tr: Trace, name: str, window: Optional[Interval] = None
              ) -> float:
    """Summed device time of the ops of kernel `name`."""
    return sum(length(clip(_iv(e for e in evs if is_op(e.name, name)),
                           window))
               for evs in tr.ops.values())


def kernel_count(tr: Trace, name: str, window: Optional[Interval] = None
                 ) -> int:
    """Number of op events of kernel `name` in `window`."""
    return sum(len(clip(_iv(e for e in evs if is_op(e.name, name)), window))
               for evs in tr.ops.values())


def module_ns(tr: Trace, name: str, window: Optional[Interval] = None
              ) -> float:
    """Summed device time of program (module) events matching `name`."""
    return sum(length(clip(_iv(e for e in evs if name in e.name), window))
               for evs in tr.modules.values())


def collective_ns(tr: Trace, prefix: str = "collective-permute",
                  window: Optional[Interval] = None
                  ) -> Tuple[float, float]:
    """(time of ops named `prefix`*, the part of it with no other op
    running on the same device), summed over devices."""
    total = exposed = 0.0
    for evs in tr.ops.values():
        coll = union(clip(_iv(e for e in evs
                              if op_name(e.name).startswith(prefix)), window))
        other = union(clip(_iv(e for e in evs
                               if not op_name(e.name).startswith(prefix)),
                           window))
        total += length(coll)
        exposed += length(minus(coll, other))
    return total, exposed


def top_ops(tr: Trace, k: int = 10, window: Optional[Interval] = None
            ) -> List[Tuple[str, float]]:
    """The `k` ops (by instruction name) with the most device time, in
    seconds, averaged over devices. An op that holds others, such as a
    ``while``, counts its whole span."""
    agg: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        for ev in evs:
            agg[op_name(ev.name)] += length(clip([(ev.start, ev.end)],
                                                 window))
    n = max(len(tr.ops), 1)
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns / n / 1e9) for name, ns in rows]


def idle_gaps(tr: Trace, window: Interval, k: int = 10
              ) -> List[Tuple[str, float]]:
    """Idle device time in `window`, summed by the innermost host
    annotation open at each gap's middle ('none' where none was), in
    seconds averaged over devices; the `k` largest."""
    import bisect
    agg: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, List[Event]] = defaultdict(list)
    for ev in tr.host:
        by_name[ev.name].append(ev)
    for evs in by_name.values():       # spans of one name do not nest
        evs.sort(key=lambda e: e.start)
    starts = {n: [e.start for e in evs] for n, evs in by_name.items()}
    for evs in tr.ops.values():
        busy = union(clip(_iv(evs), window))
        for s, e in minus([window], busy):
            mid = (s + e) / 2
            label, best = "none", None
            for name, anns in by_name.items():
                i = bisect.bisect_right(starts[name], mid) - 1
                if i >= 0 and mid < anns[i].end and \
                        (best is None or anns[i].dur < best):
                    label, best = name, anns[i].dur
            agg[label] += e - s
    n = max(len(tr.ops), 1)
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns / n / 1e9) for name, ns in rows]


def annotation_window(tr: Trace, name: str) -> Optional[Interval]:
    """The first host annotation called `name`, as an interval."""
    for ev in tr.host:
        if ev.name == name:
            return (ev.start, ev.end)
    return None
