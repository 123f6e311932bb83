"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran on the chip, named by its HLO line, with
its start and duration in nanoseconds. The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` around each call into the program) lie
on a host plane on the same clock, on the thread that drives the program.

* busy: the union of the operation intervals of a device, clipped to the
  traced window (operations overlap on a chip; a sum would count twice);
* a kernel's time: the union of the intervals of the operations whose name
  names the kernel;
* idle gaps: the stretches of the window in which no operation ran,
  attributed to the host span that covers each gap's midpoint;
* an operation's module: the program it ran in, the event of the device
  plane's ``XLA Modules`` line that covers the operation's start (named
  ``<HloModule name>(<program id>)`` on a TPU);
* a host event's stats: the keyword arguments of its
  ``TraceAnnotation`` (``rows``, ``padded``, ...).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[int, int]           # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."               # the benchmark's own host spans
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    end_ns: int
    stats: Tuple[Tuple[str, Any], ...] = ()    # host events: their stats
    module: str = ""          # device operations: the program they ran in


def module_name(name: str) -> str:
    """The HloModule name of a module event's name: a TPU trace appends
    the program id (``jit_fn(10399301410868591309)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def in_modules(ops: Sequence[Event], modules: Sequence[Event]) -> List[Event]:
    """``ops`` with each one's ``module`` set to the name of the event of
    ``modules`` that covers its start ("" where none does). A device runs
    one program at a time, so its module events do not overlap."""
    mods = sorted(modules, key=lambda m: m.start_ns)
    starts = np.array([m.start_ns for m in mods], np.int64)
    at = np.searchsorted(starts, [e.start_ns for e in ops], side="right") - 1
    return [dataclasses.replace(
                e, module=mods[k].name if k >= 0 and e.start_ns < mods[k].end_ns
                else "")
            for e, k in zip(ops, at.tolist())]


def op_name(name: str) -> str:
    """The HLO instruction name of a device operation: a TPU trace names
    an operation by its whole HLO line (``%count_batch.1 = (...)
    custom-call(...), ...``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint sorted ones (touching ones join)."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo_w, hi_w = window
    return [(max(lo, lo_w), min(hi, hi_w)) for lo, hi in intervals
            if hi > lo_w and lo < hi_w]


def total_ns(intervals: Iterable[Interval]) -> int:
    return sum(hi - lo for lo, hi in union(intervals))


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that no interval of ``busy`` covers."""
    out, cursor = [], window[0]
    for lo, hi in union(clip(busy, window)):
        if lo > cursor:
            out.append((cursor, lo))
        cursor = max(cursor, hi)
    if cursor < window[1]:
        out.append((cursor, window[1]))
    return out


def kernel_matcher(kernel: str):
    """Matches an operation named for ``kernel``: the name itself, or the
    name with a numeric suffix (``count_batch``, ``count_batch.3``)."""
    pattern = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    return lambda name: bool(pattern.match(name))


@dataclasses.dataclass
class Trace:
    """The parts of one trace the metrics read."""

    window: Interval                         # the traced window (ns)
    device_ops: Dict[str, List[Event]]       # plane name -> its operations
    spans: List[Event]                       # the benchmark's host spans
    host: List[Event] = dataclasses.field(default_factory=list)
                                             # other events of their thread
    modules: bool = False                    # device ops carry their module

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self) -> List[Event]:
        return [e for evs in self.device_ops.values() for e in evs]

    def only(self, keep) -> "Trace":
        """This trace with only the device operations that ``keep`` (a
        predicate on an ``Event``) accepts."""
        return dataclasses.replace(self, device_ops={
            plane: [e for e in evs if keep(e)]
            for plane, evs in self.device_ops.items()})

    def busy_s(self, match=None) -> float:
        """Busy seconds per device, averaged over the devices traced,
        counting only operations that ``match`` (all when None)."""
        if not self.device_ops:
            return 0.0
        per_device = [
            total_ns(clip([(e.start_ns, e.end_ns) for e in evs
                           if match is None or match(e.name)], self.window))
            for evs in self.device_ops.values()]
        return sum(per_device) / len(per_device) * 1e-9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The device operations that took the most time (seconds summed
        over their events, averaged over the devices traced)."""
        by_name: Dict[str, float] = {}
        for e in self.ops():
            lo, hi = max(e.start_ns, self.window[0]), min(e.end_ns,
                                                          self.window[1])
            if hi > lo:
                by_name[e.name] = by_name.get(e.name, 0.0) + (hi - lo) * 1e-9
        n_dev = max(len(self.device_ops), 1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / n_dev] for name, secs in ranked]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds of the first device by what the host was doing at
        each gap's midpoint: the innermost benchmark span there and the
        innermost other host event on the same thread (a JAX dispatch, a
        transfer; none while the host runs Python and numpy), the longest
        first."""
        if not self.device_ops:
            return []
        first = sorted(self.device_ops)[0]
        busy = [(e.start_ns, e.end_ns) for e in self.device_ops[first]]
        idle = gaps(busy, self.window)
        if not idle:
            return []
        mids = np.array([(lo + hi) // 2 for lo, hi in idle], np.int64)
        by_label: Dict[str, float] = {}
        labels = [_innermost(self.spans, mids), _innermost(self.host, mids)]
        for (lo, hi), span, event in zip(idle, *labels):
            label = (span or "unannotated") + (f" > {event}" if event else "")
            by_label[label] = by_label.get(label, 0.0) + (hi - lo) * 1e-9
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs] for name, secs in ranked]


def _innermost(events: Sequence[Event], points) -> List[Optional[str]]:
    """For each point, the name of the latest-starting event covering it
    (events of one thread nest, so that is the innermost), or None."""
    if not events:
        return [None] * len(points)
    start = np.array([e.start_ns for e in events], np.int64)
    end = np.array([e.end_ns for e in events], np.int64)
    out = []
    for p in points:
        covering = np.nonzero((start <= p) & (p < end))[0]
        out.append(events[covering[np.argmax(start[covering])]].name[:80]
                   if covering.size else None)
    return out


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read(path: str, *, device_plane=DEVICE_PLANE,
         ops_line: str = OPS_LINE) -> Trace:
    """Read an ``.xplane.pb``: the device operations of every plane whose
    name matches ``device_plane`` (on its lines whose name starts with
    ``ops_line``), each with its module where the plane has a
    ``XLA Modules`` line, and the benchmark's host spans with the other
    events of their thread, stats included. The window is the
    ``bench.window`` span, or the extent of the device operations when the
    trace has none."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    host: List[Event] = []
    has_modules = False
    for plane in data.planes:
        is_device = bool(device_plane.match(plane.name))
        modules: List[Event] = []
        for line in plane.lines:
            if is_device and line.name.startswith(ops_line):
                device_ops.setdefault(plane.name, []).extend(
                    Event(op_name(e.name), int(e.start_ns), int(e.end_ns))
                    for e in line.events)
                continue
            if is_device and line.name == MODULES_LINE:
                modules.extend(Event(e.name, int(e.start_ns), int(e.end_ns))
                               for e in line.events)
                continue
            events = list(line.events)
            if not any(e.name.startswith(SPAN_PREFIX) for e in events):
                continue
            for e in events:          # the thread that drives the program
                ev = Event(e.name, int(e.start_ns), int(e.end_ns),
                           tuple((k, v) for k, v in e.stats))
                (spans if e.name.startswith(SPAN_PREFIX) else host).append(ev)
        if modules and plane.name in device_ops:
            device_ops[plane.name] = in_modules(device_ops[plane.name],
                                                modules)
            has_modules = True
    window: Optional[Interval] = None
    for s in spans:
        if s.name == WINDOW_SPAN:
            window = (s.start_ns, s.end_ns)
    if window is None:
        ops = [e for evs in device_ops.values() for e in evs]
        window = ((min(e.start_ns for e in ops), max(e.end_ns for e in ops))
                  if ops else (0, 0))
    return Trace(window, device_ops,
                 [s for s in spans if s.name != WINDOW_SPAN], host,
                 has_modules)
