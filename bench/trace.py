"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.  Plain data in, plain numbers out: no JAX device is touched,
so the tests run it on a recorded trace and on made-up intervals.

Times are nanoseconds on the trace's clock.  A device's busy time is the
union of the intervals of its ``XLA Ops`` events; host annotations are the
``jax.profiler.TraceAnnotation`` events the drivers put around their calls
into the program.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]                 # [start, end) in ns

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench_window"                        # the measured window
COLLECTIVE = re.compile(
    r"all-gather|all-to-all|all-reduce|reduce-scatter|collective-permute"
    r"|all_gather|all_to_all|all_reduce|reduce_scatter|collective_permute",
    re.IGNORECASE)


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]           # device plane -> op events
    host: List[Event]                          # host annotations
    window: Interval                           # the measured window
    modules: Dict[str, List[Event]] = dataclasses.field(
        default_factory=dict)                  # device -> program runs

    def device_ops(self) -> Dict[str, List[Event]]:
        """Each device's op events, clipped to the window."""
        lo, hi = self.window
        out = {}
        for dev, evs in self.devices.items():
            out[dev] = [Event(e.name, max(e.start, lo),
                              min(e.end, hi) - max(e.start, lo))
                        for e in evs if e.end > lo and e.start < hi]
        return out


def op_name(text: str) -> str:
    """An op event's name from its HLO text: ``%fusion.17 = f32[1,8]{1,0}
    fusion(...)`` -> ``fusion.17 f32[1,8]``, the op's own name and result
    type, so that a pattern never matches an op by its operands."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    return f"{name.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def load(path: str, annotations: Iterable[str]) -> Trace:
    """Read one trace file: every TPU device plane's op line, and the host
    events named in ``annotations`` plus the window annotation."""
    from jax.profiler import ProfileData
    names = set(annotations) | {WINDOW}
    devices: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    name = op_name if line.name == OPS_LINE else str
                    evs = [Event(name(e.name), e.start_ns, e.duration_ns)
                           for e in line.events]
                    (devices if line.name == OPS_LINE else modules)[
                        plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in names)
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with an "
                         f"{OPS_LINE!r} line")
    w = max(windows, key=lambda e: e.dur)
    return Trace(devices, [e for e in host if e.name != WINDOW],
                 (w.start, w.end), modules)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Merged ``a`` minus merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(trace: Trace) -> float:
    """Busy time averaged over the devices: the union of each one's op
    intervals inside the window."""
    ops = trace.device_ops()
    return sum(length(union((e.start, e.end) for e in evs))
               for evs in ops.values()) / len(ops)


def window_ns(trace: Trace) -> float:
    return trace.window[1] - trace.window[0]


def kernel_ns(trace: Trace, pattern: str) -> float:
    """Device time of the ops whose name matches ``pattern`` (a regular
    expression, searched), summed over the window and averaged over the
    devices."""
    rx = re.compile(pattern)
    ops = trace.device_ops()
    return sum(e.dur for evs in ops.values() for e in evs
               if rx.search(e.name)) / len(ops)


def program_ns(trace: Trace, pattern: str) -> List[float]:
    """Device durations of the runs of the programs whose name matches
    ``pattern`` that start inside the window, first device."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    evs = trace.modules.get(sorted(trace.devices)[0], [])
    return [e.dur for e in evs if rx.search(e.name) and lo <= e.start < hi]


def op_totals(trace: Trace) -> Dict[str, float]:
    """Device time per op name, averaged over the devices."""
    tot: Dict[str, float] = {}
    ops = trace.device_ops()
    for evs in ops.values():
        for e in evs:
            tot[e.name] = tot.get(e.name, 0.0) + e.dur / len(ops)
    return tot


def idle_gaps(trace: Trace) -> List[Tuple[str, float]]:
    """Every idle gap of the first device inside the window, longest
    first, each named after the innermost host annotation that covers its
    middle ("unannotated" where none does)."""
    lo, hi = trace.window
    dev = sorted(trace.device_ops())[0]
    busy = union((e.start, e.end) for e in trace.device_ops()[dev])
    gaps = subtract([(lo, hi)], busy)
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        over = [a for a in trace.host if a.start <= mid < a.end]
        name = min(over, key=lambda a: a.dur).name if over else "unannotated"
        out.append((name, e - s))
    return sorted(out, key=lambda g: -g[1])


def exposed_collective_ns(trace: Trace) -> Optional[float]:
    """Time in which a collective runs on a device and no other op does,
    averaged over the devices; None where the trace has no collective."""
    ops = trace.device_ops()
    total, seen = 0.0, False
    for evs in ops.values():
        coll = union((e.start, e.end) for e in evs
                     if COLLECTIVE.search(e.name))
        seen = seen or bool(coll)
        other = union((e.start, e.end) for e in evs
                      if not COLLECTIVE.search(e.name))
        total += length(subtract(coll, other))
    return total / len(ops) if seen else None


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time and the longest idle gaps, in seconds."""
    ops = sorted(op_totals(trace).items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in idle_gaps(trace)[:n]]}
