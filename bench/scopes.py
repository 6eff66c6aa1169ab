"""Device time per named scope of the train step: the ops of the step
program's runs inside the window, each put down to the ``jax.named_scope``
path that the program's own scope map (``repro.obs.profile.hlo_scopes``,
kept on the Recorder as ``rec.scopes``) gives its HLO instruction.

Plain data in, plain numbers out, like ``bench/trace.py``.  Every instant
in which some op runs on a device counts once, for the innermost op that
covers it (the latest started: a loop's body ops inside the loop op), so
the scopes together with ``unscoped`` (the step's ops in no scope) and
``other`` (ops of other programs) sum to the device's busy time.
"""
from __future__ import annotations

import bisect
import heapq
from typing import Dict, Iterable, Tuple

from bench import trace as tr

UNSCOPED = "unscoped"
OTHER = "other"


def instruction(op: str) -> str:
    """An op event's HLO instruction name (``tr.op_name``'s first word)."""
    return op.split(" ", 1)[0]


def attribute(events: Iterable[Tuple[float, float, str]]) -> Dict[str, float]:
    """Time per key of ``(start, end, key)`` intervals, each instant given
    to the covering interval that started last; the values sum to the
    length of the intervals' union."""
    events = sorted((s, e, k) for s, e, k in events if e > s)
    bounds = sorted({t for s, e, _ in events for t in (s, e)})
    out: Dict[str, float] = {}
    # heap of (-start, end, key): the top is the latest started; an ended
    # interval leaves when it comes to the top
    live: list = []
    i = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(events) and events[i][0] <= lo:
            s, e, k = events[i]
            heapq.heappush(live, (-s, e, k))
            i += 1
        while live and live[0][1] <= lo:
            heapq.heappop(live)
        if live:
            k = live[0][2]
            out[k] = out.get(k, 0.0) + (hi - lo)
    return out


def scope_ns(trace: tr.Trace, module: str,
             scopes: Dict[str, str]) -> Dict[str, float]:
    """Device time inside the window per scope path of the program
    ``module`` (its HLO module name, ``jit_defense_step``), averaged over
    the devices: the ops that start inside one of its runs go to their
    instruction's scope or to ``unscoped``, all other ops to ``other``."""
    ops = trace.device_ops()
    total: Dict[str, float] = {}
    for dev, evs in ops.items():
        runs = sorted((e.start, e.end) for e in trace.modules.get(dev, [])
                      if e.name.split("(", 1)[0] == module)
        starts = [s for s, _ in runs]

        def key(e: tr.Event) -> str:
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start >= runs[i][1]:
                return OTHER
            return scopes.get(instruction(e.name), UNSCOPED)

        for k, v in attribute((e.start, e.end, key(e)) for e in evs).items():
            total[k] = total.get(k, 0.0) + v / len(ops)
    return total


def under(totals: Dict[str, float], prefix: str) -> float:
    """Time of the scope ``prefix`` and every scope nested in it."""
    return sum(v for k, v in totals.items()
               if k == prefix or k.startswith(prefix + "/"))
