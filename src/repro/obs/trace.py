"""Span-based tracer (repro.obs, DESIGN.md §12).

A span is the interval in which the *host* does one named piece of work.
Entering a span pushes its name onto a thread-local stack, so a span opened
inside another records under the joined path (``"engine/decode"``).  The
closed span is kept in the Recorder's memory (``Recorder.spans``: path,
parent, start and end in ``perf_counter_ns``, labels, step and request
ids), lands in the ``span_ms`` histogram when metrics are on, and is written
to the JSONL sinks when the Recorder closes.

**Spans never block.**  jax dispatches asynchronously: the call that
launches a jitted step returns before the device has run it, and a span
around that call times the dispatch and nothing more.  That is what a span
means here: the host's own time.  The device's time comes from the
profiler trace: every enabled span also enters a
``jax.profiler.TraceAnnotation`` named by its full path, with its step or
request id as an argument, so in a trace captured through
``obs/profile.py``'s ``--profile-dir`` window (or any ``jax.profiler``
trace) the span sits on the same clock as the device's ops, and an idle
gap on the device can be put down to the innermost span that covers it.
Tracing therefore changes nothing in a loop's schedule: no span reads a
value back from the device.

While a Recorder traces, a ``gc.callbacks`` hook opens a ``gc`` span
(labelled with the generation) around every collection of the cyclic
garbage collector, so a collection's pause names itself in the trace.

With tracing off, ``span()`` returns the shared ``NULL_SPAN``: nothing is
allocated per call and no gc hook is installed.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, NamedTuple, Optional

_tls = threading.local()
# Span ids, unique in the process: a span's parent may belong to another
# Recorder (the stack is per thread, not per Recorder).
_ids = itertools.count(1)


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_path() -> str:
    """The active span path ("" outside any span) — test/debug hook."""
    stack = _stack()
    return stack[-1].path if stack else ""


class SpanRecord(NamedTuple):
    """One closed span, as the Recorder keeps it in memory."""
    id: int
    parent: int                 # the enclosing span's id, 0 at the top
    path: str
    start_ns: int               # time.perf_counter_ns()
    end_ns: int
    labels: Dict[str, object]
    step: Optional[int]
    rid: Optional[int]

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6


class _NullSpan:
    """The shared zero-cost span: returned for every ``span()`` call while
    tracing is off.  A singleton so disabled instrumentation allocates
    nothing per call (pinned by tests/test_obs.py)."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


def _annotation(path: str, step: Optional[int], rid: Optional[int]):
    """The profiler annotation of a span: its full path, with the step and
    request ids as arguments (the trace keeps them as the event's
    stats)."""
    from jax import profiler
    args = {}
    if step is not None:
        args["step"] = step
    if rid is not None:
        args["rid"] = rid
    return profiler.TraceAnnotation(path, **args)


class Span:
    """One enabled span; create via ``Recorder.span(name, ...)``."""
    __slots__ = ("_recorder", "name", "labels", "step_num", "rid", "path",
                 "id", "parent", "_t0", "_annotation", "_root")

    def __init__(self, recorder, name: str, labels: Dict[str, object],
                 step_num: Optional[int] = None, rid: Optional[int] = None,
                 root: bool = False):
        self._recorder = recorder
        self.name = name
        self.labels = labels
        self.step_num = step_num
        self.rid = rid
        self.path = name
        self.id = next(_ids)
        self.parent = 0
        self._t0 = 0
        self._annotation = None
        self._root = root

    def __enter__(self) -> "Span":
        if not self._root:
            stack = _stack()
            if stack:
                self.parent = stack[-1].id
                self.path = f"{stack[-1].path}/{self.name}"
            stack.append(self)
        self._annotation = _annotation(self.path, self.step_num, self.rid)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        if not self._root:
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
        self._recorder._span_done(SpanRecord(
            self.id, self.parent, self.path, self._t0, t1, self.labels,
            self.step_num, self.rid))
        return False


class GcHook:
    """The ``gc.callbacks`` hook of a tracing Recorder: a ``gc`` span
    around each collection, at the top level whatever span is open (a
    collection interrupts whatever allocated)."""

    def __init__(self, recorder):
        self._recorder = recorder
        self._open: Optional[Span] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = Span(self._recorder, "gc",
                              {"generation": info.get("generation")},
                              root=True)
            self._open.__enter__()
        elif self._open is not None:
            span, self._open = self._open, None
            span.__exit__(None, None, None)


# -- module-level convenience ------------------------------------------------

_default_recorder = None


def set_default_recorder(recorder) -> None:
    """Install the process-default Recorder :func:`span` binds to (None
    disarms it).  The launch CLIs set this so library code can open spans
    without threading the Recorder through every signature."""
    global _default_recorder
    _default_recorder = recorder


def span(name: str, step_num: Optional[int] = None, **labels):
    """A span on the process-default Recorder (no-op when none is set)."""
    if _default_recorder is None:
        return NULL_SPAN
    return _default_recorder.span(name, step_num=step_num, **labels)
