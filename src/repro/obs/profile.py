"""Profiling hooks (repro.obs): compiled-cost sampling, the named scopes
of a compiled program, device memory, and the ``--profile-dir`` trace
window.

These reuse the same XLA surfaces the dryrun CLI reads (``lower() →
compile() → cost_analysis()`` and ``memory_stats()``), but packaged for
a live run: the Recorder samples FLOPs/bytes once per compiled step
function and device memory per log interval, so the numbers land next to
loss/latency in the same JSONL stream instead of in a separate dryrun
report.  Only the CPU backend, which has no device memory to report, reads
as empty; on an accelerator a failing profiler or missing memory stats
raise, so a run never reports a device it did not measure.
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict, Optional

# One instruction of an HLO module's text: its name, and the rest of the
# line (``%fusion.17 = f32[8]{0} fusion(%a), calls=%f.17, metadata={...}``).
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def compiled_cost(compiled) -> Dict[str, float]:
    """FLOPs / bytes-accessed estimates of one compiled program
    (``jitted_fn.lower(*args).compile()`` — an AOT compile, so make it
    once per distinct step function, not per step), from XLA's
    ``cost_analysis()``.  Returns ``{}`` when the backend doesn't report
    costs."""
    ca = compiled.cost_analysis() or {}
    out = {}
    for key, name in (("flops", "flops"), ("bytes accessed", "bytes")):
        v = ca.get(key)
        if v is not None:
            out[name] = float(v)
    return out


def _scope_of(op_name: str) -> Optional[str]:
    """The named scopes in an ``op_name``, outermost first: the ``jit(...)``
    wrappers that lead it are skipped, and the path ends before the first
    transformed component (``vmap(...)``, ``jit(...)``) or the primitive's
    own name (the last component)."""
    parts = op_name.split("/")[:-1]
    i = 0
    while i < len(parts) and "(" in parts[i]:
        i += 1
    j = i
    while j < len(parts) and "(" not in parts[j]:
        j += 1
    return "/".join(parts[i:j]) or None


def hlo_scopes(text: str) -> Dict[str, str]:
    """Each instruction of a compiled program's text
    (``lower(...).compile().as_text()``) mapped to its scope path, the
    ``jax.named_scope`` names that enclose it, outermost first
    (``"aggregate/attack"``), as its ``op_name`` metadata records them.

    A fusion without metadata of its own takes that of its fused
    computation's root.  An instruction the compiler made without
    metadata (a copy, a piece of a split concatenation) takes the scope of
    its first user that has one, so data moved for a scope counts in it.
    Instructions outside every scope are left out.  Metadata is all the
    scopes touch: the program itself is the same with or without them."""
    comps: Dict[str, list] = {}        # computation -> [(name, rest)]
    current: list = []
    for line in text.splitlines():
        if line.endswith("{") and "%" in line.split("(", 1)[0]:
            current = comps.setdefault(
                line.split("(", 1)[0].split("%")[-1].strip(), [])
            continue
        m = _INSTRUCTION.match(line)
        if m is not None:
            current.append(m.groups())
    roots = {}
    for comp, instrs in comps.items():
        if instrs:
            op = _OP_NAME.search(instrs[-1][1])
            roots[comp] = _scope_of(op.group(1)) if op else None
    out: Dict[str, str] = {}
    for instrs in comps.values():
        names = {n for n, _ in instrs}
        scope: Dict[str, Optional[str]] = {}
        users: Dict[str, list] = {}
        for name, rest in instrs:
            op = _OP_NAME.search(rest)
            if op is not None:
                scope[name] = _scope_of(op.group(1))
            else:
                called = _CALLS.search(rest)
                scope[name] = roots.get(called.group(1)) if called else None
            for operand in _OPERAND.findall(rest.split("metadata=")[0]):
                if operand in names and operand != name:
                    users.setdefault(operand, []).append(name)
        for name, _ in reversed(instrs):
            if scope[name] is None:
                scope[name] = next((scope[u] for u in users.get(name, ())
                                    if scope[u] is not None), None)
            if scope[name] is not None:
                out[name] = scope[name]
    return out


def device_memory_stats(device=None) -> Dict[str, float]:
    """Live/peak memory in bytes of ``device`` (default: the first one).

    ``{}`` on the CPU backend, which has no device memory; any other
    backend that reports no allocator stats raises."""
    import jax
    dev = jax.devices()[0] if device is None else device
    if dev.platform == "cpu":
        return {}
    stats = dev.memory_stats()
    if not stats:
        raise RuntimeError(f"{dev.platform} device {dev.device_kind!r} "
                           "reports no memory stats")
    return {name: float(stats[name])
            for name in ("bytes_in_use", "peak_bytes_in_use")}


@contextlib.contextmanager
def profile_trace(profile_dir: Optional[str]):
    """A ``jax.profiler.trace`` window over the wrapped block.

    No-op when ``profile_dir`` is falsy (the default path: launch CLIs
    wrap their whole run in this unconditionally).  Spans opened inside
    the window appear as TraceAnnotation regions in the captured trace
    (obs/trace.py).  A requested trace whose profiler cannot start raises.
    """
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir):
        yield


def sample_into(recorder, prefix: str = "device") -> None:
    """Drop current device-memory stats into ``recorder`` gauges
    (``device_bytes_in_use``, ``device_peak_bytes_in_use``).  Cheap no-op
    when metrics are off."""
    if not getattr(recorder, "metrics_enabled", False):
        return
    for name, v in device_memory_stats().items():
        recorder.gauge(f"{prefix}_{name}", v)
