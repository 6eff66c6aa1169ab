"""Profiling hooks (repro.obs): compiled-cost sampling, device memory,
and the ``--profile-dir`` trace window.

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
from typing import Dict, Optional


def compiled_cost(jitted_fn, *args) -> Dict[str, float]:
    """FLOPs / bytes-accessed estimates for one compiled call.

    Lowers and compiles ``jitted_fn(*args)`` (AOT — a one-off cost, so
    call this once per distinct step function, not per step) and reads
    XLA's ``cost_analysis()``.  Returns ``{}`` when the backend doesn't
    report costs.
    """
    ca = jitted_fn.lower(*args).compile().cost_analysis() or {}
    out = {}
    for key, name in (("flops", "flops"), ("bytes accessed", "bytes")):
        v = ca.get(key)
        if v is not None:
            out[name] = float(v)
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
