"""The harness of one run: finds the cell's files by the names in
``BENCHMARK.json``, holds the clock, the compile counter and the profiler,
and prints the result.  ``bench/run.py`` is its command line."""
from __future__ import annotations

import contextlib
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(BENCH, ".jax_cache")


class NoChip(SystemExit):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_files(bench: dict, name: str):
    """(cell, configuration, traffic mix) of workload ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that cell reports."""
    e2e = bench["end_to_end"]
    reported = {m["name"] for m in e2e
                if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def find_devices(chips: int):
    import jax
    devices = jax.devices()
    d0 = devices[0]
    found = f"platform {d0.platform!r} ({d0.device_kind}) x{len(devices)}"
    if d0.platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found {found}")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips; JAX found "
                     f"{found}")
    return devices[:chips]


class Harness:
    def __init__(self, args, t_start: float):
        self.args = args
        self.t_start = t_start
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.bench = benchmark()
        self.cell, self.config, self.traffic = cell_files(self.bench,
                                                          args.workload)
        self.chips = self.cell["chips"]
        self.devices = []
        self._compiles: list = []
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self._trace_dir: Optional[str] = None
        self._window = None

    @staticmethod
    def clock() -> float:
        return time.perf_counter()

    # -- set-up ---------------------------------------------------------------

    def start(self) -> None:
        self.devices = find_devices(self.chips)
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.count_compiles()

    def count_compiles(self) -> None:
        import jax
        from jax._src import dispatch
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_: event == dispatch.BACKEND_COMPILE_EVENT
            and self._compiles.append(time.perf_counter()))

    # -- the window -----------------------------------------------------------

    def open_window(self) -> None:
        self.t_open = self.clock()
        if self.trace:
            import jax
            self._window = jax.profiler.TraceAnnotation("bench_window")
            self._window.__enter__()

    def close_window(self, t_end: Optional[float] = None) -> None:
        self.t_close = self.clock() if t_end is None else t_end
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def compiles_in_window(self) -> int:
        return sum(self.t_open <= t <= self.t_close for t in self._compiles)

    def setup_s(self) -> float:
        return self.t_open - self.t_start

    # -- tracing --------------------------------------------------------------

    def start_trace(self) -> None:
        if self.trace:
            import jax
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_dir)

    def stop_trace(self, annotations) -> Optional[object]:
        """Stop the profiler and reduce its trace (None when not tracing)."""
        if not self.trace:
            return None
        import glob

        import jax

        from bench import trace as tr
        jax.profiler.stop_trace()
        try:
            path, = glob.glob(os.path.join(self._trace_dir, "**",
                                           "*.xplane.pb"), recursive=True)
            return tr.load(path, annotations)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    @contextlib.contextmanager
    def annotate(self, name: str):
        if not self.trace:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    def memory_peak(self) -> int:
        return int(max(d.memory_stats()["peak_bytes_in_use"]
                       for d in self.devices))


def per_layer(h: Harness, ctx: dict) -> dict:
    """Each per-layer metric of the cell that its reader finds something
    to read in."""
    out = {}
    for m in metrics_of(h.bench, h.cell["name"], "per_layer"):
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def report(res: dict, device: dict) -> None:
    """Print the checks, last on standard error, and the result line,
    last on standard output."""
    checks = res["checks"]
    correct = bool(checks) and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values())
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v!r} (limit {lim!r})", file=sys.stderr)
    line = {"correct": correct and res["failed"] == 0,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": res["metrics"], "device": device}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)


def main(args, t_start: float) -> int:
    h = Harness(args, t_start)
    try:
        h.start()
    except NoChip as e:
        print(e, file=sys.stderr)
        return 1
    driver = importlib.import_module(f"bench.drivers.{h.traffic['driver']}")
    res = driver.run(h)
    compiles = h.compiles_in_window()
    print(f"compiles inside the window: {compiles}", file=sys.stderr)
    if compiles:
        print("bench: the window compiled; no result", file=sys.stderr)
        return 3
    d0 = h.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(h.devices), "memory_peak_bytes": res["memory"]}
    if h.trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["window_s"]
    report(res, device)
    return 0
