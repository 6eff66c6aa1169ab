"""repro.obs: metrics registry semantics, exposition golden file, span
nesting under the tracer, the Recorder bus (sinks + gauge mirroring +
lifecycle), jsonify non-finite round-trips, recorder-through-
``run_experiment`` integration for all four topologies, and the reporter
CLI on a checked-in fixture JSONL."""
import dataclasses
import json
import math
import os

import numpy as np
import pytest

from repro.core import AttackConfig, RobustConfig
from repro.defense import DefenseConfig
from repro.defense.telemetry import (INF_CLAMP, TelemetryWriter, jsonify,
                                     read_jsonl)
from repro.experiment import (DataSpec, ModelSpec, ScenarioSpec,
                              run_experiment)
from repro.obs import (DEFAULT_MS_BUCKETS, Counter, Gauge, Histogram,
                       MetricsRegistry, ObsConfig, Recorder, SCHEMA,
                       as_recorder, check_kind, make_recorder,
                       parse_exposition, render_prometheus,
                       validate_record)
from repro.obs.trace import NULL_SPAN, current_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "obs")


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------

def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_last_write_wins():
    g = Gauge()
    g.set(3)
    g.set(-1.5)
    assert g.value == -1.5


def test_histogram_bucket_edges():
    h = Histogram(bounds=(1.0, 10.0, 100.0))
    # le is INCLUSIVE: a value exactly on an edge lands in that bucket.
    h.observe(1.0)
    h.observe(0.1)
    h.observe(10.0)
    h.observe(10.000001)
    h.observe(1e9)              # overflow -> +Inf slot
    h.observe(-5.0)             # below the first bound -> first bucket
    assert h.counts == [3, 1, 1, 1]
    assert h.cumulative() == [3, 4, 5, 6]
    assert h.count == 6
    assert h.sum == pytest.approx(1.0 + 0.1 + 10.0 + 10.000001 + 1e9 - 5.0)


def test_histogram_quantiles():
    h = Histogram(bounds=(1.0, 10.0, 100.0))
    for v in [0.5] * 98 + [50.0, 1e6]:
        h.observe(v)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 100.0
    # +Inf bucket reports the last finite bound
    assert h.quantile(1.0) == 100.0
    assert Histogram().quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError):
        Histogram(bounds=())
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(bounds=(10.0, 1.0))


def test_registry_label_children_and_type_conflict():
    reg = MetricsRegistry()
    a = reg.counter("hits", rule="phocas")
    b = reg.counter("hits", rule="mean")
    assert a is not b
    assert reg.counter("hits", rule="phocas") is a      # same child back
    assert reg.get("hits", rule="mean") is b
    assert reg.get("hits", rule="nope") is None
    assert reg.get("nope") is None
    with pytest.raises(ValueError):
        reg.gauge("hits")                               # type conflict


# ---------------------------------------------------------------------------
# Exposition: golden file + parser round-trip
# ---------------------------------------------------------------------------

def _golden_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("ejections", stream="train").inc(2)
    reg.gauge("q_hat").set(1)
    reg.gauge("resilience_margin", rule="phocas").set(1.0)
    h = reg.histogram("agg_ms", buckets=(1.0, 10.0, 100.0), rule="phocas")
    for v in (0.5, 1.0, 7.5, 250.0):
        h.observe(v)
    return reg


def test_exposition_golden_file():
    with open(os.path.join(FIXTURES, "golden.prom")) as fh:
        golden = fh.read()
    assert render_prometheus(_golden_registry()) == golden


def test_exposition_parse_roundtrip():
    text = render_prometheus(_golden_registry())
    fams = parse_exposition(text)
    assert fams["repro_ejections"]["type"] == "counter"
    (_, labels, value), = fams["repro_ejections"]["samples"]
    assert labels == {"stream": "train"} and value == 2.0
    hist = fams["repro_agg_ms"]
    assert hist["type"] == "histogram"
    buckets = {s[1]["le"]: s[2] for s in hist["samples"]
               if s[0].endswith("_bucket")}
    # cumulative and le-inclusive: 0.5 and 1.0 both land in le="1"
    assert buckets == {"1": 2.0, "10": 3.0, "100": 3.0,
                       "+Inf": 4.0}
    count, = (s[2] for s in hist["samples"] if s[0].endswith("_count"))
    assert count == 4.0


def test_exposition_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("c", path='a"b\\c\nd').inc()
    text = render_prometheus(reg)
    fams = parse_exposition(text)
    (_, labels, _), = fams["repro_c"]["samples"]
    assert labels == {"path": 'a"b\\c\nd'}


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError):
        parse_exposition("this is not { exposition")


# ---------------------------------------------------------------------------
# Spans: nesting, stack restore, disabled-mode zero cost
# ---------------------------------------------------------------------------

def test_span_nesting_paths():
    rec = Recorder(registry=MetricsRegistry(), trace=True)
    with rec.span("outer"):
        assert current_path() == "outer"
        with rec.span("inner", rule="phocas"):
            assert current_path() == "outer/inner"
        assert current_path() == "outer"
    assert current_path() == ""
    fams = parse_exposition(rec.snapshot())
    names = {s[1].get("name") for s in fams["repro_span_ms"]["samples"]}
    assert names == {"outer", "outer/inner"}


def test_span_stack_restored_on_exception():
    rec = Recorder(registry=MetricsRegistry(), trace=True)
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("x")
    assert current_path() == ""
    # the failed span still recorded its wall time
    assert rec.registry.get("span_ms", name="boom").count == 1


def test_span_sync_returns_value(monkeypatch):
    """An enabled span never waits for the device: it times the host's
    work inside it and nothing more (the device's time comes from the
    profiler trace)."""
    import jax
    import jax.numpy as jnp

    def refuse(*_a, **_k):
        raise AssertionError("a span blocked on the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    monkeypatch.setattr(jax.Array, "block_until_ready", refuse,
                        raising=False)
    rec = Recorder(registry=MetricsRegistry(), trace=True)
    with rec.span("s", step_num=4) as sp:
        x = jnp.ones((3,)) * 2
    assert not hasattr(sp, "sync") and not hasattr(NULL_SPAN, "sync")
    assert x.shape == (3,)
    done, = [r for r in rec.spans if r.path == "s"]
    assert done.step == 4 and done.end_ns >= done.start_ns
    rec.close()


def test_trace_without_metrics_keeps_spans_in_memory(tmp_path):
    """ObsConfig(enabled=False, trace=True): spans and no registry; the
    kept spans are written to the sinks when the Recorder closes."""
    path = str(tmp_path / "t.jsonl")
    rec = make_recorder(path, ObsConfig(enabled=False, trace=True))
    assert rec.registry is None and rec.trace_enabled
    with rec.span("engine", step_num=7):
        with rec.span("readback"):
            pass
    inner, outer = rec.spans
    assert (outer.path, inner.path) == ("engine", "engine/readback")
    assert inner.parent == outer.id and outer.parent == 0
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert read_jsonl(path) == []            # nothing written before close
    rec.close()
    spans = [r for r in read_jsonl(path) if r["kind"] == "span"]
    assert [r["name"] for r in spans] == ["engine/readback", "engine"]
    assert spans[1]["step"] == 7 and spans[0]["parent"] == spans[1]["id"]
    assert rec.spans                         # still readable after close


def test_span_annotation_in_profiler_trace(tmp_path):
    """An enabled span is a TraceAnnotation named by its full path, its
    step id an argument, on the profiler's clock."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    rec = Recorder(trace=True)
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("sync_ps"):
            with rec.span("dispatch", step_num=3):
                f(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    rec.close()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {e.name: dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("sync_ps")}
    assert found["sync_ps/dispatch"] == {"step": 3}
    assert "sync_ps" in found and "dispatch" not in found


def test_gc_span_wraps_collection():
    import gc
    before = list(gc.callbacks)
    off = Recorder(registry=MetricsRegistry())
    assert gc.callbacks == before            # no tracer, no hook
    rec = Recorder(trace=True)
    with rec.span("outer"):
        gc.collect()
    gcs = [s for s in rec.spans if s.path == "gc"]
    assert gcs and gcs[-1].labels == {"generation": 2}
    assert gcs[-1].parent == 0               # top level, whatever is open
    outer, = [s for s in rec.spans if s.path == "outer"]
    assert outer.start_ns <= gcs[-1].start_ns <= gcs[-1].end_ns \
        <= outer.end_ns
    rec.close()
    off.close()
    assert gc.callbacks == before
    n = len(rec.spans)
    gc.collect()
    assert len(rec.spans) == n               # the hook left with close()


def test_gc_hook_safe_before_jax_is_imported():
    """A tracing Recorder imports the profiler before it installs the gc
    hook: a collection can start in the middle of jax's own import, and a
    hook that imported jax there would find it half-initialised."""
    import subprocess
    import sys
    code = ("import gc, sys\n"
            "from repro.obs import Recorder\n"
            "assert 'jax' not in sys.modules\n"
            "rec = Recorder(trace=True)\n"
            "assert 'jax.profiler' in sys.modules\n"
            "gc.collect()\n"
            "rec.close()\n"
            "assert [s.path for s in rec.spans] == ['gc']\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "Exception ignored" not in out.stderr, out.stderr


def test_traced_sync_ps_reads_nothing_back(monkeypatch, tmp_path):
    """A traced sync_ps run (tracing on, metrics off) calls
    block_until_ready and reads no array back after its first step, and
    keeps its spans and the step's scope map on its Recorder."""
    import sys

    import jax
    from jax._src.array import ArrayImpl

    from repro.experiment import resolve
    from repro.experiment.topology import make_topology
    calls = {"sync": 0, "read": 0}
    real_sync, real_value = jax.block_until_ready, ArrayImpl._value

    def counting_sync(x):
        calls["sync"] += 1
        return real_sync(x)

    def counting_value(self):
        calls["read"] += 1
        return real_value.fget(self)

    monkeypatch.setattr(jax, "block_until_ready", counting_sync)
    monkeypatch.setattr(ArrayImpl, "_value", property(counting_value))
    spec = dataclasses.replace(_train_spec("sync_ps", tmp_path), steps=6,
                               log_every=1000, telemetry_path=None)
    plan = resolve(spec, obs=ObsConfig(enabled=False, trace=True))
    seen, loop = {}, {}
    inner = plan.batch_fn

    def batch_fn(step):
        seen[step] = dict(calls)
        loop.update(sys._getframe(1).f_locals)
        return inner(step)

    make_topology("sync_ps").run(dataclasses.replace(plan,
                                                     batch_fn=batch_fn))
    assert seen[5] == seen[1], seen           # steps 1..4: no read-back
    rec = loop["rec"]
    assert {"sync_ps/input", "sync_ps/dispatch", "sync_ps/record"} <= {
        s.path for s in rec.spans}
    (module, scopes), = rec.scopes.items()
    assert module == "jit_defense_step"
    assert {"grads", "aggregate/stack", "aggregate/attack",
            "aggregate/rule", "defense", "optimizer"} <= set(scopes.values())


def test_named_scopes_touch_only_metadata(monkeypatch, tmp_path):
    """The train step compiles to the same program with and without its
    named scopes: they change the HLO metadata alone."""
    import contextlib
    import re

    import jax

    from repro.data.pipeline import make_worker_batches
    from repro.defense.reputation import init_reputation
    from repro.experiment import resolve
    from repro.optim import init_opt_state
    from repro.train.step import make_train_step
    plan = resolve(_train_spec("sync_ps", tmp_path))
    params = plan.model.init(jax.random.PRNGKey(0))
    args = (params, init_opt_state(plan.opt_cfg, params),
            make_worker_batches(plan.batch_fn(0), plan.num_workers),
            jax.random.PRNGKey(1), init_reputation(plan.num_workers))

    def program():
        fn = make_train_step(plan.model, robust_cfg=plan.robust_cfg,
                             opt_cfg=plan.opt_cfg,
                             num_workers=plan.num_workers, donate=False,
                             defense_cfg=plan.defense_cfg)
        text = fn.lower(*args).compile().as_text()
        # the instructions, without their metadata (the stack-frame
        # tables before them hold source locations)
        body = text[text.index("\n%"):]
        return re.sub(r",? ?metadata=\{[^}]*\}", "", body), text

    scoped, scoped_text = program()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, plain_text = program()
    assert "aggregate/attack" in scoped_text
    assert "aggregate/attack" not in plain_text
    assert scoped == plain


def test_disabled_recorder_spans_allocate_nothing():
    rec = Recorder()
    assert not rec.enabled
    # the no-op span is one shared singleton — nothing per call
    assert rec.span("a") is rec.span("b") is NULL_SPAN
    with rec.span("a"):
        pass
    # metrics-on but trace-off also stays on the null span
    rec2 = Recorder(registry=MetricsRegistry(), trace=False)
    assert rec2.span("a") is NULL_SPAN
    # every bus method is a no-op, not an error
    rec.count("x")
    rec.gauge("x", 1.0)
    rec.observe("x", 1.0)
    rec.emit("train", 0, loss=1.0)
    rec.close()


# ---------------------------------------------------------------------------
# The Recorder bus
# ---------------------------------------------------------------------------

def test_schema_check_kind():
    assert check_kind("train") == "train"
    with pytest.raises(ValueError):
        check_kind("trian")
    assert set(SCHEMA) >= {"train", "serve", "decode", "metric", "span"}


def test_validate_record():
    assert validate_record({"t": 0, "kind": "train", "step": 1}) == []
    bad = validate_record({"kind": "nope"})
    assert any("t" in p for p in bad) and any("nope" in p for p in bad)


def test_emit_rejects_unknown_kind(tmp_path):
    rec = make_recorder(str(tmp_path / "t.jsonl"))
    with pytest.raises(ValueError):
        rec.emit("not_a_kind", 0, x=1)  # repro: noqa[CONTRACT010] the test IS the typo'd-kind case
    rec.close()


def test_recorder_mirrors_scalars_to_gauges(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = make_recorder(path, ObsConfig(trace=False))
    rec.emit("train", 3, loss=0.5, suspicion=[0.1, 0.9], q_hat=1)
    assert rec.registry.get("train_loss").value == 0.5
    assert rec.registry.get("train_q_hat").value == 1.0
    assert rec.registry.get("train_suspicion") is None   # non-scalar
    rec.close()
    recs = read_jsonl(path)
    assert recs[0]["kind"] == "train" and recs[0]["loss"] == 0.5
    # close() dumped the registry as "metric" records after the stream
    metric_names = {r["name"] for r in recs if r["kind"] == "metric"}
    assert {"train_loss", "train_q_hat"} <= metric_names


def test_recorder_close_idempotent_and_snapshot(tmp_path):
    snap = str(tmp_path / "m.prom")
    rec = make_recorder(None, ObsConfig(metrics_path=snap))
    rec.count("steps", 3)
    rec.close()
    rec.close()
    fams = parse_exposition(open(snap).read())
    assert fams["repro_steps"]["samples"][0][2] == 3.0


def test_as_recorder_adapts_writer(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with TelemetryWriter(path) as tel:
        rec = as_recorder(tel)
        rec.log("serve", 0, produced=2)
        assert as_recorder(rec) is rec
        rec.close()                      # not owned: must NOT close tel
        tel.log("serve", 1, produced=3)
    assert [r["step"] for r in read_jsonl(path)] == [0, 1]
    assert not as_recorder(None).enabled


# ---------------------------------------------------------------------------
# jsonify non-finite handling (satellite: NaN -> null, inf -> clamp)
# ---------------------------------------------------------------------------

def test_jsonify_non_finite_floats():
    assert jsonify(float("nan")) is None
    assert jsonify(float("inf")) == INF_CLAMP
    assert jsonify(float("-inf")) == -INF_CLAMP
    assert jsonify(np.float32("nan")) is None
    assert jsonify([1.0, float("nan"), float("inf")]) \
        == [1.0, None, INF_CLAMP]
    # the clamp survives strict JSON as a NUMBER
    assert json.loads(json.dumps(jsonify(float("inf")))) == INF_CLAMP


def test_telemetry_roundtrip_non_finite(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with TelemetryWriter(path) as tel:
        tel.log("train", 0, loss=float("nan"),
                grad_norm=float("inf"),
                suspicion=[0.5, float("-inf")])
    rec, = read_jsonl(path)
    assert rec["loss"] is None
    assert rec["grad_norm"] == INF_CLAMP
    assert rec["suspicion"] == [0.5, -INF_CLAMP]
    # strict JSON all the way down: the raw line parses with a strict
    # decoder that rejects NaN/Infinity literals
    with open(path) as fh:
        json.loads(fh.readline(), parse_constant=lambda c: 1 / 0)


# ---------------------------------------------------------------------------
# Recorder through run_experiment: all four topologies
# ---------------------------------------------------------------------------

def _train_spec(topology: str, tmp_path) -> ScenarioSpec:
    return ScenarioSpec(
        name=f"obs-{topology}", topology=topology,
        topology_params=({"staleness": 2} if topology == "async_ps"
                         else {}),
        model=ModelSpec(kind="mlp"),
        data=DataSpec(kind="classification", dim=16, batch_per_worker=4),
        robust=RobustConfig(rule="phocas", b=2, q=2),
        attack=AttackConfig(name="gaussian", num_byzantine=2),
        defense=(DefenseConfig() if topology in ("sync_ps", "async_ps")
                 else None),
        num_workers=8, steps=3, log_every=1,
        telemetry_path=str(tmp_path / f"{topology}.jsonl"))


@pytest.mark.parametrize("topology", ["sync_ps", "async_ps", "streaming"])
def test_recorder_through_run_experiment_training(topology, tmp_path):
    spec = _train_spec(topology, tmp_path)
    snap = str(tmp_path / f"{topology}.prom")
    result = run_experiment(spec, obs=ObsConfig(metrics_path=snap))
    assert result.history

    records = read_jsonl(spec.telemetry_path)
    kinds = {r["kind"] for r in records}
    assert "span" in kinds                       # tracing was armed
    assert all(not validate_record(r) for r in records)

    fams = parse_exposition(open(snap).read())
    assert "repro_span_ms" in fams
    assert "repro_steps" in fams
    span_rules = {s[1].get("rule")
                  for s in fams["repro_span_ms"]["samples"]}
    assert "phocas" in span_rules                # per-rule latency series
    if topology in ("sync_ps", "async_ps"):      # defended paths
        assert "repro_q_hat" in fams
        assert "repro_resilience_margin" in fams


def test_recorder_through_run_experiment_serve(tmp_path):
    tel = str(tmp_path / "serve.jsonl")
    snap = str(tmp_path / "serve.prom")
    spec = ScenarioSpec(
        name="obs-serve", topology="serve",
        model=ModelSpec(kind="arch", arch="granite-8b-reduced"),
        data=DataSpec(kind="tokens"),
        robust=RobustConfig(rule="phocas", b=1),
        attack=AttackConfig(name="gaussian", num_byzantine=1),
        topology_params={"replicas": 3, "max_slots": 2, "max_seq_len": 16,
                         "num_requests": 2, "arrival_rate": 4.0,
                         "prompt_len": 4, "max_new_tokens": 4},
        num_workers=8, steps=200,
        telemetry_path=tel)
    result = run_experiment(spec, obs=ObsConfig(metrics_path=snap))
    assert result.final_metrics["tokens"] == 8.0

    records = read_jsonl(tel)
    kinds = {r["kind"] for r in records}
    assert {"serve", "robust_decode", "span"} <= kinds
    assert all(not validate_record(r) for r in records)

    fams = parse_exposition(open(snap).read())
    names = {s[1].get("name") for s in fams["repro_span_ms"]["samples"]}
    assert {"engine", "engine/schedule", "engine/prefill", "engine/decode",
            "engine/readback", "engine/append"} <= names
    assert "repro_serve_admitted" in fams
    reqs = [r for r in records if r["kind"] == "request"]
    assert sorted(r["rid"] for r in reqs) == [0, 1]


def test_run_experiment_without_obs_stays_dark(tmp_path):
    """obs=None (the default): telemetry JSONL only, no span/metric
    records, exactly the pre-obs on-disk stream."""
    spec = _train_spec("sync_ps", tmp_path)
    run_experiment(spec)
    kinds = [r["kind"] for r in read_jsonl(spec.telemetry_path)]
    assert kinds == ["train"] * 3


# ---------------------------------------------------------------------------
# Reporter CLI
# ---------------------------------------------------------------------------

def test_reporter_cli_on_fixture(capsys):
    from repro.obs.report import main
    rc = main([os.path.join(FIXTURES, "run.jsonl")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "loss: first=2.31 last=1.2" in out
    # ejection timeline reconstructed from active-mask transitions
    assert "worker 2 ejected (train)" in out
    assert "worker 2 ejected (robust_decode)" in out
    assert "train_step" in out                   # span host-time table
    assert "ejections{stream=train} = 2" in out  # close-time counter dump
    assert "suspicion heat" in out


def test_reporter_kind_filter_and_missing(tmp_path, capsys):
    from repro.obs.report import main
    fixture = os.path.join(FIXTURES, "run.jsonl")
    assert main([fixture, "--kind", "train"]) == 0
    out = capsys.readouterr().out
    assert "records: train=3" in out
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert main([str(empty)]) == 1


def test_reporter_summarize_handles_non_finite():
    from repro.obs.report import summarize
    s = summarize([
        {"kind": "train", "step": 0, "loss": None,
         "suspicion": [0.1, None]},
        {"kind": "train", "step": 1, "loss": 1.0,
         "suspicion": [0.2, 0.3]},
    ])
    assert s["loss"]["n"] == 1 and s["loss"]["mean"] == 1.0
    assert s["suspicion_by_worker"][1] == pytest.approx(0.3)


# ---------------------------------------------------------------------------
# Default histogram buckets sanity
# ---------------------------------------------------------------------------

def test_default_buckets_are_increasing():
    assert list(DEFAULT_MS_BUCKETS) == sorted(set(DEFAULT_MS_BUCKETS))
    assert math.isfinite(DEFAULT_MS_BUCKETS[-1])
