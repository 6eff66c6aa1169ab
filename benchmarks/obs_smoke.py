"""Observability smoke benchmark: a short defended run with the obs layer
armed, asserting the exposition snapshot parses and the core series exist.

This is the CI step ISSUE 8 specifies: 20 defended sync-PS steps with
metrics + tracing on, producing

* ``BENCH_obs.jsonl``          — the run's telemetry record stream
* ``BENCH_obs_snapshot.prom``  — the Prometheus-style exposition snapshot

at the repo root (both uploaded as trend artifacts next to
``BENCH_analysis.json``).  The returned rows summarise the core series so
``benchmarks/run.py --only obs`` can trend them per PR.  Any missing
series raises — this is an assertion harness, not a passive dump.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

JSONL_PATH = os.path.join(REPO_ROOT, "BENCH_obs.jsonl")
SNAPSHOT_PATH = os.path.join(REPO_ROOT, "BENCH_obs_snapshot.prom")

# Series the acceptance criteria pin: the per-rule host time of the step's
# dispatch (span_ms; a span never waits for the device), q̂ / Δ-margin
# gauges, ejection-capable counters.
CORE_SERIES = ("repro_span_ms", "repro_q_hat", "repro_resilience_margin",
               "repro_steps", "repro_train_loss")


def main(steps: int = 20):
    from repro.core import AttackConfig, RobustConfig
    from repro.defense import DefenseConfig
    from repro.defense.telemetry import read_jsonl
    from repro.experiment import (DataSpec, ModelSpec, ScenarioSpec,
                                  run_experiment)
    from repro.obs import ObsConfig, parse_exposition

    for path in (JSONL_PATH, SNAPSHOT_PATH):
        if os.path.exists(path):
            os.remove(path)

    spec = ScenarioSpec(
        name="obs-smoke", topology="sync_ps",
        model=ModelSpec(kind="mlp"),
        data=DataSpec(kind="classification"),
        robust=RobustConfig(rule="phocas", b=2, q=2),
        attack=AttackConfig(name="gaussian", num_byzantine=2),
        defense=DefenseConfig(),
        num_workers=10, steps=steps, seed=0,
        telemetry_path=JSONL_PATH)
    result = run_experiment(
        spec, obs=ObsConfig(enabled=True, trace=True,
                            metrics_path=SNAPSHOT_PATH))

    records = read_jsonl(JSONL_PATH)
    kinds: dict = {}
    for r in records:
        kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
    assert kinds.get("train", 0) == steps, \
        f"expected {steps} train records, got {kinds}"
    dispatches = sum(1 for r in records if r["kind"] == "span"
                     and r["name"] == "sync_ps/dispatch")
    assert dispatches == steps, \
        f"expected {steps} sync_ps/dispatch spans, got {dispatches}"

    with open(SNAPSHOT_PATH) as fh:
        families = parse_exposition(fh.read())   # raises on malformed text
    missing = [s for s in CORE_SERIES if s not in families]
    assert not missing, f"snapshot missing core series: {missing}"

    # The per-rule host-time histogram of the step's dispatch: span_ms
    # labeled with the span path and the active rule.
    span_rules = {s[1].get("rule") for s in
                  families["repro_span_ms"]["samples"]}
    assert "phocas" in span_rules, span_rules

    count = next(v for n, labels, v in
                 families["repro_span_ms"]["samples"]
                 if n.endswith("_count") and labels.get("rule") == "phocas")
    rows = [{
        "steps": steps,
        "record_kinds": len(kinds),
        "records": len(records),
        "series": len(families),
        "span_observations": int(count),
        "final_loss": result.final_loss,
        "q_hat": next((r["q_hat"] for r in reversed(result.history)
                       if "q_hat" in r), None),
    }]
    return rows


if __name__ == "__main__":
    for row in main():
        print(row)
