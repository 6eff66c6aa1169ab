#!/usr/bin/env python3
"""Smoke run of the robust train step and paged serving on a TPU.

    python chip_smoke.py             # one chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: the sharded robust
                                     # reduce-scatter step against the
                                     # one-device step, nothing else

Drives the system through its own entry points (the ``sync_ps`` topology
loop that ``run_experiment`` dispatches to, and the paged ``ServeEngine``)
at the published widths of granite-8b (d_model 4096, 32 query / 8 KV heads
of 128, d_ff 14336, vocab 49152; arXiv:2405.04324), cut in depth and, for
training, in vocabulary to one chip's share.  Weights are random, from
``--seed``.  Every check that fails exits non-zero; a passing run prints
``{"ok": true, "device": {...}}`` as its last line.  Without a TPU it exits
non-zero and prints no result.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core import AttackConfig, RobustConfig  # noqa: E402
from repro.core.attacks import make_attack  # noqa: E402
from repro.core.selection import trim_family  # noqa: E402
from repro.data.pipeline import TokenStream, make_worker_batches  # noqa: E402
from repro.defense import DefenseConfig  # noqa: E402
from repro.defense.reputation import init_reputation  # noqa: E402
from repro.experiment import (DataSpec, ModelSpec, ScenarioSpec,  # noqa: E402
                              resolve)
from repro.experiment.topology import make_topology  # noqa: E402
from repro.kernels.phocas.ops import phocas_with_counts  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.obs.profile import device_memory_stats  # noqa: E402
from repro.optim import OptConfig, init_opt_state  # noqa: E402
from repro.serve import (RobustDecoder, ServeEngine,  # noqa: E402
                         corrupt_replica, make_replicas)
from repro.train.step import make_train_step  # noqa: E402

GRANITE = get_arch("granite-8b")

# Train cut: one layer and an eighth of the vocabulary (the share of one of
# eight chips that split the embedding and output head), every width as
# published.  The local robust path holds about 12*m bytes per parameter
# (m worker gradients, their (m, D) float32 matrix, the gated copy).
TRAIN_LAYERS = 1
VOCAB_SHARE = 8
WORKERS = 4                      # m
TRIM = 1                         # phocas b, and q of the gaussian attack
TRAIN_STEPS = 3
SEQ_LEN = 1024
SEQS_PER_WORKER = 2

# Serve cut: four layers, the whole vocabulary, bfloat16 as published.
SERVE_LAYERS = 4
REQUESTS = 8
PROMPT_LEN = 256
NEW_TOKENS = 32
REPLICAS = 3                     # k

# The Pallas phocas aggregate sums the m-b kept values of a coordinate in
# worker order, XLA in sorted order: the same kept set, rounded differently
# by a few float32 ulp of the largest kept value.
AGG_RTOL = 1e-6
# Drop counts are float32 sums of up to D ones; above 2**24 both paths round,
# each along its own reduction tree, by at most ~log2(D) * 2**-24 relative.
COUNT_RTOL = 1e-5
# The sharded and the one-device step compute the bfloat16 worker gradients
# in different programs, which round them differently (one ulp is 2**-8).
# Where two workers' distances from the trimmed center tie within that,
# phocas drops a different worker and the coordinate moves by up to a third
# of the workers' spread: such flips leave a relative L2 of order
# sqrt(2**-8) ~ 0.06 per leaf.  A slice aggregated into the wrong place, or
# lost, differs by O(1): 0.5 for one of four slices zeroed, more if swapped.
UPDATE_RTOL = 0.25


def reading(text: str) -> None:
    """One line of chip readings (host clock, device memory)."""
    print(f"[chip reading] {text}", flush=True)


def check(ok: bool, what: str) -> None:
    print(f"[check] {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: check failed: {what}")


def find_devices(chips: int):
    devices = jax.devices()
    d0 = devices[0]
    found = f"platform {d0.platform!r} ({d0.device_kind}) x{len(devices)}"
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {found}")
    if len(devices) != chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX found {found}")
    print(f"[device] {found}", flush=True)
    return devices


def peak_bytes(devices) -> str:
    return ", ".join(
        f"dev{i} {device_memory_stats(d)['peak_bytes_in_use'] / 2**30:.3f} GiB"
        for i, d in enumerate(devices))


# ---------------------------------------------------------------------------
# Train: the sync_ps topology loop
# ---------------------------------------------------------------------------

def train_config():
    return dataclasses.replace(
        GRANITE, name="granite-8b-chip-share", num_layers=TRAIN_LAYERS,
        vocab_size=GRANITE.vocab_size // VOCAB_SHARE)


def train_plan(*, steps: int, mesh: str, seed: int,
               q: int = TRIM, optimizer: str = "sgd"):
    """The resolved sync_ps plan, its model swapped for the chip-share cut
    of granite-8b and its token stream for one over that vocabulary; ``q``
    workers send gaussian noise (none when 0)."""
    spec = ScenarioSpec(
        name="chip-smoke-train", topology="sync_ps",
        model=ModelSpec(kind="arch", arch=GRANITE.name),
        data=DataSpec(kind="tokens", seq_len=SEQ_LEN,
                      batch_per_worker=SEQS_PER_WORKER, seed=seed),
        robust=RobustConfig(rule="phocas", b=TRIM, layout="sharded",
                            backend="auto"),
        attack=AttackConfig(name="gaussian" if q else "none",
                            num_byzantine=q),
        defense=DefenseConfig(), opt=OptConfig(name=optimizer, lr=0.1),
        num_workers=WORKERS, steps=steps, seed=seed, mesh=mesh,
        log_every=1)
    plan = resolve(spec)
    cfg = train_config()
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=SEQ_LEN,
                         global_batch=WORKERS * SEQS_PER_WORKER, seed=seed)
    return dataclasses.replace(plan, model=build_model(cfg),
                               batch_fn=stream.batch)


def step_shapes(plan):
    """Abstract arguments of the defended step, as the topology builds them."""
    params = jax.eval_shape(plan.model.init, jax.random.PRNGKey(plan.seed))
    opt = jax.eval_shape(lambda p: init_opt_state(plan.opt_cfg, p), params)
    batch = jax.eval_shape(
        lambda: make_worker_batches(plan.batch_fn(0), plan.num_workers))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    defense = jax.eval_shape(lambda: init_reputation(plan.num_workers))
    return params, opt, batch, key, defense


def build_step(plan):
    return make_train_step(plan.model, robust_cfg=plan.robust_cfg,
                           opt_cfg=plan.opt_cfg, num_workers=plan.num_workers,
                           mesh=None, donate=False,
                           defense_cfg=plan.defense_cfg)


def step_ms(result) -> list:
    """Each step's time at the loop's own boundary: the history records
    every step once its loss is read back (``log_every=1``), so
    consecutive record times bound each step from dispatch to done."""
    walls = [0.0] + [row["wall"] for row in result.history
                     if "wall" in row]
    return [round(1e3 * (b - a), 1) for a, b in zip(walls, walls[1:])]


def grad_matrix_stats(plan, params):
    """Pallas and XLA phocas statistics of one (m, D) worker-gradient matrix
    of the step: the first batch, the gaussian attack applied."""
    attack = make_attack(plan.robust_cfg.attack)
    b = plan.robust_cfg.b

    @jax.jit
    def stats(params, batch, key):
        grads = jax.vmap(jax.grad(plan.model.loss),
                         in_axes=(None, 0))(params, batch)
        mat = jax.vmap(lambda g: ravel_pytree(g)[0])(grads)
        mat = attack(key, mat.astype(jnp.float32), None)
        p_agg, p_counts = phocas_with_counts(mat, b)
        x_agg, x_counts, _ = trim_family(mat, b, "phocas", with_scores=True)
        err = jnp.max(jnp.abs(p_agg - x_agg))
        return err, jnp.max(jnp.abs(x_agg)), p_counts, x_counts

    batch = make_worker_batches(plan.batch_fn(0), plan.num_workers)
    out = stats(params, batch, jax.random.PRNGKey(plan.seed + 7))
    return jax.tree.map(np.asarray, out)


def train_phase(devices, seed: int) -> None:
    cfg = train_config()
    print(f"[train] granite-8b cut: layers {cfg.num_layers} of "
          f"{GRANITE.num_layers}, vocab {cfg.vocab_size} of "
          f"{GRANITE.vocab_size} (1/{VOCAB_SHARE} share), widths as "
          f"published (d_model {cfg.d_model}, heads {cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.head_dim}, d_ff {cfg.d_ff}); m "
          f"{WORKERS} workers x {SEQS_PER_WORKER}x{SEQ_LEN} tokens, "
          f"phocas b={TRIM}, gaussian q={TRIM}, defense on, backend auto",
          flush=True)
    plan = train_plan(steps=TRAIN_STEPS, mesh="",
                      seed=seed)
    n_params = sum(math.prod(x.shape) for x in
                   jax.tree.leaves(step_shapes(plan)[0]))
    print(f"[train] {n_params:,} parameters", flush=True)

    t0 = time.perf_counter()
    compiled = build_step(plan).lower(*step_shapes(plan)).compile()
    reading(f"train step compile {time.perf_counter() - t0:.1f} s")
    # peak_bytes_in_use counts the arrays a process holds; the step's own
    # temporaries are the compiler's to report.
    ma = compiled.memory_analysis()
    print(f"[train] compiler memory_analysis of the step: arguments "
          f"{ma.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / 2**30:.3f} GiB", flush=True)
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled train step contains a Pallas kernel "
          "(tpu_custom_call)")
    del compiled

    result = make_topology(plan.topology).run(plan)
    losses = [row["loss"] for row in result.history if "loss" in row]
    print(f"[train] losses {losses}", flush=True)
    check(len(losses) >= TRAIN_STEPS
          and all(math.isfinite(x) for x in losses),
          f"{TRAIN_STEPS} finite train losses")
    reading("train step ms (dispatch to loss read back; the first "
            "includes the jit's compile or cache load): "
            f"{step_ms(result)}")
    reading(f"peak_bytes_in_use after train: {peak_bytes(devices)}")

    err, scale, p_counts, x_counts = grad_matrix_stats(plan, result.params)
    print(f"[train] one (m, D) gradient matrix: max|pallas - xla| "
          f"aggregate {err:.3e} (max|xla| {scale:.3e}); drop counts pallas "
          f"{p_counts.tolist()} xla {x_counts.tolist()}", flush=True)
    check(err <= AGG_RTOL * scale,
          f"Pallas phocas aggregate matches XLA within {AGG_RTOL} of its "
          "largest value")
    check(np.all(np.abs(p_counts - x_counts)
                 <= COUNT_RTOL * np.maximum(x_counts, 1.0)),
          f"Pallas phocas drop counts match XLA within {COUNT_RTOL} relative")


# ---------------------------------------------------------------------------
# Serve: the paged ServeEngine, single model and k robust replicas
# ---------------------------------------------------------------------------

def serve_engine_run(model, params, prompts, decoder=None):
    """Serve ``prompts`` to completion; returns the finished requests and
    each engine step's time in ms (a step returns once its tokens are
    read back)."""
    engine = ServeEngine(model, params, max_slots=len(prompts),
                         max_seq_len=PROMPT_LEN + NEW_TOKENS,
                         decoder=decoder)
    for p in prompts:
        engine.submit(p, NEW_TOKENS)
    steps = []
    while engine.scheduler.busy:
        t0 = time.perf_counter()
        engine.step()
        steps.append(1e3 * (time.perf_counter() - t0))
    return engine.run(), steps


def serve_phase(devices, seed: int) -> None:
    cfg = dataclasses.replace(GRANITE, name="granite-8b-serve-cut",
                              num_layers=SERVE_LAYERS)
    print(f"[serve] granite-8b cut: layers {cfg.num_layers} of "
          f"{GRANITE.num_layers}, vocab {cfg.vocab_size} (whole), "
          f"{cfg.param_dtype}; {REQUESTS} requests of {PROMPT_LEN} prompt "
          f"+ {NEW_TOKENS} new tokens; then k={REPLICAS} phocas replicas, "
          "one corrupted", flush=True)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
               for _ in range(REQUESTS)]

    for name, label, decoder in (
            ("single", "single", None),
            ("robust", f"robust k={REPLICAS}",
             RobustDecoder(rule="phocas", k=REPLICAS))):
        reps = params
        if decoder is not None:
            reps = corrupt_replica(make_replicas(params, REPLICAS),
                                   REPLICAS - 1,
                                   jax.random.PRNGKey(seed + 1))
        done, steps = serve_engine_run(model, reps, prompts, decoder)
        check(len(done) == REQUESTS
              and all(len(r.generated) == NEW_TOKENS for r in done),
              f"{label}: all {REQUESTS} requests complete with "
              f"{NEW_TOKENS} tokens")
        reading(f"{label}: {REQUESTS} requests in {sum(steps) / 1e3:.2f} "
                f"s; engine step ms (to its tokens read back) first "
                f"{steps[0]:.1f} (the prefill and the first decode, with "
                f"their compiles), median of the decode steps after it "
                f"{float(np.median(steps[1:])):.2f}; request latency ms max "
                f"{max(r.latency_ms() for r in done):.1f}")
        if decoder is not None:
            check(REPLICAS - 1 in decoder.ejected_replicas(),
                  f"corrupted replica {REPLICAS - 1} ejected "
                  f"(ejected: {decoder.ejected_replicas()})")
        reading(f"peak_bytes_in_use after {label}: {peak_bytes(devices)}")


# ---------------------------------------------------------------------------
# Four chips: the sharded robust reduce-scatter against the one-device step
# ---------------------------------------------------------------------------

def mesh_phase(devices, seed: int) -> None:
    mesh = f"{WORKERS}x1"
    # No attack here: the sharded layout draws each slice's noise from its
    # own key, so the two runs would trim different coordinates wherever the
    # noise lands among the honest values.  Momentum, whose first-step
    # buffer is the aggregated gradient itself: the bfloat16 parameters
    # round away most of one SGD update.
    print(f"[mesh] sync_ps mesh {mesh} (m={WORKERS} on the data axis, "
          f"layout sharded, phocas b={TRIM}, defense on, no attack, "
          "momentum), same granite-8b cut as the one-chip train phase; "
          "then its first step against mesh=None on one device", flush=True)

    def run(name, steps, m):
        plan = train_plan(steps=steps, mesh=m, seed=seed, q=0,
                          optimizer="momentum")
        result = make_topology(plan.topology).run(plan)
        reading(f"{name} step ms (dispatch to loss read back; the first "
                f"includes compile or cache load): {step_ms(result)}")
        return result

    result = run("sharded", TRAIN_STEPS, mesh)
    losses = [row["loss"] for row in result.history if "loss" in row]
    print(f"[mesh] sharded losses {losses}", flush=True)
    check(len(losses) >= TRAIN_STEPS
          and all(math.isfinite(x) for x in losses),
          f"{TRAIN_STEPS} finite sharded train losses")
    reading(f"peak_bytes_in_use per device after the sharded run: "
            f"{peak_bytes(devices)}")
    del result

    sharded = run("sharded-first-step", 1, mesh)
    local = run("one-device-first-step", 1, "")
    rel = {}
    for (path, mu_s), mu_l in zip(
            jax.tree_util.tree_leaves_with_path(sharded.opt_state["mu"]),
            jax.tree.leaves(local.opt_state["mu"])):
        mu_s = jnp.asarray(mu_s, jnp.float32).ravel()
        mu_l = jnp.asarray(mu_l, jnp.float32).ravel()
        rel[jax.tree_util.keystr(path, simple=True, separator="/")] = float(
            jnp.linalg.norm(mu_s - mu_l)
            / jnp.maximum(jnp.linalg.norm(mu_l), 1e-30))
    worst = max(rel.values())
    print(f"[mesh] first-step update (momentum buffer = aggregated "
          f"gradient), relative L2 difference per leaf: "
          + ", ".join(f"{k} {v:.3e}" for k, v in rel.items()), flush=True)
    check(worst <= UPDATE_RTOL,
          f"sharded and one-device first-step updates agree within "
          f"{UPDATE_RTOL} relative L2 per leaf")
    reading(f"peak_bytes_in_use per device after the one-device step: "
            f"{peak_bytes(devices)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = find_devices(args.chips)
    print(f"[compile cache] {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        mesh_phase(devices, args.seed)
    else:
        train_phase(devices, args.seed)
        serve_phase(devices, args.seed)
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
