"""Training cells: the program's own ``sync_ps`` loop (``SyncPS.run``,
reached through ``resolve(ScenarioSpec)``) on the harness's weights and
batches.

One ``run()`` call is the whole run.  Its first steps compile the step and
are the steps the reference checks; the measured window opens at the step
boundary ``warmup_steps`` and closes at the first boundary after
``--seconds``, where the batch function stops the loop.  In the window the
loop runs ahead of the device, as a training loop that logs rarely does:
the batch function waits only for the step ``AHEAD_S`` seconds back, so a
host that stands still for less than that leaves the chip fed.  At the
close it dispatches nothing more and waits for every step it sent; the
rate is the tokens of all those steps over the time from the opening to
the end of that wait.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs, gen, peaks
from bench.drivers import common
from bench.reference import granite, robust_sgd

ANNOTATIONS = ("batch", "dispatch", "wait")
# Seconds of steps dispatched ahead of the one the window waits for.
AHEAD_S = 6.0


class WindowClosed(Exception):
    """Raised by the batch function at the first step boundary after the
    window's length: it ends the program's open-ended loop."""


def build_plan(model, arch: str, job: dict, seed: int):
    """The resolved ``sync_ps`` plan of this job, its model swapped for
    the configuration's.  The loop records its history at step 0 only, so
    it reads nothing back from the device in the window."""
    from repro.core import AttackConfig, RobustConfig
    from repro.defense import DefenseConfig
    from repro.experiment import DataSpec, ModelSpec, ScenarioSpec, resolve
    from repro.optim import OptConfig
    spec = ScenarioSpec(
        name="bench-train", topology="sync_ps",
        model=ModelSpec(kind="arch", arch=arch),
        data=DataSpec(kind="tokens", seq_len=job["seq_len"],
                      batch_per_worker=job["seqs_per_worker"], seed=0),
        robust=RobustConfig(rule=job["rule"], b=job.get("b", 0),
                            layout="sharded", backend=job["backend"]),
        attack=AttackConfig(name=job["attack"],
                            num_byzantine=job.get("byzantine", 0)),
        defense=DefenseConfig() if job["defense"] else None,
        opt=OptConfig(name="sgd", lr=job["lr"]),
        num_workers=job["workers"], steps=2**62, seed=seed % 2**31,
        log_every=2**62)
    plan = resolve(spec)
    return dataclasses.replace(plan, model=model)


@jax.jit
def _delta_norms(p0: dict, p: dict, scale):
    """Per array: || (p - p0) * scale ||, in float32."""
    return {k: jnp.linalg.norm((p[k].astype(jnp.float32)
                                - p0[k].astype(jnp.float32)).ravel()) * scale
            for k in p0}


def steps_ahead(ticks: list) -> int:
    """Steps in ``AHEAD_S`` seconds, by the quickest of the warm-up steps
    after the first (``ticks``: the clock at each warm-up boundary, each
    read once the step before had finished)."""
    step_s = min(b - a for a, b in zip(ticks[1:], ticks[2:]))
    return max(1, math.ceil(AHEAD_S / max(step_s, 1e-6)))


class Feed:
    """The plan's ``batch_fn``: the batch pool, the window's clock and its
    pacing, and the readings of the first steps.  At each step boundary it
    reads the parameters and metrics of the step before from the frame of
    ``SyncPS.run`` that calls it."""

    def __init__(self, h, batches, w0: dict, lr: float, warmup: int,
                 checked: int):
        from repro.experiment.topologies import SyncPS
        self.h, self.batches, self.w0 = h, batches, w0
        self.lr, self.warmup, self.checked = lr, warmup, checked
        self.losses: list = []      # each step's loss, left on the device
        self.readings: dict = {}
        self.ticks: list = []
        self.ahead = 1
        self.t_last = self.t_end = None
        self.window_steps = 0
        self._loop = SyncPS.run.__code__
        self._ann = None

    def _annotate(self, name):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if name and self.h.trace:
            self._ann = jax.profiler.TraceAnnotation(name)
            self._ann.__enter__()

    def _loop_state(self, frame) -> dict:
        if frame.f_code is not self._loop:
            raise RuntimeError("the batch function is not called by "
                               "SyncPS.run")
        return frame.f_locals

    def batch(self, step: int) -> dict:
        h = self.h
        self._annotate(None)
        loop = self._loop_state(sys._getframe(1))
        if step > 0:
            self.losses.append(loop["metrics"]["loss"])
        if step <= self.warmup:
            jax.block_until_ready(loop["params"])
            self.ticks.append(h.clock())
        if step == 1:
            self.readings["grad"] = _delta_norms(
                self.w0, common.from_program(loop["params"]), 1.0 / self.lr)
        if step == self.checked:
            self.readings["change"] = _delta_norms(
                self.w0, common.from_program(loop["params"]), 1.0)
        if step == self.warmup - 2:
            h.start_trace()
        if step == self.warmup:
            self.ahead = steps_ahead(self.ticks)
            h.open_window()
        elif step > self.warmup:
            if h.clock() - h.t_open >= h.seconds:
                self.t_last = h.clock()
                with h.annotate("wait"):
                    jax.block_until_ready((loop["params"], self.losses))
                self.t_end = h.clock()
                self.window_steps = step - self.warmup
                h.close_window(self.t_end)
                raise WindowClosed
            if step - 1 - self.ahead >= self.warmup:
                with h.annotate("wait"):
                    jax.block_until_ready(
                        self.losses[step - 1 - self.ahead])
        with h.annotate("batch"):
            b = self.batches[step % len(self.batches)]
        self._annotate("dispatch")
        return b


def norm_gap(prog: dict, ref: dict) -> float:
    """Worst leaf of | ||prog|| - ||ref|| | over max(||ref||, the median
    leaf's ||ref||); leaves whose reference norm is under a thousandth of
    the median leaf's are left out."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med)
               for k in ref if ref[k] >= 1e-3 * med)


def reference_readings(cfg: dict, job: dict, seed: int, batches,
                       precision: str = "f32", half_batch: bool = False):
    """(losses of the checked steps, first-gradient norms, change norms)
    of the reference on the same weights and batches."""
    key = gen.seed_key(seed)
    w0 = granite.make_weights(jax.random.fold_in(key, 1), cfg)
    w, losses, grad = w0, [], None
    for i in range(job["checked_steps"]):
        b = batches[i]
        if half_batch:
            rows = b["tokens"].shape[0] // job["workers"]
            keep = np.concatenate([np.arange(j * rows, j * rows + rows // 2)
                                   for j in range(job["workers"])])
            b = jax.tree.map(lambda x: x[keep], b)
            half = dict(job, seqs_per_worker=rows // 2)
            loss, w = robust_sgd.step(w, cfg, half, b,
                                      jax.random.fold_in(key, 100 + i),
                                      precision)
        else:
            loss, w = robust_sgd.step(w, cfg, job, b,
                                      jax.random.fold_in(key, 100 + i),
                                      precision)
        losses.append(loss)
        if i == 0:
            grad = _delta_norms(w0, w, 1.0 / job["lr"])
    change = _delta_norms(w0, w, 1.0)
    fetch = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
    return losses, fetch(grad), fetch(change)


def compare(prog: tuple, ref: tuple) -> dict:
    """The three numbers compared: worst relative loss gap over the
    checked steps, worst leaf of the first gradient, worst leaf of the
    change after the checked steps."""
    (pl, pg, pc), (rl, rg, rc) = prog, ref
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in zip(pl, rl)),
            "grad_gap": norm_gap(pg, rg),
            "change_gap": norm_gap(pc, rc)}


def run_program(h, cfg: dict, job: dict):
    """Set-up, the window, and the program's readings of its first
    steps.  Returns (feed, losses, trace)."""
    seed = h.seed
    key = gen.seed_key(seed)
    model = common.program_model(cfg)
    w0 = granite.make_weights(jax.random.fold_in(key, 1), cfg)
    params = common.to_program(w0)
    common.check_layout(model, params)
    batches = gen.token_pool(
        seed, batch=job["workers"] * job["seqs_per_worker"],
        seq_len=job["seq_len"], pool=job["pool"], modes=job["modes"],
        active_vocab=job["active_vocab"])
    plan = build_plan(model, cfg["program_arch"], job, seed)
    from repro.defense.reputation import init_reputation
    from repro.experiment.topology import make_topology
    from repro.optim import init_opt_state
    opt = init_opt_state(plan.opt_cfg, params)
    defense = init_reputation(job["workers"]) if job["defense"] else None
    feed = Feed(h, batches, w0, job["lr"], job["warmup_steps"],
                job["checked_steps"])
    plan = dataclasses.replace(plan, batch_fn=feed.batch, eval_fn=None)
    del params, w0
    try:
        make_topology("sync_ps").run(plan, init_state=(
            common.to_program(feed.w0), opt, defense))
        raise RuntimeError("the loop ended before the window closed")
    except WindowClosed:
        pass
    feed._annotate(None)
    trace = h.stop_trace(ANNOTATIONS)
    losses = [float(x) for x in jax.device_get(feed.losses)]
    failed = sum(not math.isfinite(x) for x in losses[job["warmup_steps"]:])
    losses = losses[:job["checked_steps"]]
    readings = {k: {n: float(x) for n, x in v.items()}
                for k, v in feed.readings.items()}
    return feed, (losses, readings["grad"], readings["change"]), failed, \
        trace


def run(h) -> dict:
    cfg, job = h.config, h.traffic
    feed, prog, failed, trace = run_program(h, cfg, job)
    memory = h.memory_peak()
    batches = feed.batches[:job["checked_steps"]]
    tokens = (feed.window_steps * job["workers"] * job["seqs_per_worker"]
              * job["seq_len"])
    window_s = feed.t_end - h.t_open
    rate = tokens / window_s
    print(f"window: {feed.window_steps} steps in {window_s:.3f} s, up to "
          f"{feed.ahead} dispatched ahead, {feed.t_end - feed.t_last:.3f} s "
          "waited at the close", file=sys.stderr)
    feed.batches = feed.w0 = None
    gc.collect()
    ref = reference_readings(cfg, job, h.seed, batches)
    nums = compare(prog, ref)
    res = {"attempted": feed.window_steps, "failed": failed,
           "memory": memory,
           "checks": {k: (v, job["limits"][k]) for k, v in nums.items()}}
    if not h.trace:
        res["metrics"] = {
            "train_tokens_per_s": {"value": rate, "unit": "tokens/s"},
            "setup_s": {"value": h.setup_s(), "unit": "s"}}
        return res
    from bench import harness
    from bench import trace as tr
    kind = h.devices[0].device_kind
    ctx = {"trace": trace, "tokens_per_s": rate, "config": cfg, "job": job,
           "peaks": peaks.peaks_for(kind), "chips": len(h.devices),
           "steps": feed.window_steps, "costs": costs}
    res["metrics"] = harness.per_layer(h, ctx)
    res["busy_s"] = tr.busy_ns(trace) * 1e-9
    res["window_s"] = tr.window_ns(trace) * 1e-9
    res["breakdown"] = tr.breakdown(trace)
    return res
