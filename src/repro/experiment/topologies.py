"""Builtin topology plugins: the three training-loop shapes the repo grew
as divergent drivers, now behind one ``Topology.run(plan)`` contract.

* ``sync_ps``   — the paper's synchronous parameter server (one SPMD
  program; DESIGN.md §2), with optional device mesh, defense loop, and the
  adaptive-b experiment step (ROADMAP item a).
* ``async_ps``  — buffered-async PS with geometric staleness (the paper's
  stated future work; ``train/async_sgd.py`` is the jitted engine).
* ``streaming`` — memory-bounded sequential scan (``train/streaming.py``);
  O((2b+1)·|θ|) instead of O(m·|θ|), collusion attacks excluded by
  metadata.

Each topology drives the existing jitted step builders — the engines stay
where they were; what moved here is the *loop*: batching, telemetry,
history records, checkpointing, adaptation.  The deprecated driver shims
(``Trainer``, ``run_async_training``, ``run_streaming_training``) call
these same loops via ``plan_from_parts``, so legacy and spec-built runs
share one code path step-for-step.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.core import registry
from repro.data.pipeline import make_worker_batches
from repro.experiment.runner import ExperimentResult, Plan
from repro.experiment.spec import SpecError
from repro.experiment.topology import Topology, register_topology
from repro.obs.metrics import make_recorder
from repro.optim.optimizers import init_opt_state
from repro.train.streaming import STREAMING_ATTACKS


def _mask_flips(rec, prev, now, stream: str):
    """Count active-mask transitions into ejection/readmission counters;
    returns the new mask (host list).  The ejection *timeline* lives in
    the JSONL records; these counters are the at-a-glance Prometheus
    view the same data."""
    now = [bool(x) for x in now]
    if prev is not None and len(prev) == len(now):
        ej = sum(1 for w, n in zip(prev, now) if w and not n)
        re = sum(1 for w, n in zip(prev, now) if n and not w)
        if ej:
            rec.count("ejections", ej, stream=stream)
        if re:
            rec.count("readmissions", re, stream=stream)
    return now


def _defense_gauges(rec, *, rule_name: str, m: int, q_hat: int,
                    b: int, q: int) -> None:
    """q̂ + Δ-resilience-margin gauges for one defended step.

    ``resilience_margin`` is the paper-level safety slack: how many more
    Byzantine workers the configured rule tolerates beyond the detector's
    current estimate (tolerance − q̂; negative means the run has left the
    rule's proven envelope).  ``delta_bound`` is the unit-variance Δ bound
    at (m, q̂, b) from core/bounds.py, when the theory defines one."""
    rule_meta = registry.get_rule(rule_name)
    tolerance = b if rule_meta.uses_b else q
    rec.gauge("q_hat", q_hat)
    rec.gauge("resilience_margin", tolerance - q_hat, rule=rule_name)
    from repro.defense.detector import _delta_bound
    bound = _delta_bound(rule_name, m, q_hat, b, 1.0)
    if bound is not None:
        rec.gauge("delta_bound_unit_var", bound, rule=rule_name)


def _profile_step(rec, plan: Plan, step_fn, args) -> None:
    """One AOT compile of the train step (a cache hit where the persistent
    compilation cache holds it), read twice: FLOPs/bytes gauges when
    metrics and ``obs.profile_cost`` are on, and the scope of each HLO
    instruction (``rec.scopes``, keyed by the module's name) when tracing
    is on, so a profiler trace's ops can be put down to the step's named
    scopes."""
    cost = (rec.metrics_enabled and plan.obs is not None
            and getattr(plan.obs, "profile_cost", False))
    if not (cost or rec.trace_enabled):
        return
    from repro.obs.profile import compiled_cost, hlo_scopes
    compiled = step_fn.lower(*args).compile()
    if cost:
        for name, v in compiled_cost(compiled).items():
            rec.gauge(f"step_{name}", v)
    if rec.trace_enabled:
        text = compiled.as_text()
        module = text.split(",", 1)[0].removeprefix("HloModule ").strip()
        rec.scopes[module] = hlo_scopes(text)


@register_topology
class SyncPS(Topology):
    """The paper's synchronous PS loop (port of ``Trainer.run``)."""

    name = "sync_ps"
    supports_mesh = True
    supports_defense = True
    supports_adapt_b = True
    fault_allowlist = None      # every registered fault kind
    supports_resume = True
    supports_compression = True
    supports_stateful_codecs = True

    def run(self, plan: Plan, init_state=None) -> ExperimentResult:
        from jax.flatten_util import ravel_pytree

        from repro.compress.pipeline import bytes_per_round
        from repro.compress.spec import make_codec
        from repro.faults.injector import make_injector, resolve_quorum
        from repro.train.step import (make_train_step, replicate_unsharded,
                                      shard_params)

        m = plan.num_workers
        robust_cfg = plan.robust_cfg
        dcfg = plan.defense_cfg
        rule_meta = registry.get_rule(robust_cfg.rule)

        injector = make_injector(plan.faults, m, plan.seed)
        if injector is not None and plan.mesh is not None:
            raise SpecError("sync_ps cannot drop whole workers from a "
                            "dim-sharded mesh; run faults without mesh")

        def build_step(rc, workers=m, mesh=plan.mesh):
            return make_train_step(
                plan.model, robust_cfg=rc, opt_cfg=plan.opt_cfg,
                num_workers=workers, mesh=mesh, donate=False,
                defense_cfg=dcfg, compress_cfg=plan.compress_cfg)

        codec = (make_codec(plan.compress_cfg)
                 if plan.compress_cfg is not None else None)

        def invoke(fn, params, opt_state, batch, sk, dstate, rsub):
            """One engine call, normalized over the four step signatures
            (defense × compression) to (params, opt, dstate, rsub,
            metrics) so every loop branch threads the same 5-tuple."""
            if dstate is not None and codec is not None:
                return fn(params, opt_state, batch, sk, dstate, rsub)
            if dstate is not None:
                p, o, d, mt = fn(params, opt_state, batch, sk, dstate)
                return p, o, d, rsub, mt
            if codec is not None:
                p, o, r, mt = fn(params, opt_state, batch, sk, rsub)
                return p, o, dstate, r, mt
            p, o, mt = fn(params, opt_state, batch, sk)
            return p, o, dstate, rsub, mt

        step_fn = build_step(robust_cfg)
        # Degraded-round steps, keyed by the effective (m', b', q', q_atk')
        # quorum: crash patterns repeat (a crashed worker stays crashed),
        # so re-jits amortize to one per distinct quorum shape.
        fault_steps: dict = {}
        if init_state is not None:
            params, opt_state, defense_state = init_state
        else:
            params = plan.model.init(jax.random.PRNGKey(plan.seed))
            if plan.mesh is not None:
                params = shard_params(params, plan.mesh)
            opt_state = init_opt_state(plan.opt_cfg, params)
            defense_state = None
            if dcfg is not None:
                from repro.defense.reputation import init_reputation
                defense_state = init_reputation(m)
            if plan.mesh is not None:
                opt_state, defense_state = replicate_unsharded(
                    (opt_state, defense_state), plan.mesh)
        dense_dim = 0
        resid = None
        if codec is not None:
            dense_dim = ravel_pytree(params)[0].size
            resid = codec.init_state(m, dense_dim)

        # adapt_b bookkeeping (ROADMAP item a): the detector's online q̂
        # feeds back into the rule's b/q.  Changing b changes the rule's
        # static selection windows, so each adaptation re-jits the step —
        # a host-side decision, made only after q̂ > current for
        # ``adapt_patience`` consecutive steps (noise hysteresis).
        adapt = dcfg is not None and dcfg.adapt_b
        bmax = (m + 1) // 2 - 1
        pending = 0

        key = jax.random.PRNGKey(plan.seed + 1)
        history: list = []
        metrics: dict = {}
        prev_active = None
        profiled_cost = False
        start_step = 0
        t0 = time.time()
        with make_recorder(plan.telemetry_path, plan.obs) as rec:
            if plan.resume_path:
                from repro.checkpoint.io import restore_checkpoint
                like = {"params": params, "opt": opt_state, "key": key,
                        "rule": _rule_tree(robust_cfg)}
                if defense_state is not None:
                    like["defense"] = defense_state
                if codec is not None and codec.stateful:
                    like["compress"] = resid
                # "key"/"rule"/"compress" are optional: checkpoints from
                # before the resume/compress eras lack them (restore
                # proceeds; bit-for-bit equivalence then needs a
                # new-format checkpoint).
                tree, ck_step, used_prev = restore_checkpoint(
                    plan.resume_path, like,
                    optional=("key", "rule", "compress"))
                params, opt_state = tree["params"], tree["opt"]
                key = tree["key"]
                defense_state = tree.get("defense", defense_state)
                resid = tree.get("compress", resid)
                if plan.mesh is not None:
                    params = shard_params(params, plan.mesh)
                b_r = int(tree["rule"]["b"])
                q_r = int(tree["rule"]["q"])
                if (b_r, q_r) != (robust_cfg.b, robust_cfg.q):
                    # the run had adapted b/q by checkpoint time
                    robust_cfg = dataclasses.replace(robust_cfg, b=b_r,
                                                     q=q_r)
                    step_fn = build_step(robust_cfg)
                start_step = ck_step + 1
                rec.log("resume", ck_step, path=plan.resume_path,
                        fallback=bool(used_prev), b=b_r, q=q_r)
                rec.count("resumes")
                if plan.verbose:
                    src = "prev checkpoint" if used_prev else "checkpoint"
                    print(f"resumed from {src} at step {ck_step} "
                          f"({plan.resume_path})", flush=True)
            for step in range(start_step, plan.steps):
                with rec.span("sync_ps/input", step_num=step):
                    batch = make_worker_batches(plan.batch_fn(step), m)
                key, sk = jax.random.split(key)
                fr = injector.collect(step) if injector is not None else None
                if fr is not None:
                    rec.gauge("present_workers", fr.m_eff)
                    if fr.retries:
                        rec.count("fault_retries", fr.retries)
                    if fr.timeouts:
                        rec.count("fault_timeouts", fr.timeouts)
                    if defense_state is not None:
                        from repro.defense.reputation import update_presence
                        defense_state = update_presence(
                            defense_state,
                            jnp.asarray(fr.present, jnp.float32), dcfg)
                if fr is not None and fr.m_eff < 2:
                    # No quorum this round: the server cannot aggregate.
                    # The round is lost (params unchanged), not the run.
                    rec.count("quorum_failures")
                    rec.log("fault", step, present=int(fr.m_eff),
                            crashed=fr.crashed, retries=fr.retries,
                            timeouts=fr.timeouts, lost_round=True)
                    continue
                degraded = fr is not None and fr.degraded
                if degraded:
                    rc_eff, q_atk = resolve_quorum(robust_cfg, fr.present)
                    ck = (fr.m_eff, rc_eff.b, rc_eff.q, q_atk)
                    fn = fault_steps.get(ck)
                    if fn is None:
                        fn = fault_steps[ck] = build_step(
                            rc_eff, workers=fr.m_eff, mesh=None)
                    idx = jnp.asarray(fr.index)
                    cbatch = jax.tree.map(lambda x: x[idx], batch)
                    rec.log("fault", step, present=int(fr.m_eff),
                            crashed=fr.crashed, retries=fr.retries,
                            timeouts=fr.timeouts, b_eff=rc_eff.b,
                            q_eff=rc_eff.q)
                    # EF residual rows travel with their workers: compact
                    # to the present set, scatter back after the step
                    # (absent workers' residuals stay frozen — nothing
                    # was sent, so no new quantization error accrued).
                    sub_r = (resid[idx]
                             if codec is not None and codec.stateful
                             else resid)
                    sub = ({k: (v[idx] if getattr(v, "ndim", 0) == 1
                                else v)
                            for k, v in defense_state.items()}
                           if defense_state is not None else None)
                    with rec.span("sync_ps/dispatch", step_num=step,
                                  rule=rc_eff.rule):
                        (params, opt_state, sub, sub_r, metrics) = invoke(
                            fn, params, opt_state, cbatch, sk, sub, sub_r)
                    if defense_state is not None:
                        defense_state = _scatter_defense(
                            defense_state, sub, idx)
                        metrics = {**metrics,
                                   "suspicion": _scatter_vec(
                                       metrics["suspicion"], idx, m),
                                   "reputation": defense_state["reputation"],
                                   "active": defense_state["active"]}
                    if codec is not None:
                        resid = (resid.at[idx].set(sub_r)
                                 if codec.stateful else sub_r)
                else:
                    if not profiled_cost:
                        profiled_cost = True
                        args = (params, opt_state, batch, sk)
                        if defense_state is not None:
                            args = args + (defense_state,)
                        if codec is not None:
                            args = args + (resid,)
                        _profile_step(rec, plan, step_fn, args)
                    with rec.span("sync_ps/dispatch", step_num=step,
                                  rule=robust_cfg.rule):
                        (params, opt_state, defense_state, resid,
                         metrics) = invoke(step_fn, params, opt_state, batch,
                                           sk, defense_state, resid)
                with rec.span("sync_ps/record", step_num=step):
                    if defense_state is not None:
                        rec.log("train", step,
                                loss=metrics["loss"],
                                grad_norm=metrics["grad_norm"],
                                suspicion=metrics["suspicion"],
                                reputation=metrics["reputation"],
                                active=metrics["active"],
                                q_hat=metrics["q_hat"])
                    if defense_state is not None and rec.metrics_enabled:
                        rc_now = rc_eff if degraded else robust_cfg
                        prev_active = _mask_flips(
                            rec, prev_active, metrics["active"], "train")
                        _defense_gauges(
                            rec, rule_name=rc_now.rule,
                            m=fr.m_eff if degraded else m,
                            q_hat=int(metrics["q_hat"]), b=rc_now.b,
                            q=rc_now.q)
                    rec.count("steps", topology=self.name)
                    if codec is not None:
                        m_r = fr.m_eff if fr is not None else m
                        rt = fr.retries if fr is not None else 0
                        sent = bytes_per_round(codec, dense_dim, m_r, rt)
                        dense = 4 * dense_dim * (m_r + rt)
                        rec.log("compress", step, codec=codec.name,
                                bytes=sent, dense_bytes=dense,
                                ratio=sent / max(dense, 1))

                    if step % plan.record_every == 0 or step == plan.steps - 1:
                        row = {"step": step, "loss": float(metrics["loss"]),
                               "grad_norm": float(metrics["grad_norm"]),
                               "wall": time.time() - t0}
                        if fr is not None:
                            row["present"] = int(fr.m_eff)
                        if "q_hat" in metrics:
                            row["q_hat"] = int(metrics["q_hat"])
                            row["n_active"] = int(jnp.sum(metrics["active"]))
                        if plan.eval_fn is not None:
                            row["eval"] = float(plan.eval_fn(params))
                        history.append(row)
                        if rec.metrics_enabled:
                            from repro.obs.profile import sample_into
                            sample_into(rec)
                        if plan.verbose:
                            msg = (f"step {step:5d}  loss {row['loss']:.4f}  "
                                   f"gnorm {row['grad_norm']:.3e}")
                            if "q_hat" in row:
                                msg += (f"  qhat {row['q_hat']}  "
                                        f"active {row['n_active']}")
                            if "eval" in row:
                                msg += f"  eval {row['eval']:.4f}"
                            print(msg, flush=True)

                    if (plan.checkpoint_path and plan.checkpoint_every and step
                            and step % plan.checkpoint_every == 0):
                        from repro.checkpoint.io import save_checkpoint
                        # "key" is the loop key AFTER this step's split, and
                        # "rule" the live (possibly adapted) b/q — together
                        # they make --resume continue bit-for-bit.
                        tree = {"params": params, "opt": opt_state,
                                "key": key, "rule": _rule_tree(robust_cfg)}
                        if defense_state is not None:
                            tree["defense"] = defense_state
                        if codec is not None and codec.stateful:
                            # EF residual is run state: dropping it on resume
                            # would re-inject already-compensated error
                            tree["compress"] = resid
                        save_checkpoint(plan.checkpoint_path, tree, step=step)

                    if adapt:
                        q_hat = int(metrics["q_hat"])
                        current = (robust_cfg.b if rule_meta.uses_b
                                   else robust_cfg.q)
                        pending = pending + 1 if q_hat > current else 0
                        if pending >= dcfg.adapt_patience:
                            new_b = (min(q_hat, bmax) if rule_meta.uses_b
                                     else robust_cfg.b)
                            new_q = (min(max(q_hat, robust_cfg.q), m - 3)
                                     if rule_meta.uses_q else robust_cfg.q)
                            pending = 0
                            # q̂ beyond the cap leaves b/q saturated —
                            # nothing to re-jit, and refiring every patience
                            # window would recompile an unchanged step
                            # forever.
                            if (new_b != robust_cfg.b
                                    or new_q != robust_cfg.q):
                                robust_cfg = dataclasses.replace(
                                    robust_cfg, b=new_b, q=new_q)
                                step_fn = build_step(robust_cfg)
                                history.append(
                                    {"step": step, "adapted_b": new_b,
                                     "adapted_q": new_q, "q_hat": q_hat})
                                rec.log("adapt", step, b=new_b, q=new_q,
                                        q_hat=q_hat)
                                rec.count("adaptations")
                                if plan.verbose:
                                    print(f"step {step:5d}  [adapt] "
                                          f"q_hat={q_hat} -> b={new_b} "
                                          f"q={new_q} (re-jit)", flush=True)
            wall = time.time() - t0
            rec.gauge("steps_per_sec",
                      (plan.steps - start_step) / max(wall, 1e-9),
                      topology=self.name)

        return ExperimentResult(
            spec=plan.spec, history=history, params=params,
            opt_state=opt_state, defense_state=defense_state,
            final_metrics=_scalarize(metrics), robust_cfg=robust_cfg,
            wall_time=wall)


def _rule_tree(robust_cfg) -> dict:
    """The checkpointed slice of the live rule config (adapt_b can move
    b/q mid-run; resume must restore them to re-jit the same step)."""
    return {"b": jnp.asarray(robust_cfg.b, jnp.int32),
            "q": jnp.asarray(robust_cfg.q, jnp.int32)}


def _scatter_vec(vec, idx, m):
    """Scatter an (m',) per-present-worker vector into an (m,) vector
    (absent workers read 0 — "no signal this round")."""
    return jnp.zeros((m,), jnp.float32).at[idx].set(
        jnp.asarray(vec, jnp.float32))


def _scatter_defense(full: dict, sub: dict, idx) -> dict:
    """Merge a compacted (m'-row) defense state back into the full m-row
    state: per-worker vectors scatter at the present indices (absent
    workers keep their frozen reputation/active), scalars adopt."""
    out = dict(full)
    for k, v in sub.items():
        old = full[k]
        if getattr(old, "ndim", 0) == 1:
            out[k] = jnp.asarray(old).at[idx].set(v)
        else:
            out[k] = v
    return out


@register_topology
class AsyncPS(Topology):
    """Buffered-async PS (port of ``run_async_training``'s loop)."""

    name = "async_ps"
    supports_defense = True
    param_names = ("staleness", "update_clip")
    fault_allowlist = None      # every registered fault kind
    supports_compression = True
    supports_stateful_codecs = True

    def run(self, plan: Plan, init_state=None) -> ExperimentResult:
        from jax.flatten_util import ravel_pytree

        from repro.compress.pipeline import bytes_per_round
        from repro.compress.spec import make_codec
        from repro.faults.injector import make_injector
        from repro.train.async_sgd import AsyncConfig, make_async_train_step

        m = plan.num_workers
        acfg = AsyncConfig(
            num_workers=m,
            staleness=int(plan.topology_params.get("staleness", 4)),
            update_clip=float(plan.topology_params.get("update_clip", 10.0)),
            seed=plan.seed)
        injector = make_injector(plan.faults, m, plan.seed)
        init_fn, step_fn = make_async_train_step(
            plan.model, robust_cfg=plan.robust_cfg, opt_cfg=plan.opt_cfg,
            acfg=acfg, defense_cfg=plan.defense_cfg,
            faulty=injector is not None, compress_cfg=plan.compress_cfg)
        codec = (make_codec(plan.compress_cfg)
                 if plan.compress_cfg is not None else None)
        key = jax.random.PRNGKey(plan.seed)
        state = init_fn(key) if init_state is None else init_state
        dense_dim = (ravel_pytree(state["params"])[0].size
                     if codec is not None else 0)
        if codec is not None and "compress" not in state:
            state["compress"] = codec.init_state(m, dense_dim)
        history: list = []
        metrics: dict = {}
        prev_active = None
        t0 = time.time()
        with make_recorder(plan.telemetry_path, plan.obs) as rec:
            for i in range(plan.steps):
                with rec.span("async_ps/input", step_num=i):
                    batch = make_worker_batches(plan.batch_fn(i), m)
                if injector is not None:
                    fr = injector.collect(i)
                    present = jnp.asarray(fr.present, jnp.float32)
                    rec.gauge("present_workers", fr.m_eff)
                    if fr.retries:
                        rec.count("fault_retries", fr.retries)
                    if fr.timeouts:
                        rec.count("fault_timeouts", fr.timeouts)
                    with rec.span("async_ps/dispatch", step_num=i,
                                  rule=plan.robust_cfg.rule):
                        state, metrics = step_fn(
                            state, batch, jax.random.fold_in(key, i),
                            present)
                    rec.log("fault", i, present=int(fr.m_eff),
                            crashed=fr.crashed, retries=fr.retries,
                            timeouts=fr.timeouts,
                            m_fresh=metrics["m_fresh"])
                    if plan.defense_cfg is not None:
                        from repro.defense.reputation import update_presence
                        state["defense"] = update_presence(
                            state["defense"], present, plan.defense_cfg)
                else:
                    with rec.span("async_ps/dispatch", step_num=i,
                                  rule=plan.robust_cfg.rule):
                        state, metrics = step_fn(
                            state, batch, jax.random.fold_in(key, i))
                with rec.span("async_ps/record", step_num=i):
                    rec.count("steps", topology=self.name)
                    if codec is not None:
                        rt = fr.retries if injector is not None else 0
                        sent = bytes_per_round(codec, dense_dim, m, rt)
                        dense = 4 * dense_dim * (m + rt)
                        rec.log("compress", i, codec=codec.name, bytes=sent,
                                dense_bytes=dense, ratio=sent / max(dense, 1))
                    if plan.defense_cfg is not None:
                        rec.log("async", i,
                                staleness_frac=metrics["staleness_frac"],
                                suspicion=metrics["suspicion"],
                                reputation=metrics["reputation"],
                                active=metrics["active"],
                                q_hat=metrics["q_hat"])
                        if rec.metrics_enabled:
                            prev_active = _mask_flips(
                                rec, prev_active, metrics["active"], "async")
                            _defense_gauges(
                                rec, rule_name=plan.robust_cfg.rule, m=m,
                                q_hat=int(metrics["q_hat"]),
                                b=plan.robust_cfg.b, q=plan.robust_cfg.q)
                    if i % plan.record_every == 0 or i == plan.steps - 1:
                        row = {"step": i, "staleness_frac":
                               float(metrics["staleness_frac"])}
                        if injector is not None:
                            row["present"] = int(fr.m_eff)
                            row["m_fresh"] = int(metrics["m_fresh"])
                        if "q_hat" in metrics:
                            row["q_hat"] = int(metrics["q_hat"])
                        if plan.eval_fn is not None:
                            row["eval"] = float(plan.eval_fn(state["params"]))
                        history.append(row)
                        if plan.verbose and "eval" in row:
                            print(f"step {i:5d}  eval {row['eval']:.4f}",
                                  flush=True)
            wall = time.time() - t0
            rec.gauge("steps_per_sec", plan.steps / max(wall, 1e-9),
                      topology=self.name)

        return ExperimentResult(
            spec=plan.spec, history=history, params=state["params"],
            opt_state=state["opt"], defense_state=state.get("defense"),
            final_metrics=_scalarize(metrics), robust_cfg=plan.robust_cfg,
            wall_time=wall)


@register_topology
class Streaming(Topology):
    """Memory-bounded scan (port of ``run_streaming_training``'s loop)."""

    name = "streaming"
    attack_allowlist = STREAMING_ATTACKS
    requires_streaming_rule = True
    fault_allowlist = None      # every registered fault kind
    supports_compression = True
    # supports_stateful_codecs stays False: the scan's O((2b+1)·|θ|)
    # memory contract cannot hold an (m, |θ|) error-feedback residual.

    def run(self, plan: Plan, init_state=None) -> ExperimentResult:
        from jax.flatten_util import ravel_pytree

        from repro.compress.pipeline import bytes_per_round
        from repro.compress.spec import make_codec
        from repro.faults.injector import make_injector, resolve_quorum
        from repro.train.streaming import make_streaming_train_step

        m = plan.num_workers
        codec = (make_codec(plan.compress_cfg)
                 if plan.compress_cfg is not None else None)

        def build_step(rc, workers=m):
            return make_streaming_train_step(
                plan.model, robust_cfg=rc, opt_cfg=plan.opt_cfg,
                num_workers=workers, compress_cfg=plan.compress_cfg)

        step_fn = build_step(plan.robust_cfg)
        injector = make_injector(plan.faults, m, plan.seed)
        fault_steps: dict = {}      # (m', b', q', q_atk') -> scan step
        key = jax.random.PRNGKey(plan.seed)
        if init_state is not None:
            params, opt_state, _ = init_state
        else:
            params = plan.model.init(key)
            opt_state = init_opt_state(plan.opt_cfg, params)
        dense_dim = (ravel_pytree(params)[0].size
                     if codec is not None else 0)
        history: list = []
        metrics: dict = {}
        t0 = time.time()
        with make_recorder(plan.telemetry_path, plan.obs) as rec:
            for i in range(plan.steps):
                with rec.span("streaming/input", step_num=i):
                    batch = make_worker_batches(plan.batch_fn(i), m)
                fr = injector.collect(i) if injector is not None else None
                if fr is not None:
                    rec.gauge("present_workers", fr.m_eff)
                    if fr.retries:
                        rec.count("fault_retries", fr.retries)
                    if fr.timeouts:
                        rec.count("fault_timeouts", fr.timeouts)
                if fr is not None and fr.m_eff < 2:
                    rec.count("quorum_failures")
                    rec.log("fault", i, present=int(fr.m_eff),
                            crashed=fr.crashed, retries=fr.retries,
                            timeouts=fr.timeouts, lost_round=True)
                    continue
                if fr is not None and fr.degraded:
                    rc_eff, q_atk = resolve_quorum(plan.robust_cfg,
                                                   fr.present)
                    ck = (fr.m_eff, rc_eff.b, rc_eff.q, q_atk)
                    fn = fault_steps.get(ck)
                    if fn is None:
                        fn = fault_steps[ck] = build_step(
                            rc_eff, workers=fr.m_eff)
                    idx = jnp.asarray(fr.index)
                    cbatch = jax.tree.map(lambda x: x[idx], batch)
                    rec.log("fault", i, present=int(fr.m_eff),
                            crashed=fr.crashed, retries=fr.retries,
                            timeouts=fr.timeouts, b_eff=rc_eff.b,
                            q_eff=rc_eff.q)
                    with rec.span("streaming/dispatch", step_num=i,
                                  rule=rc_eff.rule):
                        params, opt_state, metrics = fn(
                            params, opt_state, cbatch,
                            jax.random.fold_in(key, i))
                else:
                    with rec.span("streaming/dispatch", step_num=i,
                                  rule=plan.robust_cfg.rule):
                        params, opt_state, metrics = step_fn(
                            params, opt_state, batch,
                            jax.random.fold_in(key, i))
                with rec.span("streaming/record", step_num=i):
                    rec.count("steps", topology=self.name)
                    if codec is not None:
                        m_r = fr.m_eff if fr is not None else m
                        rt = fr.retries if fr is not None else 0
                        sent = bytes_per_round(codec, dense_dim, m_r, rt)
                        dense = 4 * dense_dim * (m_r + rt)
                        rec.log("compress", i, codec=codec.name, bytes=sent,
                                dense_bytes=dense, ratio=sent / max(dense, 1))
                    extra = ({"suspicion": metrics["suspicion"]}
                             if "suspicion" in metrics else {})
                    rec.log("streaming", i, loss=metrics["loss"], **extra)
                    if i % plan.record_every == 0 or i == plan.steps - 1:
                        row = {"step": i, "loss": float(metrics["loss"])}
                        if fr is not None:
                            row["present"] = int(fr.m_eff)
                        if plan.eval_fn is not None:
                            row["eval"] = float(plan.eval_fn(params))
                        history.append(row)
                        if plan.verbose:
                            msg = f"step {i:5d}  loss {row['loss']:.4f}"
                            if "eval" in row:
                                msg += f"  eval {row['eval']:.4f}"
                            print(msg, flush=True)
            wall = time.time() - t0
            rec.gauge("steps_per_sec", plan.steps / max(wall, 1e-9),
                      topology=self.name)

        return ExperimentResult(
            spec=plan.spec, history=history, params=params,
            opt_state=opt_state, final_metrics=_scalarize(metrics),
            robust_cfg=plan.robust_cfg, wall_time=wall)


def _scalarize(metrics: dict) -> dict:
    """Final-step metrics with device scalars pulled to floats (per-worker
    vectors and other non-scalars are dropped — they live in telemetry)."""
    out = {}
    for k, v in metrics.items():
        try:
            arr = jnp.asarray(v)
        except TypeError:
            continue
        if arr.ndim == 0:
            out[k] = float(arr)
    return out


@register_topology
class Serve(Topology):
    """Serving as a scenario (DESIGN.md §11): Poisson arrivals through the
    continuous-batching paged engine (``repro.serve.ServeEngine``), with
    ``spec.robust`` selecting the logits-aggregation rule when k replicas
    serve each decode step and ``spec.attack`` corrupting
    ``num_byzantine`` of them (clamped to the replica trim bound).

    ``spec.steps`` caps engine iterations; history records carry queue
    depth and throughput; final metrics are the latency/throughput summary
    ``benchmarks/bench_serve.py`` aggregates over its load-mix grid.
    """

    name = "serve"
    supports_defense = True
    # Replica count lives here (NOT spec.num_workers, which is the training
    # fan-out and must stay >= 2); every key is read via a literal
    # topology_params.get(...) below so repro.analysis CONTRACT006 can
    # cross-check this tuple against the loop body.
    param_names = ("replicas", "max_slots", "max_seq_len", "block_tokens",
                   "num_requests", "arrival_rate", "prompt_len",
                   "max_new_tokens")
    # corrupt_replica injects Gaussian garbage parameters — the only fault
    # model the serving path simulates.
    attack_allowlist = ("gaussian",)

    def validate_spec(self, spec) -> None:
        super().validate_spec(spec)
        if spec.model.kind != "arch":
            raise SpecError("topology 'serve' decodes an arch-zoo model; "
                            "set model.kind='arch' (+ data.kind='tokens')")
        from repro.configs import get_arch
        from repro.models.stack import paged_supported
        if not paged_supported(get_arch(spec.model.arch)):
            raise SpecError(
                f"arch {spec.model.arch!r} is not paged-serving capable "
                "(SSM/hybrid/MLA/enc-dec/windowed layers); pick an "
                "all-global attention arch like 'granite-8b-reduced'")
        k = int(spec.topology_params.get("replicas", 1))
        if k > 1:
            bmax = (k + 1) // 2 - 1
            if not 0 <= spec.robust.b <= bmax:
                raise SpecError(
                    f"replicated decode with k={k} replicas needs "
                    f"0 <= robust.b <= (k+1)//2-1 = {bmax}, got "
                    f"b={spec.robust.b}")
            q = spec.effective_attack().num_byzantine
            if q > bmax:
                raise SpecError(
                    f"attack corrupts {q} replicas but k={k} replicated "
                    f"decode tolerates at most (k+1)//2-1 = {bmax}")

    def run(self, plan: Plan, init_state=None) -> ExperimentResult:
        import numpy as np
        from repro.serve import (RobustDecoder, ServeEngine, corrupt_replica,
                                 make_replicas)

        replicas = int(plan.topology_params.get("replicas", 1))
        max_slots = int(plan.topology_params.get("max_slots", 8))
        max_seq_len = int(plan.topology_params.get("max_seq_len", 128))
        block_tokens = int(plan.topology_params.get("block_tokens", 16))
        num_requests = int(plan.topology_params.get("num_requests", 16))
        # arrival_rate: requests per engine step (Poisson)
        arrival_rate = float(plan.topology_params.get("arrival_rate", 2.0))
        prompt_len = int(plan.topology_params.get("prompt_len", 8))
        max_new = int(plan.topology_params.get("max_new_tokens", 16))

        model = plan.model
        key = jax.random.PRNGKey(plan.seed)
        params = model.init(key) if init_state is None else init_state[0]

        decoder = None
        if replicas > 1:
            rc = plan.robust_cfg
            params = make_replicas(params, replicas)
            corrupt = rc.attack.num_byzantine if rc.attack.name == "gaussian" \
                else 0
            for i in range(corrupt):
                params = corrupt_replica(
                    params, replicas - 1 - i,
                    jax.random.fold_in(key, 1000 + i))
            decoder = RobustDecoder(
                rule=rc.rule, k=replicas, b=rc.b,
                defense=plan.defense_cfg, backend=rc.backend)

        history: list = []
        t0 = time.time()
        with make_recorder(plan.telemetry_path, plan.obs) as rec:
            engine = ServeEngine(
                model, params, max_slots=max_slots, max_seq_len=max_seq_len,
                block_tokens=block_tokens, decoder=decoder, telemetry=rec)

            # Deterministic Poisson arrivals in engine-step time.
            rng = np.random.default_rng(plan.seed)
            gaps = rng.exponential(1.0 / max(arrival_rate, 1e-9),
                                   num_requests)
            due = np.cumsum(gaps)
            prompts = rng.integers(0, model.cfg.vocab_size,
                                   (num_requests, prompt_len))
            submitted = 0
            produced = 0
            for i in range(plan.steps):
                while submitted < num_requests and due[submitted] <= i:
                    engine.submit(prompts[submitted].tolist(), max_new)
                    submitted += 1
                if submitted >= num_requests and not engine.scheduler.busy:
                    break
                produced += engine.step()
                if i % plan.record_every == 0:
                    history.append({
                        "step": i, "submitted": submitted,
                        "queued": engine.scheduler.queued,
                        "active": len(engine.scheduler.active),
                        "tokens": produced})
            engine.retire_finished()

        wall = time.time() - t0
        done = engine.scheduler.completed
        lat = sorted(r.latency_ms() for r in done) or [0.0]
        ttft = sorted(r.first_token_ms() for r in done) or [0.0]
        pct = lambda xs, q: xs[min(len(xs) - 1,  # noqa: E731
                                   int(q * (len(xs) - 1) + 0.5))]
        metrics = {
            "completed": float(len(done)),
            "tokens": float(produced),
            "tokens_per_sec": produced / max(wall, 1e-9),
            "latency_p50_ms": pct(lat, 0.50),
            "latency_p99_ms": pct(lat, 0.99),
            "ttft_p50_ms": pct(ttft, 0.50),
            "engine_steps": float(engine.steps_run),
        }
        if decoder is not None:
            metrics["ejected_replicas"] = float(
                len(decoder.ejected_replicas()))
        history.append({"step": engine.steps_run, **metrics})

        return ExperimentResult(
            spec=plan.spec, history=history, params=params,
            final_metrics=metrics, robust_cfg=plan.robust_cfg,
            wall_time=wall)
