"""Byzantine-resilient synchronous-SGD train step.

The paper's PS loop, as one SPMD program (DESIGN.md §2):

  1. the global batch is reshaped to (m, B/m, ...) worker groups; axis 0 is
     sharded over the mesh worker axes (data [+pod]) — each group is one of
     the paper's m workers;
  2. per-worker gradients come from ``vmap(value_and_grad)`` over the group
     axis (NOT a psum — the per-worker estimates must survive to the
     aggregation stage);
  3. the robust aggregation runs under ``shard_map`` with explicit
     collectives (replicated all-gather = paper-faithful PS; sharded
     all_to_all = the paper's multi-server partitioning as a robust
     reduce-scatter);
  4. the aggregated gradient feeds a standard optimizer update.

Attack injection (simulation of the paper's §5 adversaries) happens inside
stage 3, on the worker-gradient matrix — exactly where a real transmission-
medium corruption would land.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compress.pipeline import aggregate_compressed_tree
from repro.compress.spec import make_codec
from repro.core.robust import RobustConfig, aggregate_stacked_tree, \
    robust_aggregate_dist
from repro.dist.sharding import model_axes_of, tree_pspecs, worker_axes_of
from repro.optim.optimizers import OptConfig, apply_updates


def make_train_step(model, *, robust_cfg: RobustConfig, opt_cfg: OptConfig,
                    num_workers: int, mesh: Optional[Mesh] = None,
                    donate: bool = True, defense_cfg=None,
                    compress_cfg=None):
    """Build the jitted train step.

    Args:
      model: a ``repro.models.Model``.
      num_workers: m — worker groups per step.  In distributed mode must
        equal the product of the mesh worker-axis sizes.
      mesh: if None, aggregation runs locally (tests / laptop scale).
      defense_cfg: a ``repro.defense.DefenseConfig`` to enable the online
        defense loop (suspicion scores -> reputation EMA -> gated
        aggregation -> q̂); None keeps the plain paper-faithful step.
      compress_cfg: a ``repro.compress.CompressionSpec`` to route the
        worker matrix through the codec wire model (encode → wire attack
        → decode → reduce); None keeps the dense path byte-identical.

    Without defense, returns ``step(params, opt_state, batch, key) ->
    (params, opt_state, metrics)`` where batch leaves are worker-stacked
    (m, B/m, ...).  With defense, the step additionally threads the
    reputation state: ``step(params, opt_state, batch, key, defense) ->
    (params, opt_state, defense, metrics)`` and the metrics gain
    ``suspicion`` / ``reputation`` / ``active`` / ``q_hat``.  With
    compression, the step threads the codec's error-feedback residual as
    its trailing argument AND trailing extra return — ``step(params,
    opt_state, batch, key[, defense], resid)`` — and the caller seeds it
    with ``codec.init_state(m, D)``.
    """
    m = num_workers
    codec = make_codec(compress_cfg) if compress_cfg is not None else None
    if codec is not None and mesh is not None:
        # spec validation excludes this pairing; guard the direct API too
        raise ValueError(
            "gradient compression encodes whole worker rows and cannot "
            "run under a dim-sharded mesh; pass mesh=None")
    if mesh is not None:
        wa = worker_axes_of(mesh)
        msize = 1
        for a in wa:
            msize *= mesh.shape[a]
        if msize != m:
            raise ValueError(f"num_workers={m} != mesh worker axes size {msize}")
        ma = model_axes_of(mesh)

    def worker_loss(params, sub_batch):
        return model.loss(params, sub_batch)

    def worker_grads(params, batch):
        from repro.models import moe
        # worker tokens are already shard-local
        with jax.named_scope("grads"), moe.no_data_grouping():
            return jax.vmap(jax.value_and_grad(worker_loss),
                            in_axes=(None, 0))(params, batch)

    def aggregate(params, grads, key, active, with_scores, train_step):
        """Robust aggregation in either layout, under the ``aggregate``
        scope; scores come back replicated.  ``train_step`` (the
        optimizer's step counter) reaches step-aware adaptive attacks
        through the engine."""
        with jax.named_scope("aggregate"):
            if mesh is None:
                return aggregate_stacked_tree(grads, robust_cfg, key,
                                              active=active,
                                              with_scores=with_scores,
                                              step=train_step)
            pspecs = tree_pspecs(params, mesh)
            stacked_specs = jax.tree.map(
                lambda sp: P(wa, *sp), pspecs,
                is_leaf=lambda x: isinstance(x, P))

            out_specs = (pspecs, P()) if with_scores else pspecs
            if active is None:
                def agg_fn(g, k, ts):
                    local = jax.tree.map(lambda x: x[0], g)
                    return robust_aggregate_dist(
                        local, robust_cfg, worker_axes=wa, model_axes=ma,
                        key=k, with_scores=with_scores, step=ts)

                return jax.shard_map(agg_fn, mesh=mesh,
                                     in_specs=(stacked_specs, P(), P()),
                                     out_specs=out_specs,
                                     check_vma=False)(grads, key, train_step)

            def agg_gated(g, k, act, ts):
                local = jax.tree.map(lambda x: x[0], g)
                return robust_aggregate_dist(
                    local, robust_cfg, worker_axes=wa, model_axes=ma,
                    key=k, active=act, with_scores=with_scores, step=ts)

            return jax.shard_map(
                agg_gated, mesh=mesh,
                in_specs=(stacked_specs, P(), P(), P()),
                out_specs=out_specs,
                check_vma=False)(grads, key, active, train_step)

    def optimize(params, agg, opt_state):
        with jax.named_scope("optimizer"):
            return apply_updates(opt_cfg, params, agg, opt_state)

    def step(params, opt_state, batch, key):
        losses, grads = worker_grads(params, batch)
        agg = aggregate(params, grads, key, None, False, opt_state["step"])
        params, opt_state = optimize(params, agg, opt_state)
        metrics = {"loss": jnp.mean(losses),
                   "loss_per_worker": losses,
                   "grad_norm": _tree_norm(agg)}
        return params, opt_state, metrics

    def defense_step(params, opt_state, batch, key, defense):
        from repro.defense.detector import estimate_q
        from repro.defense.reputation import update_reputation
        losses, grads = worker_grads(params, batch)
        agg, scores = aggregate(params, grads, key, defense["active"], True,
                                opt_state["step"])
        with jax.named_scope("defense"):
            defense = update_reputation(defense, scores, defense_cfg)
        params, opt_state = optimize(params, agg, opt_state)
        metrics = {"loss": jnp.mean(losses),
                   "loss_per_worker": losses,
                   "grad_norm": _tree_norm(agg),
                   "suspicion": scores,
                   "reputation": defense["reputation"],
                   "active": defense["active"]}
        with jax.named_scope("defense"):
            metrics["q_hat"] = estimate_q(
                scores, min_gap=defense_cfg.detector_min_gap)
        return params, opt_state, defense, metrics

    def compress_step(params, opt_state, batch, key, resid):
        losses, grads = worker_grads(params, batch)
        with jax.named_scope("aggregate"):
            agg, resid = aggregate_compressed_tree(
                grads, robust_cfg, codec, resid, key, step=opt_state["step"])
        params, opt_state = optimize(params, agg, opt_state)
        metrics = {"loss": jnp.mean(losses),
                   "loss_per_worker": losses,
                   "grad_norm": _tree_norm(agg)}
        return params, opt_state, resid, metrics

    def compress_defense_step(params, opt_state, batch, key, defense, resid):
        from repro.defense.detector import estimate_q
        from repro.defense.reputation import update_reputation
        losses, grads = worker_grads(params, batch)
        with jax.named_scope("aggregate"):
            agg, scores, resid = aggregate_compressed_tree(
                grads, robust_cfg, codec, resid, key,
                active=defense["active"], with_scores=True,
                step=opt_state["step"])
        with jax.named_scope("defense"):
            defense = update_reputation(defense, scores, defense_cfg)
        params, opt_state = optimize(params, agg, opt_state)
        metrics = {"loss": jnp.mean(losses),
                   "loss_per_worker": losses,
                   "grad_norm": _tree_norm(agg),
                   "suspicion": scores,
                   "reputation": defense["reputation"],
                   "active": defense["active"]}
        with jax.named_scope("defense"):
            metrics["q_hat"] = estimate_q(
                scores, min_gap=defense_cfg.detector_min_gap)
        return params, opt_state, defense, resid, metrics

    donate_argnums = (0, 1) if donate else ()
    if codec is not None:
        if defense_cfg is not None:
            return jax.jit(compress_defense_step,
                           donate_argnums=donate_argnums)
        return jax.jit(compress_step, donate_argnums=donate_argnums)
    if defense_cfg is not None:
        return jax.jit(defense_step, donate_argnums=donate_argnums)
    return jax.jit(step, donate_argnums=donate_argnums)


def _tree_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def shard_params(params, mesh: Mesh):
    """Device-put params according to the TP rules (entry point for real
    multi-device runs)."""
    specs = tree_pspecs(params, mesh)
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        params, specs)


def replicate_unsharded(tree, mesh: Mesh):
    """Device-put the leaves of ``tree`` not yet laid out on ``mesh`` (step
    counters, reputation vectors) replicated over it.  The step returns
    them replicated; fed from one device, its first call would compile a
    program of its own."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: x if isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, rep), tree)
