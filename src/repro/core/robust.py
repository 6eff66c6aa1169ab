"""Robust aggregation engine: pytree-level + distributed (shard_map) layouts.

Two distributed layouts (see DESIGN.md §2):

* ``replicated`` — paper-faithful PS emulation.  ``all_gather`` the full local
  gradient over the worker axes, every device robust-aggregates the complete
  (m, D_local) matrix redundantly.  Collective bytes ~ m·D per device.

* ``sharded`` — beyond-paper *robust reduce-scatter*.  ``all_to_all`` re-tiles
  the worker-gradient matrix so each device holds (m, D_local/m), aggregates
  its slice once, then ``all_gather`` (tiled) rebuilds the update.  This is the
  paper's own multi-server parameter partitioning (§5.1.4) turned into a TPU
  collective schedule; bytes ~ 2·D, aggregation compute 1/m.

Rule dispatch is fully registry-driven (DESIGN.md §6): ``RobustConfig`` is a
thin serializable spec that resolves to a registered
:class:`repro.core.registry.AggregatorRule`; both layouts simply call the
rule's ``reduce_sharded(mat, psum_axes)`` hook.  Coordinate-wise rules
inherit the slice-local default; vector-wise rules (Krum family, geomedian)
``psum`` their partial per-vector statistics over the dim-sharded worker
axes and the ``model`` (tensor-parallel) axes so selection sees full-vector
geometry.  The engine itself knows no rule names.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.core import registry
from repro.core.attacks import AttackConfig, make_attack
# The gate lives in core/selection.py (so the registry's default fused hook
# can use it without importing this engine module); re-exported here because
# it is part of the engine's public defense surface.
from repro.core.selection import gate_matrix  # noqa: F401
from repro.dist.collectives import (
    all_to_all_scatter as _a2a_scatter,
    axis_size as _axis_size,
    gather_slices as _gather_slices,
    gather_workers as _gather_workers,
    worker_slice_index as _worker_slice_index,
)


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """Serializable spec of the robust-aggregation stage of ``train_step``.

    ``rule`` names any registered aggregation rule (see
    ``registry.available_rules()``); all rule parameters are plain fields so
    the config round-trips through JSON/argparse, and ``rule_obj()`` resolves
    the spec to a bound rule object through the registry.
    """
    rule: str = "phocas"          # any registered rule name
    b: int = 2                    # trim parameter (trmean/phocas family)
    q: int = 2                    # assumed Byzantine count (krum family)
    multikrum_k: Optional[int] = None  # Multi-Krum selection size (None = m-q-2)
    geomedian_iters: int = 8      # Weiszfeld iteration count
    layout: str = "sharded"       # replicated | sharded
    backend: str = "auto"         # auto | pallas | xla (per-rule resolution)
    agg_dtype: str = "float32"    # robust statistics dtype
    attack: AttackConfig = dataclasses.field(default_factory=AttackConfig)
    # Deprecated alias for backend= (True -> "pallas", False -> "xla").
    use_kernels: dataclasses.InitVar[Optional[bool]] = None

    def __post_init__(self, use_kernels: Optional[bool]):
        if use_kernels is not None:
            warnings.warn(
                "RobustConfig(use_kernels=...) is deprecated; use "
                "backend='pallas'|'xla'|'auto'", DeprecationWarning,
                stacklevel=3)
            object.__setattr__(self, "backend",
                               "pallas" if use_kernels else "xla")

    def rule_params(self) -> registry.RuleParams:
        return registry.RuleParams(
            b=self.b, q=self.q, multikrum_k=self.multikrum_k,
            geomedian_iters=self.geomedian_iters, backend=self.backend)

    def rule_obj(self) -> registry.AggregatorRule:
        """Resolve this spec to a bound rule object via the registry."""
        return registry.make_rule(self.rule, self.rule_params())

    def aggregator(self):
        """Unary ``(m, ...) -> (...)`` closure (registry-resolved)."""
        return self.rule_obj().reduce


# ---------------------------------------------------------------------------
# Local (single host / test) path
# ---------------------------------------------------------------------------

def aggregate_matrix(u: jax.Array, cfg: RobustConfig,
                     key: Optional[jax.Array] = None, *,
                     active: Optional[jax.Array] = None,
                     with_scores: bool = False,
                     step: Optional[jax.Array] = None):
    """Aggregate an (m, d) worker matrix, optionally injecting the attack.

    ``active`` applies the reputation gate (after the attack — the defense
    never sees pre-corruption data); ``with_scores=True`` returns
    ``(agg, scores)`` via the rule's ``reduce_with_scores`` hook.  ``step``
    is the training step, forwarded to step-aware (adaptive) attacks;
    without it those attacks assume their worst-case phase.

    Scoring always observes the RAW submissions while the aggregate uses
    the gated matrix: if ejected rows were also replaced for scoring, an
    ejected worker would instantly look conforming, recover reputation,
    and be readmitted while still misbehaving (eject/readmit flapping).
    Readmission must be earned by actually-clean submissions."""
    attack = make_attack(cfg.attack)
    with jax.named_scope("stack"):
        uf = u.astype(cfg.agg_dtype)
    if attack is not None:
        if key is None:
            raise ValueError("attack configured but no PRNG key supplied")
        with jax.named_scope("attack"):
            uf = attack(key, uf, step)
    rule = cfg.rule_obj()
    if with_scores:
        # One fused hook: raw-submission scores + gated aggregate.  The
        # registry default composes the old two-pass path; the trim-family
        # rules override it with a single shared selection pass.
        with jax.named_scope("rule"):
            return rule.reduce_gated_with_scores(uf, active)
    if active is not None:
        uf = gate_matrix(uf, active)
    with jax.named_scope("rule"):
        return rule.reduce(uf)


def aggregate_stacked_tree(stacked, cfg: RobustConfig,
                           key: Optional[jax.Array] = None, *,
                           active: Optional[jax.Array] = None,
                           with_scores: bool = False,
                           step: Optional[jax.Array] = None):
    """Aggregate a pytree whose leaves are stacked (m, *leaf_shape) arrays.

    Flattens to a single (m, D) matrix so vector-wise rules (krum) see full
    gradient geometry, then unflattens the aggregated vector.  With
    ``with_scores=True`` returns ``(tree, scores)``.
    """
    leaves = jax.tree_util.tree_leaves(stacked)
    m = leaves[0].shape[0]
    # ravel each worker's slice identically
    with jax.named_scope("stack"):
        flat0, unravel = ravel_pytree(jax.tree.map(lambda x: x[0],
                                                   stacked))
        mat = jax.vmap(lambda i: ravel_pytree(
            jax.tree.map(lambda x: x[i], stacked))[0])(jnp.arange(m))
    out = aggregate_matrix(mat, cfg, key, active=active,
                           with_scores=with_scores, step=step)
    if with_scores:
        agg, scores = out
        return unravel(agg.astype(flat0.dtype)), scores
    return unravel(out.astype(flat0.dtype))


# ---------------------------------------------------------------------------
# Distributed path (must be called inside shard_map)
# ---------------------------------------------------------------------------

def robust_aggregate_dist(grad_tree, cfg: RobustConfig,
                          worker_axes: Sequence[str],
                          model_axes: Sequence[str] = (),
                          key: Optional[jax.Array] = None,
                          active: Optional[jax.Array] = None,
                          with_scores: bool = False,
                          step: Optional[jax.Array] = None):
    """Aggregate per-worker gradient pytrees inside ``shard_map``.

    Args:
      grad_tree: the *local* gradient pytree (this worker-shard's gradient,
        already psum'd over ``model_axes`` microbatch internals as needed).
      cfg: robust config (rule, layout, simulated attack).
      worker_axes: mesh axes playing the paper's "worker" role, e.g.
        ``("data",)`` or ``("pod", "data")``.
      model_axes: tensor-parallel axes (needed only by vector-wise rules'
        partial-statistic psums).
      key: per-step PRNG key (replicated), required when an attack is set.
      step: replicated training-step scalar, forwarded to step-aware
        (adaptive) attacks; None = worst-case phase.
      active: replicated (m,) reputation mask — ejected workers' rows are
        gated (``gate_matrix``) before the rule runs.
      with_scores: also return the rule's per-worker suspicion scores,
        psum'd over the layout's sharded axes so they come back replicated
        (the ``repro.defense`` contract, DESIGN.md §7).

    Returns the aggregated gradient pytree with the input structure/dtypes
    (plus the (m,) scores when ``with_scores``).
    """
    worker_axes = tuple(worker_axes)
    m = _axis_size(worker_axes)
    with jax.named_scope("stack"):
        flat, unravel = ravel_pytree(grad_tree)
        flat = flat.astype(cfg.agg_dtype)
        d = flat.shape[0]
        pad = (-d) % m
        if pad:
            flat = jnp.pad(flat, (0, pad))

    attack = make_attack(cfg.attack)
    rule = cfg.rule_obj()

    def _reduce(mat, psum_axes):
        # Scores observe RAW submissions; the aggregate uses the gated
        # matrix (see aggregate_matrix: prevents eject/readmit flapping).
        # Both come out of the one fused hook.
        if with_scores:
            with jax.named_scope("rule"):
                return rule.reduce_sharded_gated_with_scores(mat, active,
                                                             psum_axes)
        if active is not None:
            mat = gate_matrix(mat, active)
        with jax.named_scope("rule"):
            return rule.reduce_sharded(mat, psum_axes), None

    if cfg.layout == "replicated":
        mat = _gather_workers(flat, worker_axes)          # (m, D)
        if attack is not None:
            with jax.named_scope("attack"):
                mat = attack(key, mat, step)
        agg, scores = _reduce(mat, tuple(model_axes))      # (D,)
    elif cfg.layout == "sharded":
        mat = _a2a_scatter(flat, worker_axes)             # (m, D/m)
        if attack is not None:
            # Each device is a "server" owning a slice of the dims — exactly
            # the paper's §5.1.4 multi-server partitioning.
            key = jax.random.fold_in(key, _worker_slice_index(worker_axes)) \
                if key is not None else None
            with jax.named_scope("attack"):
                mat = attack(key, mat, step)
        agg_slice, scores = _reduce(
            mat, worker_axes + tuple(model_axes))         # (D/m,)
        agg = _gather_slices(agg_slice, worker_axes)      # (D,)
    else:
        raise ValueError(f"unknown layout {cfg.layout!r}")

    if pad:
        agg = agg[:d]
    agg_tree = unravel(agg.astype(ravel_pytree(grad_tree)[0].dtype))
    if with_scores:
        return agg_tree, scores
    return agg_tree
