"""Shared-selection hot path (core/selection.py, DESIGN.md §8).

New-vs-old equivalence for every coordinate-wise rule across
with_scores/active combinations and both collective layouts: the oracles
below reimplement the pre-fusion ``jnp.sort`` + double-``argsort`` paths
verbatim, so these tests pin the selection rewrite to the seed semantics
(including the gated-aggregate / raw-score defense contract).  Plus unit
coverage of the selection primitives themselves and the geomedian
norm-clip regression (ROADMAP item d, BENCH_detection.json).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (RobustConfig, aggregate_matrix, gate_matrix,
                        registry, selection)
from repro.core.registry import (AggregatorRule, drop_frequency_scores)

KEY = jax.random.PRNGKey(11)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

M, D, B = 9, 257, 2


# ---------------------------------------------------------------------------
# Pre-fusion oracles (the seed implementations, verbatim semantics)
# ---------------------------------------------------------------------------

def old_median(u):
    return jnp.median(u, axis=0)


def old_trmean(u, b):
    m = u.shape[0]
    s = jnp.sort(u, axis=0)
    return jnp.mean(s[b:m - b], axis=0) if b else jnp.mean(s, axis=0)


def old_phocas(u, b):
    m = u.shape[0]
    if b == 0:
        return jnp.mean(u, axis=0)
    center = old_trmean(u, b)
    dist = jnp.abs(u - center[None])
    ranks = jnp.argsort(jnp.argsort(dist, axis=0), axis=0)
    keep = (ranks < (m - b)).astype(u.dtype)
    return jnp.sum(u * keep, axis=0) / (m - b)


def old_mediam(u, b):
    m = u.shape[0]
    if b == 0:
        return jnp.mean(u, axis=0)
    center = jnp.median(u, axis=0)
    dist = jnp.abs(u - center[None])
    ranks = jnp.argsort(jnp.argsort(dist, axis=0), axis=0)
    dropped = ranks >= (m - b)
    return jnp.sum(u * (~dropped).astype(u.dtype), axis=0) / (m - b)


def old_mom(u, b):
    m = u.shape[0]
    g = min(2 * b + 1, m)
    if g <= 1:
        return jnp.mean(u, axis=0)
    gid = jnp.arange(m) % g
    onehot = (gid[None, :] == jnp.arange(g)[:, None]).astype(u.dtype)
    means = jnp.tensordot(onehot, u, axes=(1, 0)) \
        / jnp.sum(onehot, axis=1)[:, None]
    return jnp.median(means, axis=0)


def old_drop_counts(u, b, rule):
    """Seed double-argsort selection masks -> per-worker drop counts."""
    m = u.shape[0]
    if rule == "trmean":
        ranks = jnp.argsort(jnp.argsort(u, axis=0), axis=0)
        dropped = (ranks < b) | (ranks >= m - b)
    else:
        center = old_trmean(u, b) if rule == "phocas" \
            else jnp.median(u, axis=0)
        dist = jnp.abs(u - center[None])
        ranks = jnp.argsort(jnp.argsort(dist, axis=0), axis=0)
        dropped = ranks >= (m - b)
    return jnp.sum(dropped, axis=1).astype(jnp.float32)


def old_gate(u, active):
    med = jnp.median(u, axis=0)
    return jnp.where(active[:, None] > 0, u, med[None])


OLD_AGG = {"median": lambda u, b: old_median(u),
           "trmean": old_trmean, "phocas": old_phocas,
           "mediam": old_mediam, "mom": old_mom,
           "mean": lambda u, b: jnp.mean(u, axis=0),
           # signvote predates no fusion pass; the closed form IS the
           # oracle (majority sign per coordinate, signSGD semantics)
           "signvote": lambda u, b: jnp.sign(
               jnp.sum(jnp.sign(u), axis=0))}
BASELINE = {"trmean": lambda b, m: 2.0 * b / m,
            "phocas": lambda b, m: b / m,
            "mediam": lambda b, m: b / m}


def _u(seed=0, m=M, d=D):
    # continuous data: tie configurations (measure-zero, where old/new
    # legitimately differ in which equal-distance value they drop) excluded
    return 3.0 * jax.random.normal(jax.random.fold_in(KEY, seed), (m, d))


# ---------------------------------------------------------------------------
# New-vs-old: plain aggregates, every coordinate-wise rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", registry.coordinate_wise_rules())
def test_new_vs_old_plain_aggregate(rule):
    assert rule in OLD_AGG, f"add a pre-fusion oracle for new rule {rule!r}"
    u = _u(1)
    got = aggregate_matrix(u, RobustConfig(rule=rule, b=B, backend="xla"))
    ref = OLD_AGG[rule](u, B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("rule", ("trmean", "phocas", "mediam"))
@pytest.mark.parametrize("gated", (False, True))
def test_new_vs_old_with_scores_and_gate(rule, gated):
    """with_scores x active: aggregate AND scores match the seed two-pass
    path (scores observe RAW submissions; aggregate uses the gated
    matrix)."""
    u = _u(2)
    active = jnp.ones((M,)).at[4].set(0.0).at[7].set(0.0) if gated else None
    cfg = RobustConfig(rule=rule, b=B, backend="xla")
    got_agg, got_scores = aggregate_matrix(u, cfg, active=active,
                                           with_scores=True)
    ref_scores = drop_frequency_scores(
        old_drop_counts(u, B, rule), jnp.float32(D), BASELINE[rule](B, M))
    ref_agg = OLD_AGG[rule](old_gate(u, active), B) if gated \
        else OLD_AGG[rule](u, B)
    np.testing.assert_allclose(np.asarray(got_scores),
                               np.asarray(ref_scores), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_agg), np.asarray(ref_agg),
                               atol=1e-4)


@pytest.mark.parametrize("rule", ("trmean", "phocas", "mediam"))
def test_fused_hook_matches_composed_default(rule):
    """The trim-family override of reduce_sharded_gated_with_scores is
    drop-in for the registry's composed default."""
    u = _u(3)
    active = jnp.ones((M,)).at[0].set(0.0)
    r = registry.make_rule(rule, registry.RuleParams(b=B, backend="xla"))
    got_agg, got_sc = r.reduce_gated_with_scores(u, active)
    ref_agg, ref_sc = AggregatorRule.reduce_sharded_gated_with_scores(
        r, u, active, ())
    np.testing.assert_allclose(np.asarray(got_sc), np.asarray(ref_sc),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_agg), np.asarray(ref_agg),
                               atol=1e-4)


def test_gate_matrix_concrete_all_ones_is_free():
    u = _u(4)
    assert gate_matrix(u, jnp.ones((M,))) is u        # short-circuit
    active = jnp.ones((M,)).at[2].set(0.0)
    np.testing.assert_allclose(np.asarray(gate_matrix(u, active)),
                               np.asarray(old_gate(u, active)), atol=1e-6)


# ---------------------------------------------------------------------------
# Selection primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 2, 3, 5, 8, 20, 33, 64])
def test_sorted_rows_matches_jnp_sort(m):
    u = _u(5, m=m, d=101)
    got = jnp.stack(selection.sorted_rows(selection.worker_rows(u)))
    np.testing.assert_allclose(np.asarray(got),
                               np.sort(np.asarray(u), axis=0), atol=0)


@pytest.mark.parametrize("m", [2, 7, 16, 40])
def test_stable_ranks_match_double_argsort_with_duplicates(m):
    key = jax.random.fold_in(KEY, m)
    # heavy duplicates: integer-quantized values
    u = jnp.floor(4 * jax.random.normal(key, (m, 57)))
    ref = jnp.argsort(jnp.argsort(u, axis=0), axis=0)
    got = jnp.stack(selection.stable_ranks(selection.worker_rows(u)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sorted_rows_large_m_fallback(monkeypatch):
    monkeypatch.setattr(selection, "_NETWORK_MAX_M", 4)
    monkeypatch.setattr(selection, "_PAIRWISE_MAX_M", 4)
    u = _u(6, m=9, d=33)
    got = jnp.stack(selection.sorted_rows(selection.worker_rows(u)))
    np.testing.assert_allclose(np.asarray(got),
                               np.sort(np.asarray(u), axis=0), atol=0)
    ref = jnp.argsort(jnp.argsort(u, axis=0), axis=0)
    got_r = jnp.stack(selection.stable_ranks(selection.worker_rows(u)))
    np.testing.assert_array_equal(np.asarray(got_r), np.asarray(ref))


def test_nan_submissions_are_trimmed_not_propagated():
    """A NaN row (the cheapest Byzantine payload) must be selected against
    like jnp.sort's NaN-last placement, not poison every coordinate
    through the network's min/max compare-exchanges."""
    u = jnp.array([[0.0], [1.0], [2.0], [3.0], [jnp.nan]])
    np.testing.assert_allclose(
        np.asarray(selection.trim_family(u, 1, "trmean")[0]), [2.0])
    for kind in ("phocas", "mediam"):
        agg, counts, _ = selection.trim_family(u, 1, kind, with_scores=True)
        assert np.isfinite(np.asarray(agg)).all(), kind
        assert float(counts[4]) == 1.0, kind       # NaN worker is blamed


def test_b0_fused_gate_still_ejects():
    """b=0 degenerates to the mean but the reputation gate must still
    keep an ejected row out of the aggregate (review regression)."""
    u = jnp.array([[0.0], [1.0], [2.0], [1e6]])
    active = jnp.ones((4,)).at[3].set(0.0)
    for rule in ("trmean", "phocas", "mediam"):
        r = registry.make_rule(rule, registry.RuleParams(b=0, backend="xla"))
        got, _ = r.reduce_gated_with_scores(u, active)
        ref, _ = AggregatorRule.reduce_sharded_gated_with_scores(
            r, u, active, ())
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, err_msg=rule)
        assert float(got[0]) < 10.0, rule          # 1e6 row stayed out


def test_pallas_scores_large_m_falls_back_to_xla():
    """m above the counts kernels' 128-lane pack must fall back to the
    XLA selection path instead of crashing (review regression)."""
    u = _u(12, m=130, d=64)
    rp = registry.make_rule("trmean",
                            registry.RuleParams(b=2, backend="pallas"))
    rx = registry.make_rule("trmean",
                            registry.RuleParams(b=2, backend="xla"))
    pa, ps = rp.reduce_with_scores(u)
    xa, xs = rx.reduce_with_scores(u)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(xs), atol=1e-6)
    np.testing.assert_allclose(np.asarray(pa), np.asarray(xa), atol=1e-4)


def test_nearest_window_no_prefix_cancellation():
    """The window sum must survive a 1e19 adversarial row (the bitflip
    regression that rules out a prefix-sum implementation)."""
    u = jnp.concatenate([1.0 + 0.01 * _u(7, m=10, d=16),
                         jnp.full((2, 16), -1.5e19)])
    agg = selection.trim_family(u, 2, "mediam")[0]
    assert np.abs(np.asarray(agg) - 1.0).max() < 0.5


# ---------------------------------------------------------------------------
# Score-emitting kernels: pallas == xla in interpret mode, both variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,b", [(8, 2), (8, 3), (20, 2), (20, 9), (5, 2)])
def test_trmean_counts_kernel_matches_xla(m, b):
    from repro.core.aggregators import trmean_stats
    from repro.kernels.trmean.ops import trmean_with_counts
    u = _u(8, m=m, d=3001)
    ka, kc = trmean_with_counts(u, b)
    xa, xc, _ = trmean_stats(u, b)
    np.testing.assert_allclose(np.asarray(ka), np.asarray(xa), atol=1e-4)
    np.testing.assert_allclose(np.asarray(kc), np.asarray(xc), atol=0)


@pytest.mark.parametrize("m,b", [(8, 2), (8, 3), (20, 2), (20, 9), (5, 2),
                                 (3, 1), (4, 1)])
def test_phocas_counts_kernel_matches_xla(m, b):
    """At widths whose last lane tile is partial (the shape-chosen tile and
    the 2,048-lane one): lanes past d never count."""
    from repro.core.aggregators import phocas_stats
    from repro.kernels.common import lane_tile
    from repro.kernels.phocas.ops import phocas_with_counts
    for d in (3001, 3 * lane_tile(m, 2**31) + 1):
        u = _u(9, m=m, d=d)
        ka, kc = phocas_with_counts(u, b)
        xa, xc, _ = phocas_stats(u, b)
        np.testing.assert_allclose(np.asarray(ka), np.asarray(xa), atol=1e-4)
        np.testing.assert_allclose(np.asarray(kc), np.asarray(xc), atol=0)


def test_kernel_network_variant_heuristic():
    from repro.kernels.trmean.kernel import use_network
    assert not use_network(8, 2 * 2)      # trmean m=8 b=2: extraction
    assert use_network(8, 3 * 3)          # phocas m=8 b=3: network
    assert use_network(20, 3 * 9)         # big-b phocas: network


def test_pallas_backend_scores_through_rule():
    """emits_scores no longer forces the XLA fallback: the pallas backend
    serves reduce_with_scores through the counts kernels."""
    u = _u(10, m=8, d=501)
    for rule in ("trmean", "phocas"):
        rp = registry.make_rule(rule,
                                registry.RuleParams(b=2, backend="pallas"))
        rx = registry.make_rule(rule,
                                registry.RuleParams(b=2, backend="xla"))
        pa, ps = rp.reduce_with_scores(u)
        xa, xs = rx.reduce_with_scores(u)
        np.testing.assert_allclose(np.asarray(pa), np.asarray(xa),
                                   atol=1e-4, err_msg=rule)
        np.testing.assert_allclose(np.asarray(ps), np.asarray(xs),
                                   atol=1e-6, err_msg=rule)


# ---------------------------------------------------------------------------
# Geomedian norm-clip regression (ROADMAP item d / BENCH_detection.json)
# ---------------------------------------------------------------------------

def test_geomedian_scores_localize_under_omniscient_blowup():
    """Seed behavior: omniscient's 1e20 rows kept the 8-iter Weiszfeld
    fixed point from localizing, destroying the rule's suspicion scores.
    With the pre-iteration norm clip the Byzantine rows separate
    cleanly."""
    q = 3
    u = 1.0 + 0.1 * jax.random.normal(KEY, (12, 64))
    u = u.at[:q].set(-1e20)               # omniscient_scale rows
    z, scores = aggregate_matrix(u, RobustConfig(rule="geomedian"),
                                 with_scores=True)
    scores = np.asarray(scores)
    assert scores[:q].min() > scores[q:].max() + 0.2, scores
    assert np.abs(np.asarray(z) - 1.0).max() < 0.5    # fixed point localized


def test_geomedian_clip_leaves_clean_runs_unchanged():
    u = 1.0 + 0.1 * jax.random.normal(KEY, (10, 64))
    from repro.core.aggregators import clip_rows_to_norm_quantile
    np.testing.assert_array_equal(
        np.asarray(clip_rows_to_norm_quantile(u, ())), np.asarray(u))


# ---------------------------------------------------------------------------
# Both collective layouts x active x with_scores (subprocess, 8 devices)
# ---------------------------------------------------------------------------

DIST_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core import RobustConfig, robust_aggregate_dist, aggregate_matrix

mesh = jax.make_mesh((4, 2), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
key = jax.random.PRNGKey(5)
base = 1.0 + 0.1*jax.random.normal(key, (4, 64))
base = base.at[0].set(30.0 * base[0])
grads = {'w': base[:, :60], 'b': base[:, 60:]}
from jax.flatten_util import ravel_pytree
mat = np.stack([ravel_pytree(jax.tree.map(lambda x: x[i], grads))[0]
                for i in range(4)])
active = jnp.ones((4,)).at[0].set(0.0)
results = {}
for rule in ('median', 'trmean', 'phocas', 'mediam', 'mom'):
    cfg_l = RobustConfig(rule=rule, b=1, q=1)
    for layout in ('replicated', 'sharded'):
        cfg = RobustConfig(rule=rule, b=1, q=1, layout=layout)
        for ws in (False, True):
            for act in (None, active):
                ref = aggregate_matrix(jnp.asarray(mat), cfg_l,
                                       active=act, with_scores=ws)
                ref_agg, ref_sc = ref if ws else (ref, None)
                @partial(jax.shard_map, mesh=mesh,
                         in_specs=(P('data'), P()),
                         out_specs=(P(), P()) if ws else P(),
                         check_vma=False)
                def f(g, a):
                    local = jax.tree.map(lambda x: x[0], g)
                    out = robust_aggregate_dist(
                        local, cfg, worker_axes=('data',),
                        model_axes=('model',), active=a, with_scores=ws)
                    if ws:
                        return ravel_pytree(out[0])[0], out[1]
                    return ravel_pytree(out)[0]
                out = f(grads, act if act is not None else jnp.ones((4,)))
                # active=None vs all-ones gate are equivalent semantics
                flat, sc = out if ws else (out, None)
                ok = bool(np.allclose(np.asarray(flat), np.asarray(ref_agg),
                                      atol=1e-4))
                if ws:
                    ok = ok and bool(np.allclose(np.asarray(sc),
                                                 np.asarray(ref_sc),
                                                 atol=1e-4))
                results[f'{rule}/{layout}/ws{int(ws)}/'
                        f'act{int(act is not None)}'] = ok
print(json.dumps(results))
"""


@pytest.mark.slow
def test_layouts_with_scores_and_gate_match_local():
    """Every coordinate-wise rule x layout x with_scores x active combo
    reproduces the local path through shard_map (the §6/§7 psum and gate
    contracts survive the shared-selection rewrite)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", DIST_EQUIV],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(results) == 5 * 2 * 2 * 2
    bad = [k for k, v in results.items() if not v]
    assert not bad, bad


def test_stable_ranks_fallback_warns_once_above_cliff():
    """Above _PAIRWISE_MAX_M stable_ranks routes through the double-argsort
    fallback — same bits, but it must say so (once per process) instead of
    silently re-paying the two XLA sorts (ROADMAP selection follow-up c)."""
    import warnings
    from repro.core import selection
    m = selection._PAIRWISE_MAX_M + 1
    keys = [jnp.arange(4, dtype=jnp.float32) * i for i in range(m)]
    orig = selection._RANK_FALLBACK_WARNED
    selection._RANK_FALLBACK_WARNED = False
    try:
        with pytest.warns(RuntimeWarning, match="double-argsort"):
            got = selection.stable_ranks(keys)
        # exact fallback semantics: argsort(argsort(...))
        ref = jnp.argsort(jnp.argsort(jnp.stack(keys), axis=0), axis=0)
        np.testing.assert_array_equal(np.asarray(jnp.stack(got)),
                                      np.asarray(ref))
        # one-time: a second call stays silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            selection.stable_ranks(keys)
    finally:
        selection._RANK_FALLBACK_WARNED = orig
