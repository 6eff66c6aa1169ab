"""Unit + property tests for the paper's aggregation rules.

The hypothesis-based property tests are optional: on minimal installs
without ``hypothesis`` they are skipped and the rest of the module still
collects and runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (aggregators as agg, bounds)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Oracles
# ---------------------------------------------------------------------------

def np_trmean(u, b):
    s = np.sort(u, axis=0)
    m = u.shape[0]
    return s[b:m - b].mean(0)


class TestTrmean:
    def test_matches_numpy(self):
        u = np.asarray(jax.random.normal(KEY, (20, 257)))
        for b in (0, 1, 4, 9):
            np.testing.assert_allclose(agg.trmean(jnp.asarray(u), b),
                                       np_trmean(u, b), atol=1e-5)

    def test_b0_is_mean(self):
        u = jax.random.normal(KEY, (7, 11))
        np.testing.assert_allclose(agg.trmean(u, 0), agg.mean(u), atol=1e-6)

    def test_b_range_validation(self):
        u = jnp.ones((6, 3))
        with pytest.raises(ValueError):
            agg.trmean(u, 3)          # ceil(6/2)-1 = 2 is max

    def test_max_b_is_median_odd_m(self):
        u = jax.random.normal(KEY, (9, 33))
        np.testing.assert_allclose(agg.trmean(u, 4), agg.median(u), atol=1e-6)


class TestPhocas:
    def test_keeps_m_minus_b_nearest(self):
        # hand example: m=4, b=1; trmean drops 2/0, center=(1+1)/2=1
        u = jnp.array([[0.0], [1.0], [1.0], [10.0]])
        # dists to 1: [1,0,0,9] -> drop 10 -> mean(0,1,1)=2/3
        np.testing.assert_allclose(agg.phocas(u, 1), [2.0 / 3], atol=1e-6)

    def test_b0_is_mean(self):
        u = jax.random.normal(KEY, (7, 11))
        np.testing.assert_allclose(agg.phocas(u, 0), agg.mean(u), atol=1e-6)

    def test_agrees_with_kernel_ref(self):
        from repro.kernels.phocas.ref import phocas_ref
        u = jax.random.normal(KEY, (20, 100))
        np.testing.assert_allclose(agg.phocas(u, 5), phocas_ref(u, 5),
                                   atol=1e-5)


class TestKrum:
    def test_selects_inlier(self):
        u = np.tile(np.linspace(0, 1, 64), (10, 1)).astype(np.float32)
        u += 0.01 * np.asarray(jax.random.normal(KEY, u.shape))
        u[0] = 100.0                            # outlier
        out = agg.krum(jnp.asarray(u), q=1)
        assert np.abs(np.asarray(out) - u[1:].mean(0)).max() < 1.0

    def test_output_is_a_candidate(self):
        u = jax.random.normal(KEY, (8, 13))
        out = np.asarray(agg.krum(u, q=2))
        assert any(np.allclose(out, np.asarray(u[i])) for i in range(8))

    def test_q_validation(self):
        with pytest.raises(ValueError):
            agg.krum(jnp.ones((5, 3)), q=3)

    def test_multikrum_mean_of_selected(self):
        u = jax.random.normal(KEY, (10, 7))
        out = agg.multikrum(u, q=2, k=10 - 2 - 2)
        assert out.shape == (7,)


class TestGeomedian:
    def test_resists_outlier(self):
        u = np.zeros((9, 5), np.float32)
        u[:8] = 1.0
        u[8] = 1e6
        out = np.asarray(agg.geomedian(jnp.asarray(u)))
        assert np.abs(out - 1.0).max() < 0.1


# ---------------------------------------------------------------------------
# Dimensional-resilience properties (hypothesis)
# ---------------------------------------------------------------------------

@st.composite
def byz_matrices(draw):
    m = draw(st.integers(4, 24))
    d = draw(st.integers(1, 40))
    q = draw(st.integers(0, (m - 1) // 2))     # 2q < m
    b = draw(st.integers(q, max(q, (m + 1) // 2 - 1)))
    seed = draw(st.integers(0, 2**31 - 1))
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    u = jax.random.normal(k1, (m, d))
    # generalized Byzantine: q arbitrary values PER DIMENSION corrupted
    scores = jax.random.uniform(k2, (m, d))
    ranks = jnp.argsort(jnp.argsort(scores, axis=0), axis=0)
    hit = ranks < q
    byz = 1e8 * jax.random.normal(k3, (m, d))
    return np.asarray(u), np.asarray(jnp.where(hit, byz, u)), q, b


@given(byz_matrices())
@settings(max_examples=60, deadline=None)
def test_trmean_dimensional_resilience(data):
    """Lemma 2 consequence: with b >= q corrupted per dimension, the trimmed
    mean stays within the correct values' range per coordinate."""
    u, tilde, q, b = data
    if b > (u.shape[0] + 1) // 2 - 1:
        return
    out = np.asarray(agg.trmean(jnp.asarray(tilde), b))
    lo, hi = u.min(0), u.max(0)
    assert (out >= lo - 1e-4).all() and (out <= hi + 1e-4).all()


@given(byz_matrices())
@settings(max_examples=60, deadline=None)
def test_phocas_dimensional_resilience(data):
    """Kept values are within max-correct-distance of the trimmed mean, so
    Phocas lands in [2lo - hi, 2hi - lo] per coordinate (Lemma 3)."""
    u, tilde, q, b = data
    if b > (u.shape[0] + 1) // 2 - 1:
        return
    out = np.asarray(agg.phocas(jnp.asarray(tilde), b))
    lo, hi = u.min(0), u.max(0)
    span = hi - lo
    assert (out >= lo - span - 1e-3).all() and (out <= hi + span + 1e-3).all()


@given(st.integers(5, 30), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_coordinate_wise_rules_permutation_invariant(m, seed):
    ku, kp = jax.random.split(jax.random.PRNGKey(seed))
    u = jax.random.normal(ku, (m, 8))
    perm = jax.random.permutation(kp, m)
    b = (m - 1) // 3
    for rule in (lambda x: agg.trmean(x, b), lambda x: agg.phocas(x, b),
                 agg.median, agg.mean):
        np.testing.assert_allclose(rule(u), rule(u[perm]), atol=1e-5)


# ---------------------------------------------------------------------------
# Negative results (Propositions 1-3)
# ---------------------------------------------------------------------------

def test_proposition1_mean_not_resilient():
    """One corrupted value per dimension drives the mean anywhere."""
    m, d = 10, 4
    u = jnp.ones((m, d))
    target = -1e6
    tilde = u.at[0].set(m * target - (m - 1))
    out = agg.mean(tilde)
    assert float(jnp.max(out)) < -1e5       # arbitrarily bad
    # while trmean with b>=1 is unaffected:
    np.testing.assert_allclose(agg.trmean(tilde, 1), np.ones(d), atol=1e-5)


def test_proposition2_selection_rules_fail_dimensionally():
    """Prop 2 counterexample: corrupt dimension i of vector i — any rule that
    outputs one of its inputs returns a corrupted coordinate."""
    m = 6
    u = jnp.ones((m, m))
    tilde = u + jnp.diag(jnp.full((m,), -1e9))
    out = np.asarray(agg.krum(tilde, q=1))
    assert out.min() < -1e8                 # Krum output contains a Byz value
    out2 = np.asarray(agg.trmean(tilde, 1))
    np.testing.assert_allclose(out2, np.ones(m), atol=1e-4)  # Trmean fine


# ---------------------------------------------------------------------------
# Variance bounds (Theorems 1-2), Monte-Carlo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule,delta_fn", [
    ("trmean", bounds.delta_trmean), ("phocas", bounds.delta_phocas)])
def test_variance_bound_montecarlo(rule, delta_fn):
    m, d, q, b, trials = 20, 50, 3, 6, 200
    V = float(d)                             # per-coordinate unit variance
    delta = delta_fn(m, q, b, V)
    fn = agg.get_aggregator(rule, b=b)
    key = jax.random.PRNGKey(42)
    errs = []
    for t in range(trials):
        k1, k2, _ = jax.random.split(jax.random.fold_in(key, t), 3)
        u = jax.random.normal(k1, (m, d))    # g = 0
        scores = jax.random.uniform(k2, (m, d))
        ranks = jnp.argsort(jnp.argsort(scores, axis=0), axis=0)
        tilde = jnp.where(ranks < q, 1e6, u)  # adversarial per-dim corruption
        errs.append(float(jnp.sum(fn(tilde) ** 2)))
    assert np.mean(errs) <= delta, (np.mean(errs), delta)


def test_bounds_monotonicity():
    V = 1.0
    assert bounds.delta_trmean(40, 2, 4, V) < bounds.delta_trmean(20, 2, 4, V)
    assert bounds.delta_trmean(20, 2, 4, V) < bounds.delta_trmean(20, 2, 8, V)
    assert bounds.delta_phocas(20, 2, 4, V) > bounds.delta_trmean(20, 2, 4, V)
    with pytest.raises(ValueError):
        bounds.delta_trmean(10, 5, 5, V)     # 2q < m violated


# ---------------------------------------------------------------------------
# One-pass gated defense overrides (fused_gate) for the vector-wise rules
# ---------------------------------------------------------------------------

def _gated_setup(m=10, d=33, seed=7):
    from repro.core import registry
    ku, _ = jax.random.split(jax.random.PRNGKey(seed))
    u = jax.random.normal(ku, (m, d))
    u = u.at[0].set(50.0)                    # far outlier, soon ejected
    active = jnp.ones((m,)).at[0].set(0.0)
    return registry, u, active


@pytest.mark.parametrize("rule", ("krum", "multikrum"))
def test_krum_family_gated_override_matches_composed(rule):
    """The incremental gated-Gram one-pass hook is drop-in for the
    registry's two-pass composition (same selection, same scores)."""
    from repro.core.registry import AggregatorRule
    registry, u, active = _gated_setup()
    r = registry.make_rule(rule, registry.RuleParams(q=2, backend="xla"))
    got_agg, got_sc = r.reduce_gated_with_scores(u, active)
    ref_agg, ref_sc = AggregatorRule.reduce_sharded_gated_with_scores(
        r, u, active, ())
    np.testing.assert_allclose(np.asarray(got_sc), np.asarray(ref_sc),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_agg), np.asarray(ref_agg),
                               atol=1e-4)


@pytest.mark.parametrize("rule", ("krum", "multikrum", "geomedian"))
def test_vector_rule_gated_none_equals_ungated(rule):
    registry, u, _ = _gated_setup()
    r = registry.make_rule(rule, registry.RuleParams(q=2, backend="xla"))
    got_agg, got_sc = r.reduce_gated_with_scores(u, None)
    ref_agg, ref_sc = r.reduce_sharded_with_scores(u, ())
    np.testing.assert_allclose(np.asarray(got_agg), np.asarray(ref_agg),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_sc), np.asarray(ref_sc),
                               atol=1e-6)


def test_geomedian_gated_override_center_matches_composed():
    """One Weiszfeld run on the gated matrix == the composed path's gated
    aggregate; scores still observe the raw submissions (the ejected far
    row stays maximally suspicious — flap prevention)."""
    registry, u, active = _gated_setup()
    r = registry.make_rule("geomedian", registry.RuleParams(backend="xla"))
    got_z, got_sc = r.reduce_gated_with_scores(u, active)
    from repro.core.selection import gate_matrix
    ref_z = r.reduce_sharded(gate_matrix(u, active), ())
    np.testing.assert_allclose(np.asarray(got_z), np.asarray(ref_z),
                               atol=1e-5)
    sc = np.asarray(got_sc)
    assert sc.shape == (u.shape[0],) and np.isfinite(sc).all()
    assert (sc >= 0.0).all() and (sc <= 1.0).all()
    assert sc[0] == sc.max() and sc[0] > 0.5   # raw outlier still blamed


def test_fused_gate_metadata_matches_overrides():
    """fused_gate is the routing metadata CONTRACT007 enforces: True
    exactly for rules whose gated hook is a genuine override."""
    from repro.core.registry import AggregatorRule
    from repro.core import registry
    expected = set()
    for name in registry.available_rules():
        cls = registry.get_rule(name)
        own = cls.reduce_sharded_gated_with_scores \
            is not AggregatorRule.reduce_sharded_gated_with_scores
        assert cls.fused_gate == own, name
        if own:
            expected.add(name)
    assert set(registry.fused_gate_rules()) == expected
    assert {"krum", "multikrum", "geomedian"} <= expected
