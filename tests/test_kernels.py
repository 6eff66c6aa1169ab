"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties
against the pure-jnp oracles (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental import pallas as pl

from repro.kernels import ops as kops
from repro.kernels.common import DEFAULT_TILE_D, LANE_TILE_MAX, lane_tile
from repro.kernels.krum.ref import pairwise_sq_dists_ref
from repro.kernels.phocas.kernel import phocas_counts_pallas, phocas_pallas
from repro.kernels.phocas.ref import phocas_ref
from repro.kernels.trmean.ref import trmean_ref

KEY = jax.random.PRNGKey(0)


def _valid_bs(m):
    return sorted(b for b in {1, 2, (m + 1) // 2 - 1}
                  if 1 <= b <= (m + 1) // 2 - 1)


def _assert_phocas_close(u, b, got, ref, atol=1e-4):
    """Phocas is discontinuous at distance ties (two values symmetric around
    the center): a 1-ulp center difference legitimately flips which value is
    dropped.  Mismatching coordinates must exhibit such a tie."""
    got, ref = np.asarray(got), np.asarray(ref)
    bad = np.where(np.abs(got - ref) > atol)[0]
    if bad.size == 0:
        return
    center = np.asarray(trmean_ref(u, b))
    for i in bad:
        d = np.sort(np.abs(np.asarray(u[:, i]) - center[i]))
        m = u.shape[0]
        boundary_gap = d[m - b] - d[m - b - 1]
        assert boundary_gap < 1e-4, (
            f"coord {i}: err {abs(got[i] - ref[i])} without a boundary tie "
            f"(gap {boundary_gap})")


@pytest.mark.parametrize("m", [4, 5, 20, 32, 64])
@pytest.mark.parametrize("d", [1, 100, 2048, 5000])
def test_trmean_kernel_sweep(m, d):
    u = 10 * jax.random.normal(jax.random.fold_in(KEY, m * d), (m, d))
    for b in _valid_bs(m):
        np.testing.assert_allclose(kops.trmean(u, b), trmean_ref(u, b),
                                   atol=1e-4, err_msg=f"b={b}")


def _ragged_width(m):
    """Three whole lane tiles of the shape-chosen width and one lane more,
    so the grid's last block holds a single real column."""
    return 3 * lane_tile(m, 2**31) + 1


@pytest.mark.parametrize("m", [3, 4, 5, 8, 20, 32])
@pytest.mark.parametrize("d", [1, 100, 2048, 3001, 5000, "ragged"])
def test_phocas_kernel_sweep(m, d):
    d = _ragged_width(m) if d == "ragged" else d
    u = 10 * jax.random.normal(jax.random.fold_in(KEY, m + d), (m, d))
    for b in _valid_bs(m):
        _assert_phocas_close(u, b, kops.phocas(u, b), phocas_ref(u, b))


@pytest.mark.parametrize("m,d", [(3, 3001), (4, "ragged"), (5, 3001),
                                 (8, "ragged"), (20, 3001)])
def test_phocas_shape_tile_matches_default_tile(m, d):
    """The lane tile chosen from the shape changes only the blocks: columns
    are independent, so aggregate and counts equal the 2,048-lane grid's
    bit for bit, extraction and network bodies alike."""
    d = _ragged_width(m) if d == "ragged" else d
    u = 10 * jax.random.normal(jax.random.fold_in(KEY, 7 * m + d), (m, d))
    u = u.at[0].set(200.0 * u[0])
    for b in _valid_bs(m):
        np.testing.assert_array_equal(
            phocas_pallas(u, b), phocas_pallas(u, b, tile_d=DEFAULT_TILE_D))
        for got, want in zip(phocas_counts_pallas(u, b),
                             phocas_counts_pallas(u, b,
                                                  tile_d=DEFAULT_TILE_D)):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,b", [(4, 1), (5, 2), (8, 3)])
def test_phocas_counts_ignore_lanes_past_d(m, b):
    """On the chip the ragged grid's last block holds stale values past d
    (interpret mode reads NaN there, which no body counts); the counts
    body must mask them whatever they hold.  Feed it a block padded with an
    attack-sized row and check the counts against the unpadded matrix."""
    from repro.core.aggregators import phocas_stats
    from repro.kernels.phocas.kernel import _phocas_counts_kernel
    from repro.kernels.trmean.kernel import COUNTS_LANES, use_network
    d, tile = 3001, DEFAULT_TILE_D
    u = jax.random.normal(jax.random.fold_in(KEY, m), (m, d))
    stale = jnp.zeros((m, (-d) % tile)).at[m - 1].set(1e6)
    nblocks = pl.cdiv(d, tile)
    _, counts = pl.pallas_call(
        functools.partial(_phocas_counts_kernel, b=b, m=m, d=d, tile_d=tile,
                          network=use_network(m, 3 * b)),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((m, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                   pl.BlockSpec((None, 1, COUNTS_LANES),
                                lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, nblocks * tile), jnp.float32),
                   jax.ShapeDtypeStruct((nblocks, 1, COUNTS_LANES),
                                        jnp.float32)],
        interpret=True,
    )(jnp.concatenate([u, stale], axis=1))
    np.testing.assert_array_equal(counts.sum(axis=(0, 1))[:m],
                                  phocas_stats(u, b)[1])


@pytest.mark.parametrize("d", [1, 100, 3001, 786_432, 268_447_744])
def test_lane_tile(d):
    """Lane-aligned, no wider than d needs or than ``LANE_TILE_MAX``, and
    never wider for more workers; the widest tile at the cells' m, one unit
    where a block of 128 workers would outgrow the VMEM budget."""
    cap = -(-d // 128) * 128
    prev = None
    for m in (1, 3, 4, 5, 8, 9, 20, 32, 64, 128):
        tile = lane_tile(m, d)
        assert tile % 128 == 0 and tile <= min(cap, LANE_TILE_MAX)
        assert tile % DEFAULT_TILE_D == 0 or tile == cap
        assert prev is None or tile <= prev
        prev = tile
    assert lane_tile(4, d) == min(cap, LANE_TILE_MAX)
    assert lane_tile(128, d) == min(cap, DEFAULT_TILE_D)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_kernel_dtypes(dtype):
    u = (10 * jax.random.normal(KEY, (16, 512))).astype(dtype)
    t = kops.trmean(u, 3)
    p = kops.phocas(u, 3)
    assert t.dtype == jnp.float32 and p.dtype == jnp.float32
    np.testing.assert_allclose(t, trmean_ref(u, 3), atol=1e-2)
    np.testing.assert_allclose(p, phocas_ref(u, 3), atol=1e-2)


@pytest.mark.parametrize("m,d", [(5, 100), (20, 2048), (32, 4096)])
def test_krum_gram_kernel(m, d):
    u = 10 * jax.random.normal(KEY, (m, d))
    ref = np.asarray(pairwise_sq_dists_ref(u))
    got = np.asarray(kops.pairwise_sq_dists(u))
    # Gram-trick cancellation scales with the squared norms
    np.testing.assert_allclose(got, ref, atol=1e-6 * ref.max() + 1e-3)


def test_krum_kernel_selects_same_vector():
    from repro.core import aggregators as agg
    u = jax.random.normal(KEY, (12, 777))
    u = u.at[3].set(40.0)
    np.testing.assert_allclose(kops.krum(u, 2), agg.krum(u, 2), atol=1e-5)
    np.testing.assert_allclose(kops.multikrum(u, 2), agg.multikrum(u, 2),
                               atol=1e-5)


def test_kernel_b_validation():
    with pytest.raises(ValueError):
        kops.trmean(jnp.ones((6, 8)), 3)
    with pytest.raises(ValueError):
        kops.phocas(jnp.ones((6, 8)), 4)


@given(st.integers(4, 33), st.integers(1, 300), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_trmean_kernel_property(m, d, seed):
    u = 5 * jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    b = (m - 1) // 3
    if b == 0:
        return
    np.testing.assert_allclose(kops.trmean(u, b), trmean_ref(u, b), atol=1e-4)


@given(st.integers(4, 25), st.integers(1, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_phocas_kernel_property(m, d, seed):
    u = 5 * jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    b = (m - 1) // 3
    if b == 0:
        return
    np.testing.assert_allclose(kops.phocas(u, b), phocas_ref(u, b), atol=1e-4)


@pytest.mark.parametrize("rule", ["trmean", "phocas"])
@pytest.mark.parametrize("counts", [False, True])
def test_kernel_exact_beside_attack_rows(rule, counts):
    """A trimmed N(0, 200²) attack row must not leak rounding into the
    honest 1e-4-scale average: the kernels sum the kept values, they never
    subtract the dropped ones from a total that passed through the attack."""
    u = 1e-4 * jax.random.normal(KEY, (6, 4096))
    u = u.at[0].set(200.0 * jax.random.normal(jax.random.fold_in(KEY, 1),
                                              (4096,)))
    ref = {"trmean": trmean_ref, "phocas": phocas_ref}[rule](u, 1)
    fn = getattr(kops, f"{rule}_with_counts" if counts else rule)
    got = fn(u, 1)[0] if counts else fn(u, 1)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-10)


def test_kernel_with_duplicate_values_ties():
    """Exact ties at the keep/drop boundary must match the stable oracle."""
    u = jnp.array([[0.0, 2.0], [2.0, 0.0], [1.0, 1.0], [1.0, 1.0]])
    np.testing.assert_allclose(kops.phocas(u, 1), phocas_ref(u, 1), atol=1e-6)
    u2 = jnp.tile(jnp.array([[1.0], [1.0], [1.0], [2.0], [0.0]]), (1, 200))
    np.testing.assert_allclose(kops.trmean(u2, 2), trmean_ref(u2, 2),
                               atol=1e-6)
