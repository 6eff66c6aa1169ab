"""Pallas TPU kernels: coordinate-wise b-trimmed mean over m workers.

TPU adaptation of the paper's selection-algorithm aggregation (§4.4).  Two
variants share the public entry points (DESIGN.md §8):

* **extraction** (small b): each (m, TILE_D) VMEM block removes its b
  smallest and b largest values per column by 2b unrolled masked min/max
  extractions along the sublane (worker) axis — O(b·m·TILE_D) vectorized
  work, everything VMEM resident, d on the 128-wide lane axis.
* **network** (large b): a Batcher odd-even merge sorting network along the
  sublane axis (``core/selection.py``), O(log²m) compare-exchange stages of
  O(m·TILE_D) vector work each, after which every trim window is a static
  row slice.  Chosen when the 2b extraction passes would cost more than the
  network's stages.

The ``*_counts`` kernels additionally emit per-worker drop counts — the
defense suspicion statistic — as a second output accumulated per grid
block, so ``emits_scores`` no longer forces the XLA fallback on TPU.
Padded lanes are masked out of the counts; tie handling matches the XLA
stable-rank masks exactly (stable extraction / ``stable_ranks``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.selection import (network_stages, sorted_rows, stable_ranks,
                                  trimmed_mean_of_sorted)
from repro.kernels.common import (DEFAULT_TILE_D, INTERPRET, extract_max,
                                  extract_max_stable, extract_min, masked_sum,
                                  pad_lanes)

# Score kernels pack per-worker counts into one 128-lane output row.
COUNTS_LANES = 128


def use_network(m: int, passes: int) -> bool:
    """Variant heuristic: an extraction pass and a network stage are both
    O(m·TILE_D) vector work, so the O(log²m)-stage network wins once the
    unrolled extraction loop needs more than that many passes.  Strictly
    more: at parity the extraction variant is preferred because its
    index-aware tie handling matches the stable-argsort oracle exactly,
    which the value-only network cannot (boundary distance ties)."""
    return passes > network_stages(m)


def _lane_mask(shape, *, block: int, tile_d: int, d: int):
    """(… , TILE_D) bool mask of lanes holding real (un-padded) columns."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return block * tile_d + lane < d


def _counts_row(dropped, lane_ok, m: int):
    """Sum an (m, TILE_D) drop mask over valid lanes into a (1, 128) row."""
    counts = jnp.sum(jnp.where(dropped & lane_ok, 1.0, 0.0), axis=1)
    return jnp.pad(counts, (0, COUNTS_LANES - m))[None]


def _trmean_kernel(u_ref, o_ref, *, b: int, m: int):
    u = u_ref[...].astype(jnp.float32)          # (m, TILE_D)
    valid = jnp.ones(u.shape, jnp.bool_)
    for _ in range(b):                          # b static & small: unrolled
        valid = extract_min(u, valid)
    for _ in range(b):
        valid = extract_max(u, valid)
    o_ref[...] = (masked_sum(u, valid) / (m - 2 * b))[None]


def _rows_of(u, m: int):
    """Worker rows with NaN mapped to +inf (the sort-last placement the
    XLA selection path uses, ``selection.worker_rows``)."""
    return [jnp.where(jnp.isnan(u[i]), jnp.inf, u[i]) for i in range(m)]


def _trmean_kernel_net(u_ref, o_ref, *, b: int, m: int):
    u = u_ref[...].astype(jnp.float32)
    srows = sorted_rows(_rows_of(u, m))
    o_ref[...] = trimmed_mean_of_sorted(srows, b)[None]


def _trmean_counts_kernel(u_ref, o_ref, c_ref, *, b: int, m: int, d: int,
                          tile_d: int, network: bool):
    u = u_ref[...].astype(jnp.float32)
    lane_ok = _lane_mask(u.shape, block=pl.program_id(0), tile_d=tile_d, d=d)
    if network:
        rows = _rows_of(u, m)
        srows = sorted_rows(rows)
        agg = trimmed_mean_of_sorted(srows, b)
        ranks = stable_ranks(rows)
        dropped = jnp.stack([(r < b) | (r >= m - b) for r in ranks])
    else:
        valid = jnp.ones(u.shape, jnp.bool_)
        for _ in range(b):
            valid = extract_min(u, valid)
        for _ in range(b):
            valid = extract_max_stable(u, valid)
        agg = masked_sum(u, valid) / (m - 2 * b)
        dropped = ~valid
    o_ref[...] = agg[None]
    c_ref[...] = _counts_row(dropped, lane_ok, m)


@functools.partial(jax.jit, static_argnames=("b", "tile_d", "interpret"))
def trmean_pallas(u: jax.Array, b: int, *, tile_d: int = DEFAULT_TILE_D,
                  interpret: bool = INTERPRET) -> jax.Array:
    """(m, d) f32 -> (d,) b-trimmed mean via pallas_call."""
    m = u.shape[0]
    if not 0 <= b <= (m + 1) // 2 - 1:
        raise ValueError(f"b={b} out of range for m={m}")
    u = u.astype(jnp.float32)
    u, d = pad_lanes(u, tile_d)
    dp = u.shape[1]
    body = _trmean_kernel_net if use_network(m, 2 * b) else _trmean_kernel
    out = pl.pallas_call(
        functools.partial(body, b=b, m=m),
        grid=(dp // tile_d,),
        in_specs=[pl.BlockSpec((m, tile_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(u)
    return out[0, :d]


@functools.partial(jax.jit, static_argnames=("b", "tile_d", "interpret"))
def trmean_counts_pallas(u: jax.Array, b: int, *,
                         tile_d: int = DEFAULT_TILE_D,
                         interpret: bool = INTERPRET):
    """(m, d) f32 -> ((d,) trimmed mean, (m,) per-worker drop counts)."""
    m = u.shape[0]
    if not 0 <= b <= (m + 1) // 2 - 1:
        raise ValueError(f"b={b} out of range for m={m}")
    if m > COUNTS_LANES:
        raise ValueError(f"counts kernel packs m into {COUNTS_LANES} lanes; "
                         f"got m={m}")
    u = u.astype(jnp.float32)
    u, d = pad_lanes(u, tile_d)
    dp = u.shape[1]
    nblocks = dp // tile_d
    agg, counts = pl.pallas_call(
        functools.partial(_trmean_counts_kernel, b=b, m=m, d=d,
                          tile_d=tile_d, network=use_network(m, 2 * b)),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((m, tile_d), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile_d), lambda i: (0, i)),
                   pl.BlockSpec((None, 1, COUNTS_LANES),
                                lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, dp), jnp.float32),
                   jax.ShapeDtypeStruct((nblocks, 1, COUNTS_LANES),
                                        jnp.float32)],
        interpret=interpret,
    )(u)
    return agg[0, :d], jnp.sum(counts, axis=(0, 1))[:m]
