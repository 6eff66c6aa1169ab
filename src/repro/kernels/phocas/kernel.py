"""Pallas TPU kernels: fused Phocas aggregation.

Single VMEM pass per (m, tile) block: computes the b-trimmed mean (as in
the trmean kernel), then drops the b values farthest from it and averages
the remaining m-b — the trimmed mean never round-trips to HBM, which is the
fusion win over running trmean + a second distance/selection pass (2 fewer
HBM reads of the m×d matrix).

The lane tile comes from the shape (``kernels.common.lane_tile``): 4,096
lanes where the VMEM budget allows, which halves the grid steps of 2,048
while the body keeps its pace (wider blocks slow the body more than their
fewer steps save; DESIGN.md §8).  The grid is ragged, ``cdiv(d, tile)``
steps with no pad: the last block's lanes past d compute garbage that
Pallas never writes back, and the counts mask them.  Columns are
independent, so the results do not depend on the tile.

Two variants share the public entry points (DESIGN.md §8):

* **extraction** (small b): b masked max-extractions on |u - t| along the
  sublane axis, tie-broken on the HIGHEST worker index to match the
  stable-argsort oracle — O(3b) unrolled passes in total.
* **network** (large b): one Batcher sorting network along the sublane axis
  (``core/selection.py``); the kept (m-b)-nearest set is a contiguous
  window of the sorted order, so the selection reduces to b+1 statically
  sliced candidate windows over a prefix sum — O(log²m) stages + O(b)
  cheap window ops.

The ``*_counts`` kernel additionally emits per-worker drop counts (the
defense suspicion statistic) as a second per-grid-block output, with lanes
past d masked out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.selection import (nearest_window_sum, sorted_rows,
                                  stable_ranks, trimmed_mean_of_sorted)
from repro.kernels.common import (INTERPRET, extract_max, extract_min,
                                  lane_tile, masked_sum)
from repro.kernels.trmean.kernel import (COUNTS_LANES, _counts_row,
                                         _lane_mask, _rows_of, use_network)


def _trimmed_center(u, *, b: int, m: int):
    """Trimmed-mean center of an (m, tile) block."""
    valid = jnp.ones(u.shape, jnp.bool_)
    for _ in range(b):
        valid = extract_min(u, valid)
    for _ in range(b):
        valid = extract_max(u, valid)
    return masked_sum(u, valid) / (m - 2 * b)


def _drop_farthest(u, center, *, b: int):
    """(m, tile) mask of the b values farthest from ``center``.

    Ties break on the HIGHEST worker index, matching the stable-argsort
    oracle (which ranks lower indices as "nearer" on equal distance).
    """
    dist = jnp.abs(u - center[None])
    iota = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    dropped = jnp.zeros(u.shape, jnp.bool_)
    for _ in range(b):
        mx = jnp.max(dist, axis=0)
        idx = jnp.max(jnp.where(dist == mx[None], iota, -1), axis=0)
        onehot = iota == idx[None]
        dist = jnp.where(onehot, -jnp.inf, dist)
        dropped = dropped | onehot
    return dropped


def _phocas_kernel(u_ref, o_ref, *, b: int, m: int):
    u = u_ref[...].astype(jnp.float32)              # (m, tile)
    dropped = _drop_farthest(u, _trimmed_center(u, b=b, m=m), b=b)
    o_ref[...] = (masked_sum(u, ~dropped) / (m - b))[None]


def _phocas_kernel_net(u_ref, o_ref, *, b: int, m: int):
    u = u_ref[...].astype(jnp.float32)
    srows = sorted_rows(_rows_of(u, m))
    center = trimmed_mean_of_sorted(srows, b)
    total, _ = nearest_window_sum(srows, center, b)
    o_ref[...] = (total / (m - b))[None]


def _phocas_counts_kernel(u_ref, o_ref, c_ref, *, b: int, m: int, d: int,
                          tile_d: int, network: bool):
    u = u_ref[...].astype(jnp.float32)
    lane_ok = _lane_mask(u.shape, block=pl.program_id(0), tile_d=tile_d, d=d)
    if network:
        rows = _rows_of(u, m)
        srows = sorted_rows(rows)
        center = trimmed_mean_of_sorted(srows, b)
        total, _ = nearest_window_sum(srows, center, b)
        ranks = stable_ranks([jnp.abs(r - center) for r in rows])
        dropped = jnp.stack([r >= m - b for r in ranks])
    else:
        dropped = _drop_farthest(u, _trimmed_center(u, b=b, m=m), b=b)
        total = masked_sum(u, ~dropped)
    o_ref[...] = (total / (m - b))[None]
    c_ref[...] = _counts_row(dropped, lane_ok, m)


def _check_b(m: int, b: int) -> None:
    if not 0 <= b <= (m + 1) // 2 - 1:
        raise ValueError(f"b={b} out of range for m={m}")


@functools.partial(jax.jit, static_argnames=("b", "tile_d", "interpret"))
def phocas_pallas(u: jax.Array, b: int, *, tile_d: int | None = None,
                  interpret: bool = INTERPRET) -> jax.Array:
    """(m, d) f32 -> (d,) Phocas aggregation via pallas_call.

    ``tile_d=None`` takes the lane tile from the shape (``lane_tile``).
    """
    m, d = u.shape
    _check_b(m, b)
    tile = lane_tile(m, d) if tile_d is None else tile_d
    body = _phocas_kernel_net if use_network(m, 3 * b) else _phocas_kernel
    out = pl.pallas_call(
        functools.partial(body, b=b, m=m),
        grid=(pl.cdiv(d, tile),),
        in_specs=[pl.BlockSpec((m, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(u.astype(jnp.float32))
    return out[0]


@functools.partial(jax.jit, static_argnames=("b", "tile_d", "interpret"))
def phocas_counts_pallas(u: jax.Array, b: int, *, tile_d: int | None = None,
                         interpret: bool = INTERPRET):
    """(m, d) f32 -> ((d,) Phocas aggregate, (m,) per-worker drop counts).

    ``tile_d=None`` takes the lane tile from the shape (``lane_tile``).
    """
    m, d = u.shape
    _check_b(m, b)
    if m > COUNTS_LANES:
        raise ValueError(f"counts kernel packs m into {COUNTS_LANES} lanes; "
                         f"got m={m}")
    tile = lane_tile(m, d) if tile_d is None else tile_d
    nblocks = pl.cdiv(d, tile)
    agg, counts = pl.pallas_call(
        functools.partial(_phocas_counts_kernel, b=b, m=m, d=d,
                          tile_d=tile, network=use_network(m, 3 * b)),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((m, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                   pl.BlockSpec((None, 1, COUNTS_LANES),
                                lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((nblocks, 1, COUNTS_LANES),
                                        jnp.float32)],
        interpret=interpret,
    )(u.astype(jnp.float32))
    # Each block's count is an exact integer; summing them as integers keeps
    # the total exact past 2**24 and the same whatever the tile.
    counts = jnp.sum(counts[:, 0, :m].astype(jnp.int32), axis=0)
    return agg[0], counts.astype(jnp.float32)
