"""Shared helpers for the aggregation Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Lane-axis tile unit: a multiple of 128 (TPU lane width).  The trmean and
# krum kernels take (m, 2048) blocks; the phocas kernels take a multiple of
# it from ``lane_tile``.
DEFAULT_TILE_D = 2048

# Sublane (second-minor) axis of the f32 TPU vector-memory tile: min tile is
# (8, 128).  Layout constants that put a token/worker axis on the sublane
# dimension (e.g. serve/cache.DEFAULT_BLOCK_TOKENS) must be multiples of it.
SUBLANE = 8

# ``lane_tile``'s bounds.  On a v5e each grid step costs ~0.15 µs besides
# its block's work, and the phocas kernels' time stops falling past 4,096
# lanes (at m = 4 and 20): wider blocks run the body slower than their
# fewer steps save.  A tile may fill at most 12 MiB of the 16 MiB of scoped
# VMEM the TPU compiler gives a Pallas kernel by default.
LANE_TILE_MAX = 2 * DEFAULT_TILE_D
LANE_TILE_VMEM_BYTES = 12 * 2**20

# On CPU containers Pallas runs the kernel body in interpret mode.
INTERPRET = jax.default_backend() == "cpu"


def extract_min(u: jax.Array, valid: jax.Array) -> jax.Array:
    """Mark one occurrence of the per-column minimum over the still-valid
    entries as removed; returns the updated (m, t) valid mask.

    Kernels sum the survivors with :func:`masked_sum` at the end instead of
    subtracting each removed value from a running total: a total that
    passes through an adversarial row (N(0, 200²) noise, 1e20 payloads)
    keeps only a few ulp of that row's magnitude, which can exceed the
    honest values it is meant to average.
    """
    masked = jnp.where(valid, u, jnp.inf)
    idx = jnp.argmin(masked, axis=0)                  # (t,)
    onehot = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0) == idx[None]
    return valid & ~onehot


def extract_max(u: jax.Array, valid: jax.Array) -> jax.Array:
    """Mirror of :func:`extract_min` for the per-column maximum."""
    masked = jnp.where(valid, u, -jnp.inf)
    idx = jnp.argmax(masked, axis=0)
    onehot = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0) == idx[None]
    return valid & ~onehot


def extract_max_stable(u: jax.Array, valid: jax.Array) -> jax.Array:
    """:func:`extract_max` with ties broken on the HIGHEST worker index.

    ``argmax`` prefers the lowest index; the stable-argsort oracle ranks
    equal values by index ascending, so the *largest* (value, index) pair —
    the one a stable trim drops first — is the highest-indexed tie.  The
    aggregate can't tell (equal values sum equally) but the per-worker drop
    masks the score kernels emit can, so they must extract with this
    variant to match the XLA stable-rank counts bit-for-bit.
    """
    masked = jnp.where(valid, u, -jnp.inf)
    iota = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    mx = jnp.max(masked, axis=0)
    idx = jnp.max(jnp.where(masked == mx[None], iota, -1), axis=0)
    return valid & ~(iota == idx[None])


def masked_sum(u: jax.Array, keep: jax.Array) -> jax.Array:
    """Column sums of the kept entries of an (m, t) block."""
    return jnp.sum(jnp.where(keep, u, 0.0), axis=0)


def pad_lanes(u: jax.Array, tile: int):
    """Pad the lane (last) axis of (m, d) to a multiple of ``tile``."""
    d = u.shape[-1]
    pad = (-d) % tile
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad)))
    return u, d


def lane_tile(m: int, d: int) -> int:
    """Lane tile for an (m, d) float32 worker matrix: the widest multiple of
    ``DEFAULT_TILE_D`` up to ``LANE_TILE_MAX`` whose blocks fit
    ``LANE_TILE_VMEM_BYTES`` (at least one unit), capped at d rounded up
    to 128 lanes."""
    rows = -(-m // SUBLANE) * SUBLANE
    # Float32 words per lane: the double-buffered (m, tile) input block, the
    # double-buffered (1, tile) output row, and 16 (m, tile) temporaries of
    # the body (the v5e compiler keeps at most 13 live, in the phocas
    # sorting network; 4.1 in the extraction body).
    words = 2 * rows + 2 + 16 * rows
    fit = min(LANE_TILE_VMEM_BYTES // (4 * words), LANE_TILE_MAX)
    return min(max(fit // DEFAULT_TILE_D, 1) * DEFAULT_TILE_D,
               -(-d // 128) * 128)
