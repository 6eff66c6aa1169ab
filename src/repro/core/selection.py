"""Shared per-coordinate selection pass for the coordinate-wise rules.

Every coordinate-wise rule in the stack (median / trmean / phocas / mediam,
their ``*_stats`` score variants, and the defense gate's median row) is a
composition of the same two primitives over the worker axis:

* **order statistics** of the m values at each coordinate (centers, trim
  windows, the gate's median row), and
* **stable selection ranks** (which workers the trim/selection step drops —
  the defense suspicion signal).

Before this module each rule paid for those separately with full
``jnp.sort`` + double-``argsort`` rank tricks — up to three O(m log m)
XLA sorts per rule per step, and XLA's CPU sort lowers to a scalar
comparator loop that is dramatically slower than the fused vector code the
same backend emits for min/max/where.  This module computes each primitive
once, in a form XLA fuses well, and every rule reads the shared result:

* :func:`sorted_rows` — a Batcher odd-even merge sorting **network** over a
  Python list of ``(d,)`` rows.  Compare-exchanges are ``minimum``/
  ``maximum`` pairs on row vectors, so the whole network fuses into wide
  vector code with no (m, d) temporaries and no comparator calls
  (~100x faster than ``jnp.sort`` on the CPU backend at m=8).  Falls back
  to one ``jnp.sort`` above ``_NETWORK_MAX_M`` where O(m log^2 m) network
  traffic would lose.
* :func:`stable_ranks` — exact stable-argsort ranks via O(m^2) pairwise
  lexicographic ``(key, worker index)`` comparisons, again pure fused
  vector ops.  Reproduces ``argsort(argsort(key))`` bit-for-bit, including
  duplicate handling.  Falls back to the double-argsort above
  ``_PAIRWISE_MAX_M``.
* :func:`trim_family` — the one driver behind trmean/phocas/mediam (and
  their fused defense paths): one sorted block feeds the center, the
  selection window, the raw-submission drop ranks, the gate's median row,
  and the gated re-aggregation.

The Pallas kernels reuse :func:`sorted_rows` / :func:`stable_ranks` inside
their kernel bodies for the large-b variants (DESIGN.md §8).
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# Above these worker counts the O(m^2) pairwise ranks / O(m log^2 m) network
# lose to XLA's O(m log m) sort despite its worse constant; both bounds are
# far beyond the paper's experiments (m <= 100).
_NETWORK_MAX_M = 128
_PAIRWISE_MAX_M = 64

# One-time-per-process warning guard for the stable_ranks fallback cliff
# (ROADMAP selection follow-up c): above _PAIRWISE_MAX_M the exact pairwise
# path would cost O(m^2) compares, so we route through the documented
# double-argsort fallback — semantically identical, but it re-pays the two
# XLA sorts the fused path exists to avoid.  Warn once so large-fleet users
# know the perf model changed instead of silently losing the speedup.
_RANK_FALLBACK_WARNED = False


def _warn_rank_fallback(m: int) -> None:
    global _RANK_FALLBACK_WARNED
    if _RANK_FALLBACK_WARNED:
        return
    _RANK_FALLBACK_WARNED = True
    warnings.warn(
        f"stable_ranks: m={m} exceeds _PAIRWISE_MAX_M={_PAIRWISE_MAX_M}; "
        "falling back to the double-argsort rank path (two O(m log m) XLA "
        "sorts per call — bit-identical results, but the fused O(m^2) "
        "pairwise speedup no longer applies at this fleet size). "
        "This warning is emitted once per process.",
        RuntimeWarning, stacklevel=3)


def _as_f32(u: jax.Array) -> jax.Array:
    return u.astype(jnp.float32) if u.dtype != jnp.float32 else u


def worker_rows(u: jax.Array) -> List[jax.Array]:
    """Split an (m, *shape) block into a list of m f32 rows.

    The list-of-rows form is what lets XLA fuse the selection math: every
    downstream op is elementwise over ``shape``-shaped vectors instead of
    materializing (m, *shape) temporaries.

    NaN submissions (the cheapest Byzantine payload) are mapped to +inf:
    ``jnp.sort`` placed NaN past every real value so the old paths trimmed
    it away, but the network's min/max compare-exchanges and the pairwise
    rank compares would both let NaN poison every coordinate instead of
    being selected against.  +inf reproduces the sort-last placement for
    the trim windows, the distance ranks, AND the suspicion scores.
    """
    uf = _as_f32(u)
    return [jnp.where(jnp.isnan(uf[i]), jnp.inf, uf[i])
            for i in range(u.shape[0])]


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@functools.lru_cache(maxsize=None)
def batcher_pairs(n: int) -> Tuple[Tuple[int, int], ...]:
    """Batcher odd-even mergesort compare-exchange schedule for n = 2^k."""
    if n & (n - 1):
        raise ValueError(f"batcher_pairs needs a power of two, got {n}")
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(k):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return tuple(pairs)


def network_stages(m: int) -> int:
    """Stage count of the Batcher network on next_pow2(m) inputs —
    O(log^2 m), the unit the kernels' variant heuristic compares against
    masked-extraction pass counts."""
    k = max(1, next_pow2(m).bit_length() - 1)
    return k * (k + 1) // 2


def sorted_rows(rows: Sequence[jax.Array]) -> List[jax.Array]:
    """Sort m same-shaped rows coordinate-wise ascending; returns m rows.

    Values only (worker identity is not tracked — use :func:`stable_ranks`
    when the selection mask must name workers).  Non-power-of-two m is
    padded with +inf rows that sort past every real value.
    """
    m = len(rows)
    if m <= 1:
        return list(rows)
    if m > _NETWORK_MAX_M:
        s = jnp.sort(jnp.stack(rows), axis=0)
        return [s[i] for i in range(m)]
    mp = next_pow2(m)
    work = list(rows)
    if mp != m:
        inf = jnp.full_like(rows[0], jnp.inf)
        work += [inf] * (mp - m)
    for a, b in batcher_pairs(mp):
        lo = jnp.minimum(work[a], work[b])
        hi = jnp.maximum(work[a], work[b])
        work[a], work[b] = lo, hi
    return work[:m]


def stable_ranks(keys: Sequence[jax.Array]) -> List[jax.Array]:
    """Exact stable-argsort ranks of m rows: ``ranks[i]`` counts workers j
    with ``(key_j, j) < (key_i, i)`` lexicographically — identical to
    ``argsort(argsort(stack(keys), axis=0), axis=0)[i]`` for every input,
    duplicates included, but as O(m^2) fused vector compares instead of two
    XLA sorts."""
    m = len(keys)
    if m > _PAIRWISE_MAX_M:
        _warn_rank_fallback(m)
        stacked = jnp.stack(keys)
        r = jnp.argsort(jnp.argsort(stacked, axis=0), axis=0)
        return [r[i] for i in range(m)]
    ranks = []
    for i in range(m):
        r = jnp.zeros_like(keys[i], dtype=jnp.int32)
        for j in range(m):
            if j == i:
                continue
            lt = keys[j] < keys[i]
            if j < i:  # stable: equal keys rank by worker index
                lt = lt | (keys[j] == keys[i])
            r = r + lt.astype(jnp.int32)
        ranks.append(r)
    return ranks


def median_of_sorted(srows: Sequence[jax.Array]) -> jax.Array:
    """Coordinate-wise median from an already-sorted row list."""
    m = len(srows)
    if m % 2:
        return srows[m // 2]
    return 0.5 * (srows[m // 2 - 1] + srows[m // 2])


def trimmed_mean_of_sorted(srows: Sequence[jax.Array], b: int) -> jax.Array:
    """b-trimmed mean (Definition 7) from an already-sorted row list."""
    m = len(srows)
    kept = srows[b:m - b]
    return sum(kept[1:], start=kept[0]) / len(kept) if len(kept) > 1 \
        else kept[0]


def nearest_window_sum(srows: Sequence[jax.Array], center: jax.Array,
                       drop: int) -> Tuple[jax.Array, jax.Array]:
    """Sum of the (m - drop) values nearest ``center`` per coordinate.

    The nearest set is always a contiguous window of the sorted order, so
    only drop+1 candidate windows exist; each is scored by its worst
    distance and the best window's sum is read off a running prefix sum.
    Ties between candidate windows (values symmetric around the center)
    resolve to the leftmost window — the same boundary-tie class the
    Pallas kernels document vs the stable-argsort oracle.

    Returns ``(window_sum, window_start)``.
    """
    m = len(srows)
    k = m - drop
    if drop == 0:
        return sum(srows[1:], start=srows[0]), \
            jnp.zeros_like(center, dtype=jnp.int32)
    widths = [jnp.maximum(center - srows[j], srows[j + k - 1] - center)
              for j in range(drop + 1)]
    best, bestj = widths[0], jnp.zeros_like(center, dtype=jnp.int32)
    for j in range(1, drop + 1):
        better = widths[j] < best
        best = jnp.where(better, widths[j], best)
        bestj = jnp.where(better, j, bestj)
    # Masked accumulation over the sorted rows, NOT a prefix-sum
    # difference: a prefix that passes through an adversarial 1e20 row
    # would cancel catastrophically in f32 and erase the kept values.
    total = jnp.zeros_like(center)
    for p in range(m):
        keep = (bestj <= p) & (p < bestj + k)
        total = total + jnp.where(keep, srows[p], 0.0)
    return total, bestj


def ncoords_of(u: jax.Array) -> jax.Array:
    """Static count of coordinates per worker (trailing-shape product)."""
    return jnp.float32(math.prod(u.shape[1:]) or 1)


def _count_per_worker(drop_masks: Sequence[jax.Array]) -> jax.Array:
    return jnp.stack([jnp.sum(d, dtype=jnp.float32) for d in drop_masks])


def validate_b(m: int, b: int) -> None:
    if not 0 <= b <= (m + 1) // 2 - 1:
        raise ValueError(f"b={b} out of range [0, ceil(m/2)-1] for m={m}")


# Center of each trim-family rule, as a function of the sorted block.
_CENTERS = {
    "trmean": trimmed_mean_of_sorted,          # Definition 7 center
    "phocas": trimmed_mean_of_sorted,          # Definition 8 center
    "mediam": lambda srows, b: median_of_sorted(srows),   # Xie et al. 2018
}


def trim_family(u: jax.Array, b: int, kind: str, *,
                active: Optional[jax.Array] = None,
                with_scores: bool = False):
    """One shared selection pass behind trmean / phocas / mediam.

    Computes, from a single sorted block of the raw (m, *shape) matrix:
    the rule's center, its aggregate, optionally the per-worker drop counts
    of the RAW submissions (the defense score statistic), and — when
    ``active`` is given — the reputation-gated aggregate, whose gate median
    row is free once the raw block is sorted (DESIGN.md §8).

    Returns ``(agg, drop_counts, ncoords)``; ``drop_counts`` is None unless
    ``with_scores``.  Score semantics are unchanged from the pre-fusion
    stack: counts observe the raw matrix even when the aggregate is gated.
    """
    if kind not in _CENTERS:
        raise ValueError(f"unknown trim-family rule kind {kind!r}")
    m = u.shape[0]
    validate_b(m, b)
    rows = worker_rows(u)
    counts = None
    if b == 0:
        # Every trim-family rule degenerates to the plain mean — but the
        # reputation gate still applies (an ejected row must not re-enter
        # the average).
        if with_scores:
            counts = jnp.zeros((m,), jnp.float32)
        if active is not None:
            med = median_of_sorted(sorted_rows(rows))
            rows = [jnp.where(active[i] > 0, rows[i], med)
                    for i in range(m)]
        agg = sum(rows[1:], start=rows[0]) / m
        return agg, counts, ncoords_of(u)

    srows = sorted_rows(rows)
    center = _CENTERS[kind](srows, b)

    if with_scores:
        if kind == "trmean":
            ranks = stable_ranks(rows)
            dropped = [(r < b) | (r >= m - b) for r in ranks]
        else:
            dists = [jnp.abs(r - center) for r in rows]
            ranks = stable_ranks(dists)
            dropped = [r >= m - b for r in ranks]
        counts = _count_per_worker(dropped)

    if active is not None:
        # Reputation gate: ejected rows -> the raw matrix's median row
        # (read straight off the sorted block), then re-sort and re-center.
        # The raw aggregate is never materialized — this is the fusion that
        # keeps a defense-enabled step from running the rule twice.
        med = median_of_sorted(srows)
        rows = [jnp.where(active[i] > 0, rows[i], med) for i in range(m)]
        srows = sorted_rows(rows)
        center = _CENTERS[kind](srows, b)

    if kind == "trmean":
        agg = trimmed_mean_of_sorted(srows, b)
    else:
        total, _ = nearest_window_sum(srows, center, b)
        agg = total / (m - b)
    return agg, counts, ncoords_of(u)


def matrix_median(u: jax.Array) -> jax.Array:
    """Coordinate-wise median of an (m, *shape) block via the network."""
    return median_of_sorted(sorted_rows(worker_rows(u)))


def gate_matrix(mat: jax.Array, active: jax.Array) -> jax.Array:
    """Replace ejected workers' rows before an aggregation rule runs.

    ``active`` is the (m,) 0/1 mask from the reputation state
    (``repro.defense.reputation``).  Ejected rows are replaced with the
    coordinate-wise median of the matrix — a dimensional-robust proxy that
    is exact slice-locally in both collective layouts, so the gate composes
    with ``shard_map`` without extra collectives.  The rule still sees m
    rows (its b/q parameters keep their meaning) but an ejected worker's
    values can no longer move any order statistic beyond the median.

    A *concrete* all-ones mask (no ejections, outside jit) short-circuits
    to the input — the gate costs nothing until a worker is ejected.
    """
    if not isinstance(active, jax.core.Tracer):
        import numpy as np
        if bool(np.all(np.asarray(active) > 0)):
            return mat
    with jax.named_scope("gate"):
        med = matrix_median(mat)
        keep = active.reshape((mat.shape[0],) + (1,) * (mat.ndim - 1))
        return jnp.where(keep > 0, mat, med[None].astype(mat.dtype))
