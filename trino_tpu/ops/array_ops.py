"""Array/map kernels: per-row segment views over flattened child columns.

Reference role: ``core/trino-main/.../operator/scalar/ArraySubscriptOperator
.java``, ``ArrayPositionFunction``, ``MapSubscriptOperator``, and the unnest
operator's block traversal (``operator/unnest/UnnestOperator.java:41``). The
TPU formulation: a nested column is (lengths int32[n], flat children), so
every per-row operation becomes either

- a *gather* at ``offset[row] + k`` (subscript, element_at), or
- a *flat-parallel pass + monotonic segment reduction* (contains, position,
  array_min/max/sum, map key lookup): compute per-element predicates over the
  flat child, then reduce per row via cumsum-difference over the row's
  [offset, offset+length) range — no scatter, shapes static (SURVEY §7.1).

``rowid_of_flat`` is the inverse map (flat position -> parent row), a
searchsorted over the offsets — also the unnest expansion's core.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from trino_tpu.ops import scans


def offsets_from_lengths(lengths: jnp.ndarray) -> jnp.ndarray:
    """int32[n+1] exclusive prefix sum of per-row element counts."""
    lens = lengths.astype(jnp.int32)
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), scans.cumsum(lens, dtype=jnp.int32)]
    )

def rowid_of_flat(offsets: jnp.ndarray, flat_n: int) -> jnp.ndarray:
    """int32[flat_n]: parent row of each flat element position."""
    pos = jnp.arange(flat_n, dtype=jnp.int32)
    return (
        jnp.searchsorted(offsets, pos, side="right").astype(jnp.int32) - 1
    )

def segment_reduce_by_range(
    offsets: jnp.ndarray, flat_vals: jnp.ndarray
) -> jnp.ndarray:
    """Per-row sums of a flat int/float array via cumsum + boundary diff
    (exact for ints; rows = offsets.shape[0]-1). Integer inputs widen to
    int64 so narrow element dtypes can't wrap."""
    if jnp.issubdtype(flat_vals.dtype, jnp.integer) or flat_vals.dtype == jnp.bool_:
        flat_vals = flat_vals.astype(jnp.int64)
    c = scans.cumsum(flat_vals)
    c0 = jnp.concatenate([jnp.zeros((1,), c.dtype), c])
    return c0[offsets[1:]] - c0[offsets[:-1]]

def gather_at(
    offsets: jnp.ndarray,
    lengths: jnp.ndarray,
    flat_vals: jnp.ndarray,
    index1: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Element at 1-based ``index1`` per row -> (values, in_bounds). Negative
    indices count from the end (reference ArraySubscriptOperator supports
    them)."""
    lens = lengths.astype(jnp.int32)
    i1 = index1.astype(jnp.int32)
    eff = jnp.where(i1 < 0, lens + i1 + 1, i1)
    in_bounds = (eff >= 1) & (eff <= lens)
    flat_n = max(int(flat_vals.shape[0]), 1)
    idx = jnp.clip(offsets[:-1] + eff - 1, 0, flat_n - 1)
    safe_flat = flat_vals if flat_vals.shape[0] else jnp.zeros((1,), flat_vals.dtype)
    return safe_flat[idx], in_bounds

def first_match_index(
    offsets: jnp.ndarray,
    match: jnp.ndarray,
) -> jnp.ndarray:
    """int32[n]: 1-based index of the first True per row's range, 0 if none.
    ``match`` is flat-parallel. Implemented as a per-row min over masked
    positions using cumsum-of-count trick (monotonic, scatter-free)."""
    flat_n = match.shape[0]
    if flat_n == 0:
        return jnp.zeros((offsets.shape[0] - 1,), jnp.int32)
    pos = jnp.arange(flat_n, dtype=jnp.int32)
    # Position of first match at-or-after each flat slot, computed by a
    # reverse cummin; then per row read the value at the row's start.
    big = jnp.int32(flat_n)
    cand = jnp.where(match, pos, big)
    suffix_min = scans.cummin(cand, reverse=True)
    starts = offsets[:-1]
    first = suffix_min[jnp.clip(starts, 0, flat_n - 1)]
    lens = offsets[1:] - starts
    hit = (first < offsets[1:]) & (lens > 0)
    return jnp.where(hit, first - starts + 1, 0)

def count_in_ranges(
    offsets: jnp.ndarray, flags: jnp.ndarray
) -> jnp.ndarray:
    """int32[n]: per-row count of True flat flags."""
    c = scans.cumsum(flags.astype(jnp.int32))
    c0 = jnp.concatenate([jnp.zeros((1,), jnp.int32), c])
    return c0[offsets[1:]] - c0[offsets[:-1]]
