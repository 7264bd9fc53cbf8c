"""HyperLogLog approx_distinct — scatter-free grouped sketch estimation.

Reference: ``operator/aggregation/ApproximateCountDistinctAggregation`` over
airlift's HyperLogLog (m = 2048 registers, ~2.3% standard error — the
reference's default). TPU redesign: instead of materializing per-group
register arrays (a [groups, 2048] scatter-max), rows regroup by
(group, bucket) with the same sorted machinery the engine uses everywhere:

1. per row: h = mix64(x); bucket = low 11 bits; rho = 1 + clz of the
   remaining 53 bits (capped);
2. group rows by (outer group id, bucket) — one fused sort;
3. register value = max(rho) per (group, bucket) pair (segmented max);
4. per outer group, two monotonic segment sums over the pair rows give
   sum(2^-register) and the count of PRESENT buckets; absent buckets
   contribute 2^0 each, so the harmonic denominator completes as
   sum_present + (m - present);
5. alpha_m * m^2 / denominator, with the standard small-range linear
   counting correction (E <= 2.5m -> m * ln(m / V)).

No scatter appears; the cost profile is one extra (gid, bucket) sort —
the sketch semantics of the reference at sorted-segment prices.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.ops import ranks as ranks_ops
from trino_tpu.ops import scans

from trino_tpu.ops import segments as seg

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]

LOG2_M = 11
M = 1 << LOG2_M  # 2048 registers -> ~1.04/sqrt(m) = 2.3% standard error
_ALPHA = 0.7213 / (1.0 + 1.079 / M)  # alpha_m for m >= 128

# numpy scalars to stay concrete if first imported under a trace
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = (x ^ (x >> 30)) * _M1
    x = (x ^ (x >> 27)) * _M2
    return x ^ (x >> 31)


def _rho(w: jnp.ndarray, width: int) -> jnp.ndarray:
    """1 + count of leading zeros of ``w`` within ``width`` bits (capped at
    width + 1 when w == 0) — the HLL register value."""
    # clz via bit-length: floor(log2(w)) through float conversion is unsafe
    # for 53-bit ints; use a shift cascade (6 steps for 64-bit)
    n = jnp.zeros_like(w, dtype=jnp.int32)
    x = w
    for shift in (32, 16, 8, 4, 2, 1):
        mask = x >= (jnp.uint64(1) << shift)
        n = jnp.where(mask, n + shift, n)
        x = jnp.where(mask, x >> shift, x)
    bit_length = jnp.where(w == 0, 0, n + 1)
    return (width - bit_length + 1).astype(jnp.int32)


def approx_distinct(layout: seg.GroupLayout, arg: Lowered, sel) -> Tuple[jnp.ndarray, None]:
    """Per-group HLL estimate (int64). ``arg``/``sel`` are in ORIGINAL row
    order (this re-groups, like agg_count_distinct)."""
    from trino_tpu.ops import groupby as gb

    vals, valid = arg
    n = vals.shape[0]
    live = sel if sel is not None else jnp.ones((n,), bool)
    if valid is not None:
        live = live & valid
    if jnp.issubdtype(vals.dtype, jnp.floating):
        # BIT-cast floats (a value cast to int64 would collapse distinct
        # fractional values onto the same integer)
        f64 = vals.astype(jnp.float64)
        key_bits = jax.lax.bitcast_convert_type(f64, jnp.int64)
    else:
        key_bits = vals.astype(jnp.int64)
    h = _mix64(key_bits.astype(jnp.uint64))
    bucket = (h & jnp.uint64(M - 1)).astype(jnp.int32)
    w = h >> LOG2_M
    rho = _rho(w, 64 - LOG2_M)

    outer = layout.gids_orig()
    order, gid_sorted, num_pairs, (rho_l,) = gb.group_plan(
        [(outer, None), (bucket, None)], live, payloads=[rho]
    )
    pairs = seg.sorted_layout(order, gid_sorted, num_pairs)
    # two DIFFERENT prefixes: live ROWS (dead rows sort last) vs live pair
    # SLOTS (distinct (group, bucket) pairs)
    n_live = jnp.sum(live).astype(jnp.int32)
    row_live = jnp.arange(n, dtype=jnp.int32) < n_live
    slot_live = jnp.arange(n, dtype=jnp.int32) < num_pairs.astype(jnp.int32)
    register = seg.seg_minmax(pairs, rho_l, row_live, is_min=False)
    register = jnp.where(slot_live, register, 0)
    # outer group id per pair slot (dead pairs past every real group)
    outer_of_pair = jnp.where(
        slot_live,
        outer[jnp.clip(pairs.rep, 0, n - 1)].astype(jnp.int32),
        jnp.int32(layout.capacity),
    )
    inv_pow = jnp.where(slot_live, jnp.exp2(-register.astype(jnp.float64)), 0.0)
    sum_present = seg.monotonic_segment_sum(inv_pow, outer_of_pair, layout.capacity)
    present = seg.monotonic_segment_sum(
        slot_live.astype(jnp.int64), outer_of_pair, layout.capacity
    )
    denom = sum_present + (M - present).astype(jnp.float64)
    raw = _ALPHA * M * M / jnp.maximum(denom, 1e-9)
    v_zero = (M - present).astype(jnp.float64)
    linear = M * jnp.log(jnp.maximum(M / jnp.maximum(v_zero, 1e-9), 1.0))
    est = jnp.where((raw <= 2.5 * M) & (v_zero > 0), linear, raw)
    out = jnp.round(est).astype(jnp.int64)
    return jnp.where(present > 0, out, 0), None


def approx_percentile(
    layout: seg.GroupLayout,
    vals_l: jnp.ndarray,
    m_l,
    p: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-group percentile by nearest rank over the grouped sort.

    Design note vs the reference (``ApproximateDoublePercentileAggregations``
    over tdigest): a streaming sketch exists to bound memory on
    row-at-a-time execution; under sorted-segment execution the engine can
    sort (group, value) outright — one fused lax.sort — and read the exact
    percentile, which is both cheaper here and strictly more accurate.
    ``vals_l``/``m_l`` are in layout space (group_structure payloads).
    """
    if jnp.issubdtype(vals_l.dtype, jnp.floating):
        sentinel = jnp.asarray(jnp.inf, vals_l.dtype)
    else:
        sentinel = jnp.asarray(jnp.iinfo(vals_l.dtype).max, vals_l.dtype)
    x = vals_l if m_l is None else jnp.where(m_l, vals_l, sentinel)
    if layout.is_direct:
        # direct layouts are tiny-capacity: sort by (gid, value) too
        gids = layout.gids
        _, x_by_group = ranks_ops.stable_sort((gids, x), 2)
        starts, cnt = _direct_ranges(layout, m_l)
    else:
        _, x_by_group = ranks_ops.stable_sort((layout.gid_sorted, x), 2)
        starts = layout.starts
        cnt = seg.seg_count(layout, m_l)
    nn = x_by_group.shape[0]
    rank = jnp.clip(
        jnp.ceil(p * cnt.astype(jnp.float64)).astype(jnp.int64) - 1, 0, None
    )
    pos = jnp.clip(starts.astype(jnp.int64) + rank, 0, nn - 1)
    out = x_by_group[pos]
    return out, cnt > 0


# Mergeable quantile-summary width: 64 rank intervals -> worst-case rank
# error ~1/(2*64) < 1% after merging (reference role: the mergeable
# t-digest/qdigest of ApproximatePercentileAggregations — here an
# equal-rank sample summary, the natural fixed-shape formulation).
QUANTILE_SAMPLES = 65


def percentile_states(layout: seg.GroupLayout, vals_l, m_l):
    """Partial approx_percentile state: per group, QUANTILE_SAMPLES local
    values at evenly spaced ranks + the live count. All static shapes: one
    (gid, value) sort + one [capacity, SAMPLES] bounded gather."""
    if jnp.issubdtype(vals_l.dtype, jnp.floating):
        sentinel = jnp.asarray(jnp.inf, vals_l.dtype)
    else:
        sentinel = jnp.asarray(jnp.iinfo(vals_l.dtype).max, vals_l.dtype)
    x = vals_l if m_l is None else jnp.where(m_l, vals_l, sentinel)
    if layout.is_direct:
        _, x_by_group = ranks_ops.stable_sort((layout.gids, x), 2)
        starts, cnt = _direct_ranges(layout, m_l)
    else:
        _, x_by_group = ranks_ops.stable_sort((layout.gid_sorted, x), 2)
        starts = layout.starts
        cnt = seg.seg_count(layout, m_l)
    nn = x_by_group.shape[0]
    j = jnp.arange(QUANTILE_SAMPLES, dtype=jnp.float64) / (QUANTILE_SAMPLES - 1)
    ranks = jnp.round(
        j[None, :] * jnp.maximum(cnt - 1, 0).astype(jnp.float64)[:, None]
    ).astype(jnp.int64)
    pos = jnp.clip(starts.astype(jnp.int64)[:, None] + ranks, 0, max(nn - 1, 0))
    samples = x_by_group[pos]  # [capacity, SAMPLES]
    live = cnt > 0
    out = [(samples[:, k], live) for k in range(QUANTILE_SAMPLES)]
    out.append((cnt, None))
    return out


def percentile_merge(layout: seg.GroupLayout, samples, cnt_state, p: float):
    """Final approx_percentile: weighted quantile over every shard's
    summary. Each partial row expands to its SAMPLES values weighted
    count/SAMPLES; one (gid, value) sort + a cumulative-weight rank pick
    per group slot. ``samples``/``cnt_state`` are layout-space payloads of
    the final grouping (small arrays: shards x groups rows)."""
    S = len(samples)
    cnt_l, _ = cnt_state
    n_l = cnt_l.shape[0]
    vals = jnp.stack([v for v, _ in samples], axis=1)  # [n_l, S]
    valid0 = samples[0][1]
    live_row = cnt_l > 0
    if valid0 is not None:
        live_row = live_row & valid0
    w_row = jnp.where(live_row, cnt_l.astype(jnp.float64) / S, 0.0)
    if layout.is_direct:
        gid_l = layout.gids
        starts_l, _cnt = _direct_ranges(layout, None)
        ends_l = starts_l.astype(jnp.int64) + seg.seg_count(layout, None)
    else:
        gid_l = layout.gid_sorted
        starts_l = layout.starts
        ends_l = layout.ends
    gid2 = jnp.repeat(gid_l, S)
    x2 = vals.reshape(-1)
    w2 = jnp.repeat(w_row, S)
    _, x_s, w_s = ranks_ops.stable_sort((gid2, x2, w2), 2)
    c = scans.cumsum(w_s)
    c0 = jnp.concatenate([jnp.zeros((1,), c.dtype), c])
    e_start = starts_l.astype(jnp.int64) * S
    e_end = ends_l.astype(jnp.int64) * S
    w_group = c0[e_end] - c0[e_start]
    # lower weighted percentile: first sample whose cumulative weight
    # reaches p * W (reduces to the nearest-rank pick for equal weights)
    target = c0[e_start] + p * w_group
    pos = jnp.searchsorted(c, target, side="left")
    pos = jnp.clip(pos, e_start, jnp.maximum(e_end - 1, e_start))
    out = x_s[jnp.clip(pos, 0, max(x_s.shape[0] - 1, 0))]
    return out, w_group > 0


def _direct_ranges(layout: seg.GroupLayout, m_l):
    """(starts, live counts) per slot for a direct layout, derived from the
    per-slot counts (rows sort group-contiguous by gid)."""
    cnt_all = seg.seg_count(layout, None)  # rows per slot including masked
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64), scans.cumsum(cnt_all)[:-1]]
    ).astype(jnp.int32)
    cnt = seg.seg_count(layout, m_l)
    return starts, cnt
