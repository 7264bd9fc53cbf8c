"""Scatter-free segment reductions: the TPU group-by/aggregate substrate.

Reference role: ``operator/FlatHash.java`` + ``AccumulatorCompiler`` — the
grouped-accumulation inner loop. On TPU, scatter (``jax.ops.segment_*``)
compiles to serialized HBM read-modify-write and is ~50x slower than the
streaming alternatives (measured on v5e: 6M-row int64 segment_sum = 513 ms vs
9.5 ms for masked reductions). So grouped aggregation here never scatters
integers; it uses one of two layouts:

- **direct** (the BigintGroupByHash analog): group keys are small perfect
  indices (dictionary codes / booleans); per-group values come from an
  unrolled masked-reduction loop over the (small, static) capacity — each
  reduction is a streaming VPU pass, XLA fuses the whole unrolled set into
  few passes.
- **sorted** (the FlatHash analog): rows are permuted group-contiguous
  (stable multi-key argsort, dead rows last); per-group sums are
  cumsum-then-boundary-difference (exact in int64), min/max are a segmented
  associative scan — all streaming ops, no scatter.
- **run** (input ALREADY group-contiguous: one ascending key): nothing is
  permuted, listed or gathered. Slot i is row i, a group's results sit on
  the LAST row of its run, and sums and counts are prefix scans alone
  (``_run_sums``). On v5e a gather of 62.9 M slots out of a 62.9 M-row
  array takes 2 s (TPC-H Q18's group-by at SF 10 issued eight a statement
  through the sorted layout's ``starts`` / ``ends``); a scan of the same
  array takes 0.05 s.

Float sums still use ``jax.ops.segment_sum`` (f32 scatter is fast on TPU and
per-slot accumulation order is deterministic).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from trino_tpu.ops import ranks
from trino_tpu.ops import scans

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]

# Above this capacity the unrolled masked loop stops making sense and the
# sort-based layout wins (threshold: capacity reads of the column).
DIRECT_CAPACITY_MAX = 128


@dataclasses.dataclass
class GroupLayout:
    """Grouping structure shared by every aggregate of one aggregation node.

    Exactly one of (``gids``,) / (``order``, ``gid_sorted``) is populated:
    direct layouts keep per-row perfect-index group ids in original row
    order; sorted layouts keep the permutation to group-contiguous order
    plus per-slot [start, end) ranges in that sorted space.
    """

    n: int  # input rows
    capacity: int  # static output slots
    # direct layout
    gids: Optional[jnp.ndarray] = None  # int32[n] perfect index
    # sorted layout
    order: Optional[jnp.ndarray] = None  # int32[n] permutation
    gid_sorted: Optional[jnp.ndarray] = None  # int32[n] non-decreasing
    starts: Optional[jnp.ndarray] = None  # int32[capacity]
    ends: Optional[jnp.ndarray] = None  # int32[capacity]
    num_groups: Optional[jnp.ndarray] = None  # scalar (sorted only)
    rep: Optional[jnp.ndarray] = None  # int[capacity] representative row (orig order)
    # run layout: bool[n], True on the first row of every run of equal keys
    # (and where live rows end); slot i is row i, nothing else is populated
    run_start: Optional[jnp.ndarray] = None

    @property
    def is_direct(self) -> bool:
        return self.gids is not None

    def gids_layout(self) -> jnp.ndarray:
        """Per-row group ids in LAYOUT SPACE (original order for direct
        layouts, sorted order for sorted ones)."""
        return self.gids if self.gids is not None else self.gid_sorted

    def gids_orig(self) -> jnp.ndarray:
        """Per-row group ids in original row order (rarely needed: only
        nested regroupings like count(DISTINCT) ask for it)."""
        if self.gids is not None:
            return self.gids
        inverse = ranks.inverse_permutation(self.order)
        return self.gid_sorted[inverse]


def direct_layout(gids: jnp.ndarray, capacity: int, live: Optional[jnp.ndarray]) -> GroupLayout:
    """Layout for perfect-index group ids (capacity <= DIRECT_CAPACITY_MAX)."""
    n = gids.shape[0]
    assert capacity <= DIRECT_CAPACITY_MAX
    idx = jnp.arange(n, dtype=jnp.int32)
    dead_idx = jnp.int32(n)
    reps = []
    for g in range(capacity):
        m = gids == g
        if live is not None:
            m = m & live
        reps.append(jnp.min(jnp.where(m, idx, dead_idx)))
    rep = jnp.stack(reps)
    return GroupLayout(n=n, capacity=capacity, gids=gids, rep=rep)


def sorted_layout(
    order: jnp.ndarray, gid_sorted: jnp.ndarray, num_groups: jnp.ndarray
) -> GroupLayout:
    """Layout from a group-contiguous permutation (ops/groupby.py).

    ``gid_sorted`` is DENSE and non-decreasing (run k has gid k), so slot
    ranges need no rank search: the run-boundary positions, listed in
    order from prefix counts (``ranks.true_positions``), ARE ``starts``,
    and each run ends where the next begins. No sort."""
    n = order.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), gid_sorted[1:] != gid_sorted[:-1]]
    )
    nb = jnp.sum(boundary.astype(jnp.int32))
    starts_seq = ranks.true_positions(boundary, n)
    nn = jnp.int32(n)
    starts = jnp.where(pos < nb, starts_seq, nn)
    next_start = jnp.concatenate([starts_seq[1:], jnp.full((1,), nn, jnp.int32)])
    ends = jnp.where(pos < nb, jnp.where(pos + 1 < nb, next_start, nn), nn)
    rep = order[jnp.clip(starts, 0, n - 1)]
    return GroupLayout(
        n=n,
        capacity=n,
        order=order,
        gid_sorted=gid_sorted,
        starts=starts,
        ends=ends,
        num_groups=num_groups,
        rep=rep,
    )


def run_layout(run_start: jnp.ndarray) -> GroupLayout:
    """Layout of a page that is already group-contiguous: ``run_start``
    marks each run's first row. Only ``seg_sum`` and ``seg_count`` read it
    (sum, count and avg of integer arguments); whoever builds it keeps to
    those (Executor.group_structure)."""
    n = run_start.shape[0]
    return GroupLayout(n=n, capacity=n, run_start=run_start)


def run_ends(layout: GroupLayout) -> jnp.ndarray:
    """bool[n]: the last row of every run, where a run layout's per-group
    results sit."""
    return jnp.concatenate([layout.run_start[1:], jnp.ones((1,), bool)])


def _run_first(run_start: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Per row, ``v`` at the first row of the row's run, for int64 ``v`` of
    any sign, with no gather: a running maximum over (row index, half of
    the value) packed into one word finds the latest flagged row, once for
    each 32-bit half. Row 0 always starts a run."""
    pos = jnp.arange(v.shape[0], dtype=jnp.int64) << 32
    m32 = jnp.int64(0xFFFFFFFF)
    halves = [
        scans.cummax(jnp.where(run_start, pos | half, jnp.int64(-1))) & m32
        for half in (v & m32, (v >> 32) & m32)]
    return halves[0] | (halves[1] << 32)


def _run_sums(run_start: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Per row, the sum of int ``x`` over the row's run up to and including
    the row (exact: wraparound cancels mod 2^64); on a run's last row, the
    run's sum."""
    x = x.astype(jnp.int64)
    c = scans.cumsum(x)
    return c - _run_first(run_start, c - x)


def _run_counts(run_start: jnp.ndarray, m: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Per row, how many rows of its run up to and including it satisfy
    ``m``. A count before the run never decreases, so one running maximum
    carries it forward."""
    n = run_start.shape[0]
    if m is None:
        pos = jnp.arange(n, dtype=jnp.int32)
        first = scans.cummax(jnp.where(run_start, pos, 0))
        return (pos - first + 1).astype(jnp.int64)
    m = m.astype(jnp.int32)
    c = scans.cumsum(m)
    before = scans.cummax(jnp.where(run_start, c - m, 0))
    return (c - before).astype(jnp.int64)


def occupancy(layout: GroupLayout, live: Optional[jnp.ndarray]) -> jnp.ndarray:
    """bool[capacity]: slots holding at least one live row (the live mask is
    already baked into ``rep`` by direct_layout)."""
    if layout.is_direct:
        return layout.rep < layout.n
    return jnp.arange(layout.capacity) < layout.num_groups


def _cumsum_diff_ranges(
    starts: jnp.ndarray, ends: jnp.ndarray, x_sorted: jnp.ndarray
) -> jnp.ndarray:
    """Per-range sums of a segment-contiguous array via cumsum + boundary
    difference (exact for ints: wraparound cancels mod 2^64)."""
    c = scans.cumsum(x_sorted)
    c0 = jnp.concatenate([jnp.zeros((1,), c.dtype), c])
    return c0[ends] - c0[starts]


def _cumsum_diff(layout: GroupLayout, x_sorted: jnp.ndarray) -> jnp.ndarray:
    return _cumsum_diff_ranges(layout.starts, layout.ends, x_sorted)


def seg_sum(
    layout: GroupLayout, vals: jnp.ndarray, m: Optional[jnp.ndarray], out_dtype
) -> jnp.ndarray:
    """Per-slot sum of ``vals`` over rows where mask ``m`` holds.

    ``vals``/``m`` are in LAYOUT SPACE: original row order for direct
    layouts, group-contiguous sorted order for sorted layouts. Callers get
    sorted-space arrays for free as payload operands of the grouping sort
    (Executor.group_structure) — a per-aggregate random re-gather by the
    permutation would cost ~40 ms per 6M rows on v5e."""
    x = vals.astype(out_dtype)
    if m is not None:
        x = jnp.where(m, x, jnp.zeros((), out_dtype))
    if layout.run_start is not None:
        return _run_sums(layout.run_start, x).astype(out_dtype)
    if layout.is_direct:
        return jnp.stack([jnp.sum(jnp.where(layout.gids == g, x, 0)) for g in range(layout.capacity)])
    if jnp.issubdtype(jnp.dtype(out_dtype), jnp.floating):
        # f32/f64 scatter-add is fast on TPU and avoids cumsum error growth
        return jax.ops.segment_sum(
            x, layout.gid_sorted, num_segments=layout.capacity
        )
    return _cumsum_diff(layout, x)


def seg_count(layout: GroupLayout, m: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Per-slot count of rows where mask ``m`` holds (int64). ``m`` is in
    layout space (see seg_sum)."""
    if layout.run_start is not None:
        return _run_counts(layout.run_start, m)
    ones = (
        jnp.ones((layout.n,), jnp.int64)
        if m is None
        else m.astype(jnp.int64)
    )
    if layout.is_direct:
        return jnp.stack(
            [jnp.sum(jnp.where(layout.gids == g, ones, 0)) for g in range(layout.capacity)]
        )
    if m is None:
        return (layout.ends - layout.starts).astype(jnp.int64)
    return _cumsum_diff(layout, ones)


def seg_minmax(
    layout: GroupLayout, vals: jnp.ndarray, m: Optional[jnp.ndarray], is_min: bool
) -> jnp.ndarray:
    """Per-slot min/max of vals over rows where ``m`` holds (sentinel-filled
    for empty slots — pair with seg_count to derive validity).

    ``vals``/``m`` are in layout space (see seg_sum).

    Sorted path: one fused sort by (gid, value) puts each group's min at its
    start and max at its end — two gathers finish the job. (A segmented
    associative_scan would be the textbook formulation, but its unrolled
    log-depth graph does not compile at multi-million rows on v5e.)"""
    if jnp.issubdtype(vals.dtype, jnp.floating):
        sentinel = jnp.inf if is_min else -jnp.inf
    elif vals.dtype == jnp.bool_:
        vals = vals.astype(jnp.int32)
        sentinel = 1 if is_min else 0
    else:
        info = jnp.iinfo(vals.dtype)
        sentinel = info.max if is_min else info.min
    x = vals if m is None else jnp.where(m, vals, sentinel)
    if layout.is_direct:
        red = jnp.min if is_min else jnp.max
        return jnp.stack(
            [red(jnp.where(layout.gids == g, x, sentinel)) for g in range(layout.capacity)]
        )
    _, x_by_group = ranks.stable_sort((layout.gid_sorted, x), 2)
    n = layout.n
    pos = layout.starts if is_min else jnp.clip(layout.ends - 1, 0, n - 1)
    out = x_by_group[jnp.clip(pos, 0, n - 1)]
    return jnp.where(layout.ends > layout.starts, out, sentinel)


def monotonic_segment_sum(
    x: jnp.ndarray, seg: jnp.ndarray, n_segments: int
) -> jnp.ndarray:
    """Segment sums when ``seg`` is already non-decreasing (e.g. the
    probe-major output of a join expansion) — cumsum + boundary diff,
    no scatter."""
    slots = jnp.arange(n_segments, dtype=seg.dtype)
    starts, cnt = ranks.sorted_ranks([seg], [slots])
    return _cumsum_diff_ranges(starts, starts + cnt, x)
