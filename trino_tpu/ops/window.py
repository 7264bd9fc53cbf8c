"""Window-function kernels: one fused sort + streaming prefix passes.

Reference: ``operator/WindowOperator.java:69`` + ``window/`` (36 files) —
which iterates partitions row-by-row with per-frame state. TPU redesign:
sort ALL rows once by (dead, partition keys, order keys); in sorted space
every quantity is a streaming prefix computation:

- partition / peer-run starts: ``lax.cummax`` over boundary-masked indices;
- row_number / rank / dense_rank: index arithmetic on those starts;
- running and whole-partition sums/counts: cumsum + gathered boundary
  differences (peer-run ends from merge ranks, ops/ranks.py);
- whole-partition min/max: one extra sort by (partition, value), gather at
  partition starts/ends (same trick as ops/segments.seg_minmax);
- lag/lead/first_value/last_value: bounds-checked gathers in sorted space.

Results return to original row order through the sort's inverse permutation.
Everything is O(n log n) with static shapes — no per-partition loop exists.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from trino_tpu.ops import ranks
from trino_tpu.ops import scans
from trino_tpu.ops import sort as sort_ops

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]


@dataclasses.dataclass
class WindowLayout:
    """Shared sorted-space structure for all window calls of one node."""

    n: int
    order: jnp.ndarray  # int32[n]: sorted slot -> original row
    inv: jnp.ndarray  # int32[n]: original row -> sorted slot
    part_start: jnp.ndarray  # int32[n] per sorted slot
    part_end: jnp.ndarray  # int32[n] per sorted slot (exclusive)
    peer_start: jnp.ndarray  # int32[n]
    peer_end: jnp.ndarray  # int32[n] (exclusive)
    part_id: jnp.ndarray  # int32[n] dense, non-decreasing
    dense_peer: jnp.ndarray  # int32[n] peer-run ordinal within all rows


def _null_split(col: Lowered) -> List[jnp.ndarray]:
    """(null_flag, masked_value) arrays so NULL groups/compares as its own
    value (IS NOT DISTINCT semantics for PARTITION BY / peer detection)."""
    vals, valid = col
    if valid is None:
        return [vals]
    return [~valid, jnp.where(valid, vals, jnp.zeros((), vals.dtype))]


def build_layout(
    partition_keys: List[Lowered],
    order_keys: List[Tuple[Lowered, bool, Optional[bool]]],
    sel: Optional[jnp.ndarray],
    n: int,
) -> WindowLayout:
    sort_keys: List[jnp.ndarray] = []
    if sel is not None:
        sort_keys.append(~sel)  # dead rows last, outside every partition
    part_cols: List[jnp.ndarray] = []
    for pk in partition_keys:
        part_cols.extend(_null_split(pk))
    sort_keys.extend(part_cols)
    peer_cols: List[jnp.ndarray] = []
    for (col, asc, nf) in order_keys:
        peer_cols.extend(sort_ops._sort_key(col[0], col[1], asc, nf))
    sort_keys.extend(peer_cols)
    if not sort_keys:
        sort_keys = [jnp.zeros((n,), jnp.int8)]
    order = ranks.lex_argsort32(sort_keys)
    inv = ranks.inverse_permutation(order)

    def boundary(cols: List[jnp.ndarray]) -> jnp.ndarray:
        neq = jnp.zeros((max(n - 1, 0),), bool)
        for c in cols:
            cs = c[order]
            neq = neq | (cs[1:] != cs[:-1])
        return jnp.concatenate([jnp.ones((1,), bool), neq])

    dead_cols = [~sel] if sel is not None else []
    pb = boundary(dead_cols + part_cols)
    peerb = pb | boundary(peer_cols) if peer_cols else pb
    idx = jnp.arange(n, dtype=jnp.int32)
    part_start = scans.cummax(jnp.where(pb, idx, jnp.int32(-1)))
    peer_start = scans.cummax(jnp.where(peerb, idx, jnp.int32(-1)))
    part_id = scans.cumsum(pb.astype(jnp.int32)) - 1
    dense_peer = scans.cumsum(peerb.astype(jnp.int32)) - 1
    # ends via merge ranks over the dense non-decreasing ids
    ps, pc = ranks.sorted_ranks([part_id], [part_id])
    part_end = ps + pc
    es, ec = ranks.sorted_ranks([dense_peer], [dense_peer])
    peer_end = es + ec
    return WindowLayout(
        n=n, order=order, inv=inv,
        part_start=part_start, part_end=part_end,
        peer_start=peer_start, peer_end=peer_end,
        part_id=part_id, dense_peer=dense_peer,
    )


def _to_orig(layout: WindowLayout, sorted_vals, sorted_valid=None) -> Lowered:
    v = sorted_vals[layout.inv]
    return v, (sorted_valid[layout.inv] if sorted_valid is not None else None)


def row_number(layout: WindowLayout) -> Lowered:
    idx = jnp.arange(layout.n, dtype=jnp.int64)
    return _to_orig(layout, idx - layout.part_start + 1)


def rank(layout: WindowLayout) -> Lowered:
    v = (layout.peer_start - layout.part_start + 1).astype(jnp.int64)
    return _to_orig(layout, v)


def dense_rank(layout: WindowLayout) -> Lowered:
    base = layout.dense_peer[jnp.clip(layout.part_start, 0, layout.n - 1)]
    v = (layout.dense_peer - base + 1).astype(jnp.int64)
    return _to_orig(layout, v)


def _frame_bounds(layout: WindowLayout, frame: str,
                  frame_lo=None, frame_hi=None):
    """[lo, hi) sorted-slot range per row for the supported frames.
    ``rows_offset``: numeric ROWS bounds relative to the current row
    (reference: window/FrameInfo), clamped to the partition."""
    idx = jnp.arange(layout.n, dtype=jnp.int32)
    if frame == "partition":
        return layout.part_start, layout.part_end
    if frame == "rows_running":
        return layout.part_start, idx + 1
    if frame == "rows_offset":
        lo = layout.part_start if frame_lo is None else jnp.maximum(
            layout.part_start, idx + jnp.int32(frame_lo))
        hi = layout.part_end if frame_hi is None else jnp.minimum(
            layout.part_end, idx + jnp.int32(frame_hi) + 1)
        return lo, jnp.maximum(hi, lo)  # empty frame -> hi == lo
    # default 'running': RANGE UNBOUNDED PRECEDING..CURRENT ROW = peers incl.
    return layout.part_start, layout.peer_end


def agg_sum(layout: WindowLayout, arg: Lowered, frame: str, out_dtype,
            frame_lo=None, frame_hi=None) -> Lowered:
    vals, valid = arg
    x = vals[layout.order].astype(out_dtype)
    m = valid[layout.order] if valid is not None else None
    if m is not None:
        x = jnp.where(m, x, jnp.zeros((), out_dtype))
    c = scans.cumsum(x)
    c0 = jnp.concatenate([jnp.zeros((1,), c.dtype), c])
    lo, hi = _frame_bounds(layout, frame, frame_lo, frame_hi)
    s = c0[hi] - c0[lo]
    cnt = _count_in_frame(layout, m, lo, hi)
    return _to_orig(layout, s, cnt > 0)


def agg_count(layout: WindowLayout, arg: Optional[Lowered], frame: str,
              frame_lo=None, frame_hi=None) -> Lowered:
    lo, hi = _frame_bounds(layout, frame, frame_lo, frame_hi)
    if arg is None or arg[1] is None:
        return _to_orig(layout, (hi - lo).astype(jnp.int64))
    m = arg[1][layout.order]
    return _to_orig(layout, _count_in_frame(layout, m, lo, hi))


def _count_in_frame(layout, m, lo, hi) -> jnp.ndarray:
    if m is None:
        return (hi - lo).astype(jnp.int64)
    c = scans.cumsum(m.astype(jnp.int64))
    c0 = jnp.concatenate([jnp.zeros((1,), c.dtype), c])
    return c0[hi] - c0[lo]


def agg_minmax(layout: WindowLayout, arg: Lowered, frame: str, is_min: bool) -> Lowered:
    """Whole-partition min/max via one sort by (partition, value)."""
    if frame != "partition":
        raise NotImplementedError("running min/max window frames")
    vals, valid = arg
    if jnp.issubdtype(vals.dtype, jnp.floating):
        sentinel = jnp.inf if is_min else -jnp.inf
    else:
        info = jnp.iinfo(vals.dtype if vals.dtype != jnp.bool_ else jnp.int32)
        vals = vals.astype(jnp.int32) if vals.dtype == jnp.bool_ else vals
        sentinel = info.max if is_min else info.min
    x = vals if valid is None else jnp.where(valid, vals, sentinel)
    xs = x[layout.order]
    _, x_by = ranks.stable_sort((layout.part_id, xs), 2)
    pos = layout.part_start if is_min else jnp.clip(layout.part_end - 1, 0, layout.n - 1)
    out = x_by[pos]
    m = valid[layout.order] if valid is not None else None
    lo, hi = _frame_bounds(layout, "partition")
    cnt = _count_in_frame(layout, m, lo, hi)
    return _to_orig(layout, out, cnt > 0)


def shifted_value(layout: WindowLayout, arg: Lowered, offset: int, lead: bool) -> Lowered:
    """lag/lead: the value ``offset`` rows before/after within the partition
    (NULL outside)."""
    vals, valid = arg
    xs = vals[layout.order]
    vs = valid[layout.order] if valid is not None else None
    idx = jnp.arange(layout.n, dtype=jnp.int32)
    tgt = idx + offset if lead else idx - offset
    inside = (tgt >= layout.part_start) & (tgt < layout.part_end)
    tgt = jnp.clip(tgt, 0, layout.n - 1)
    v = xs[tgt]
    ok = inside if vs is None else (inside & vs[tgt])
    return _to_orig(layout, v, ok)


def edge_value(layout: WindowLayout, arg: Lowered, frame: str, first: bool,
               frame_lo=None, frame_hi=None) -> Lowered:
    """first_value / last_value over the frame (default frame: last_value is
    the current peer run's end — the SQL footgun, faithfully)."""
    vals, valid = arg
    xs = vals[layout.order]
    vs = valid[layout.order] if valid is not None else None
    lo, hi = _frame_bounds(layout, frame, frame_lo, frame_hi)
    pos = lo if first else jnp.clip(hi - 1, 0, layout.n - 1)
    v = xs[pos]
    ok = None if vs is None else vs[pos]
    nonempty = hi > lo
    ok = nonempty if ok is None else (ok & nonempty)
    return _to_orig(layout, v, ok)


def nth_value(layout: WindowLayout, arg: Lowered, nth: int, frame: str,
              frame_lo=None, frame_hi=None) -> Lowered:
    """nth_value(x, n): the frame's n-th row's value (NULL past the end)."""
    vals, valid = arg
    xs = vals[layout.order]
    vs = valid[layout.order] if valid is not None else None
    lo, hi = _frame_bounds(layout, frame, frame_lo, frame_hi)
    pos = lo + jnp.int32(nth - 1)
    inside = pos < hi
    pos = jnp.clip(pos, 0, layout.n - 1)
    v = xs[pos]
    ok = inside if vs is None else (inside & vs[pos])
    return _to_orig(layout, v, ok)


def ntile(layout: WindowLayout, buckets: int) -> Lowered:
    """ntile(k): partition rows into k buckets, earlier buckets one larger
    when sizes don't divide (reference: window/NTileFunction)."""
    idx = jnp.arange(layout.n, dtype=jnp.int64)
    rn0 = idx - layout.part_start  # 0-based row number
    size = (layout.part_end - layout.part_start).astype(jnp.int64)
    k = jnp.int64(buckets)
    q = size // k
    r = size % k
    big_rows = r * (q + 1)  # rows covered by the (q+1)-sized buckets
    tile = jnp.where(
        rn0 < big_rows,
        rn0 // jnp.maximum(q + 1, 1),
        r + (rn0 - big_rows) // jnp.maximum(q, 1),
    )
    return _to_orig(layout, tile + 1)


def percent_rank(layout: WindowLayout) -> Lowered:
    """(rank - 1) / (partition size - 1); 0 for single-row partitions."""
    rk = (layout.peer_start - layout.part_start).astype(jnp.float64)
    size = (layout.part_end - layout.part_start).astype(jnp.float64)
    v = jnp.where(size > 1, rk / jnp.maximum(size - 1.0, 1.0), 0.0)
    return _to_orig(layout, v)


def cume_dist(layout: WindowLayout) -> Lowered:
    """rows at-or-before the current peer group / partition size."""
    covered = (layout.peer_end - layout.part_start).astype(jnp.float64)
    size = (layout.part_end - layout.part_start).astype(jnp.float64)
    return _to_orig(layout, covered / jnp.maximum(size, 1.0))
