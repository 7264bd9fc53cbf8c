"""Join kernels: lookup (N:1), M:N expansion, semi/anti — sort-merge based.

Reference: ``operator/join/`` — PagesHash open addressing + PositionLinks
chains (JoinHash.java:28-69). TPU formulation: the build side is sorted by
key once (one fused multi-operand ``lax.sort``); probe ranges come from
merge ranks (ops/ranks.py: one combined stable sort + streaming prefixes —
binary search and its log2(n) random-gather passes never appear):

- unique-key build (PK-FK joins, N:1): probe -> at most one match -> output
  size == probe size (static shapes, no two-pass emit). The planner proves
  uniqueness (primary keys / group-by outputs) before choosing this kernel.
- general M:N join: two-pass count+emit (``probe_counts`` + ``expand``) —
  the role of PositionLinks chain-following (JoinHash.java:28-69), done as
  one vectorized gather into a *static-capacity* output (capacity from the
  executor's shape-hint mechanism; exceeding it raises a deferred error and
  triggers a bucketed recompile).
- semi/anti joins: membership only (duplicates on build side are fine).

Composite keys of any column count and full int64 range are supported (the
lex sort and merge ranks compare all columns; no bit packing). The reference
hashes arbitrary-width keys the same way (InterpretedHashGenerator.java:85).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp

from trino_tpu.ops import ranks
from trino_tpu.ops import scans

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]


def _sentinel_max(dtype):
    """Largest value of the key dtype — dead rows sort last under it. A live
    key equal to the sentinel is re-guarded by the live mask at probe time
    (probe_counts checks build.live at the range start)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


_INT_WIDEN = {jnp.dtype(jnp.int8): jnp.int16, jnp.dtype(jnp.int16): jnp.int32,
              jnp.dtype(jnp.int32): jnp.int64}


def align_join_keys(
    build_keys: List[Lowered],
    probe_keys: List[Lowered],
    build_vranges=None,
    probe_vranges=None,
) -> Tuple[List[Lowered], List[Lowered]]:
    """Cast each (build, probe) key pair to its common PHYSICAL dtype so the
    kernels below sort/compare at the narrowest width the data rides
    (data/page.py Column: int32-narrowed keys sort ~2x faster than emulated
    int64 on TPU). Bool keys promote to int8.

    Single-key builds mask dead rows with the dtype's max value (sentinel),
    so a live key equal to that max could collide with dead rows. When the
    pair's value ranges don't PROVE the max is unreachable, integer keys
    widen one step (int8->int16->...->int64; int64 keeps the legacy
    2^63-1 edge). Multi-key builds use a dead-flag column instead of a
    sentinel and never need this."""
    n = len(build_keys)
    single = n == 1
    if build_vranges is None:
        build_vranges = [None] * n
    if probe_vranges is None:
        probe_vranges = [None] * n
    out_b, out_p = [], []
    for (bv, bva), (pv, pva), bvr, pvr in zip(
        build_keys, probe_keys, build_vranges, probe_vranges
    ):
        dt = jnp.promote_types(bv.dtype, pv.dtype)
        if dt == jnp.bool_:
            dt = jnp.int8
        if single and jnp.issubdtype(dt, jnp.integer):
            proven = (
                bvr is not None and pvr is not None
                and max(bvr[1], pvr[1]) < jnp.iinfo(dt).max
            )
            if not proven and jnp.dtype(dt) in _INT_WIDEN:
                dt = _INT_WIDEN[jnp.dtype(dt)]
        out_b.append((bv.astype(dt), bva))
        out_p.append((pv.astype(dt), pva))
    return out_b, out_p


@dataclasses.dataclass
class SortedBuild:
    """Build side sorted lexicographically by key, dead rows last.

    ``cols`` are the search columns in sorted order, most significant first.
    Single-key builds carry one sentinel-masked column (fast path); multi-key
    builds carry a leading dead-flag column (0 live / 1 dead) so dead rows
    can never equal a probe (whose flag is implicitly 0).
    """

    cols: List[jnp.ndarray]
    rows: jnp.ndarray  # original row index per sorted slot
    live: jnp.ndarray  # bool per sorted slot
    single: bool  # True -> cols == [sentinel-masked key], no flag column

    @property
    def n(self) -> int:
        return self.rows.shape[0]


def _live_mask(keys: List[Lowered], sel: Optional[jnp.ndarray]) -> jnp.ndarray:
    n = keys[0][0].shape[0]
    live = jnp.ones((n,), dtype=bool)
    if sel is not None:
        live = live & sel
    for _, valid in keys:
        if valid is not None:
            live = live & valid
    return live


def build_side(keys: List[Lowered], sel: Optional[jnp.ndarray],
               presorted: bool = False) -> SortedBuild:
    """Sort the build side by composite key; dead/null rows sort last and can
    never match (single-key: sentinel; multi-key: leading dead-flag column).

    ``presorted``: the caller proves a SINGLE null-free key already
    ascending with dead rows forming a TAIL (Column.ascending +
    Page.live_prefix) — the build sort is skipped entirely (sentinel-masked
    dead tail keeps the array sorted: the sentinel is the dtype max)."""
    import jax

    live = _live_mask(keys, sel)
    n = live.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    if presorted and len(keys) == 1 and keys[0][1] is None:
        vals = keys[0][0]
        if vals.dtype == jnp.bool_:
            vals = vals.astype(jnp.int8)
        k = jnp.where(live, vals, _sentinel_max(vals.dtype))
        return SortedBuild([k], iota, live, True)
    # sorted key columns and the permuted live flags come out of ONE
    # ranks.stable_sort
    if len(keys) == 1:
        vals = keys[0][0]
        if vals.dtype == jnp.bool_:
            vals = vals.astype(jnp.int8)
        k = jnp.where(live, vals, _sentinel_max(vals.dtype))
        k_s, live_s, order = ranks.stable_sort((k, live, iota), 1)
        return SortedBuild([k_s], order, live_s, True)
    dead = (~live).astype(jnp.int8)
    masked = [
        jnp.where(live, v.astype(jnp.int8) if v.dtype == jnp.bool_ else v,
                  jnp.zeros((), jnp.int8 if v.dtype == jnp.bool_ else v.dtype))
        for v, _ in keys
    ]
    sort_keys = [dead] + masked
    out = ranks.stable_sort(tuple(sort_keys) + (live, iota), len(sort_keys))
    return SortedBuild(list(out[:-2]), out[-1], out[-2], False)


def _probe_cols(build: SortedBuild, probe_keys: List[Lowered]) -> List[jnp.ndarray]:
    """Probe-side search columns aligned with ``build.cols`` (callers align
    physical dtypes up front via align_join_keys)."""
    def as_key(v):
        return v.astype(jnp.int8) if v.dtype == jnp.bool_ else v

    if build.single:
        return [as_key(probe_keys[0][0])]
    m = probe_keys[0][0].shape[0]
    return [jnp.zeros((m,), jnp.int8)] + [as_key(v) for v, _ in probe_keys]


def probe_valid(probe_keys: List[Lowered]) -> Optional[jnp.ndarray]:
    """AND of per-column probe validity (NULL keys never match)."""
    valid = None
    for _, v in probe_keys:
        if v is not None:
            valid = v if valid is None else (valid & v)
    return valid


def probe_unique(
    build: SortedBuild, probe_keys: List[Lowered]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Probe against a unique-key build. Returns (build_row_idx, matched)."""
    lo, counts = probe_counts(build, probe_keys, None)
    pos = jnp.clip(lo, 0, build.n - 1)
    return build.rows[pos], counts > 0


def membership(
    build_keys: List[Lowered],
    build_sel: Optional[jnp.ndarray],
    probe_keys: List[Lowered],
    presorted: bool = False,
) -> jnp.ndarray:
    """Semi-join membership test (build side may have duplicates)."""
    build = build_side(build_keys, build_sel, presorted=presorted)
    _, counts = probe_counts(build, probe_keys, None)
    return counts > 0


def probe_counts(
    build: SortedBuild,
    probe_keys: List[Lowered],
    probe_sel: Optional[jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pass 1 of the M:N join: per probe row, the sorted-build range start
    and match count (merge ranks, ops/ranks.py — no binary search). Dead
    probe rows (sel/NULL key) count 0."""
    probe = _probe_cols(build, probe_keys)
    lo, counts = ranks.sorted_ranks(build.cols, probe)
    # ranges of a real key contain only live rows (dead rows sort last with
    # unmatchable key) but guard the all-dead-build edge anyway
    counts = jnp.where(build.live[jnp.clip(lo, 0, build.n - 1)], counts, 0)
    pvalid = probe_valid(probe_keys)
    if pvalid is not None:
        counts = jnp.where(pvalid, counts, 0)
    if probe_sel is not None:
        counts = jnp.where(probe_sel, counts, 0)
    return lo, counts


def expand(
    counts: jnp.ndarray, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pass 2: map output slot j -> (probe_row, within-range offset).

    Returns (probe_row[cap], offset_in_range[cap], live[cap], total).
    Output is probe-major (all matches of probe row 0, then row 1, ...).
    """
    n = counts.shape[0]
    c64 = counts.astype(jnp.int64)  # cumsum in int64: totals can exceed 2^31
    if n == 0:  # zero-row probe page: all output slots dead
        z = jnp.zeros((capacity,), jnp.int64)
        return z, z, jnp.zeros((capacity,), bool), jnp.zeros((), jnp.int64)
    offsets = scans.cumsum(c64)  # inclusive
    total = offsets[n - 1]
    starts = offsets - c64
    # search in int32 when capacity fits: offsets past 2^31 only occur when
    # total overflowed the capacity anyway (flagged, run discarded), so
    # clipping them cannot change any slot j < capacity's result
    if capacity < 2**31:
        offs = jnp.clip(offsets, 0, 2**31 - 1).astype(jnp.int32)
        j = jnp.arange(capacity, dtype=jnp.int32)
    else:
        offs = offsets
        j = jnp.arange(capacity, dtype=jnp.int64)
    # both sides sorted -> merge ranks, not binary search
    p = jnp.clip(ranks.ranks_sorted_queries(offs, j, side="right"), 0, n - 1)
    k = j.astype(jnp.int64) - starts[p]
    live = j < jnp.minimum(total, capacity).astype(j.dtype)
    return p, k, live, total


# ---------------------------------------------------------------- dense path
# Direct-address join: when the single integer build key rides a known value
# range (Column.vrange) whose span fits a device table, the build side
# scatters row ids into a span-sized table and the probe side does ONE
# bounded gather — no sort of either side ever happens. This is the TPU
# answer to the reference's array-based lookup sources
# (``operator/join/ArrayBasedLookupSource``): TPC-H/DS keys are dense
# integer sequences, so the "hash" is the identity map onto the vrange.
DENSE_SPAN_MAX = 1 << 27  # int32 table slots (512 MiB worst case)


def dense_span(build_vrange, n_build: int) -> Optional[Tuple[int, int]]:
    """(lo, span) when a direct-address table is worth it, else None.
    Worth it = span bounded AND not absurdly sparse relative to the build
    (a 128x-over-provisioned table still beats a sort at these sizes)."""
    if build_vrange is None:
        return None
    lo, hi = int(build_vrange[0]), int(build_vrange[1])
    span = hi - lo + 1
    if span <= 0 or span > DENSE_SPAN_MAX:
        return None
    if span > 128 * max(n_build, 1024):
        return None
    return lo, span


def dense_unique_table(
    key: Lowered, sel: Optional[jnp.ndarray], lo: int, span: int
) -> jnp.ndarray:
    """Scatter build row ids (+1; 0 = empty) into the span table. Dead rows
    scatter to DISTINCT out-of-bounds slots (span + iota) and are dropped,
    so ``unique_indices`` stays truthful — the planner proved live-key
    uniqueness (right_unique) before choosing this kernel."""
    vals, valid = key
    n = vals.shape[0]
    iota = jnp.arange(n, dtype=jnp.int64)
    live = jnp.ones((n,), bool) if sel is None else sel
    if valid is not None:
        live = live & valid
    idx = jnp.where(live, vals.astype(jnp.int64) - lo, span + iota)
    return jnp.zeros((span,), jnp.int32).at[idx].set(
        iota.astype(jnp.int32) + 1, mode="drop", unique_indices=True)


def dense_probe_unique(
    table: jnp.ndarray, key: Lowered, lo: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(build_row_idx, matched) — the dense analog of probe_unique."""
    vals, valid = key
    span = table.shape[0]
    v = vals.astype(jnp.int64)
    slot = table[jnp.clip(v - lo, 0, span - 1)]
    matched = (v >= lo) & (v < lo + span) & (slot > 0)
    if valid is not None:
        matched = matched & valid
    return jnp.maximum(slot - 1, 0), matched


def dense_membership_table(
    build_key: Lowered, build_sel: Optional[jnp.ndarray], lo: int, span: int,
) -> jnp.ndarray:
    """Build half of the dense membership test: the boolean LUT (build
    duplicates are fine: True is idempotent, so the non-unique scatter-set
    is deterministic). Split out so callers probing many pages against ONE
    build (the overlapped per-block exchange) scatter the table once."""
    bvals, bvalid = build_key
    live = (jnp.ones((bvals.shape[0],), bool) if build_sel is None
            else build_sel)
    if bvalid is not None:
        live = live & bvalid
    idx = jnp.where(live, bvals.astype(jnp.int64) - lo, span)
    return jnp.zeros((span,), bool).at[idx].set(True, mode="drop")


def dense_membership_probe(
    lut: jnp.ndarray, probe_key: Lowered, lo: int,
) -> jnp.ndarray:
    """Probe half of the dense membership test: one bounded gather."""
    span = lut.shape[0]
    pvals, pvalid = probe_key
    v = pvals.astype(jnp.int64)
    hit = (v >= lo) & (v < lo + span) & lut[jnp.clip(v - lo, 0, span - 1)]
    if pvalid is not None:
        hit = hit & pvalid
    return hit


def dense_membership(
    build_key: Lowered, build_sel: Optional[jnp.ndarray],
    probe_key: Lowered, lo: int, span: int,
) -> jnp.ndarray:
    """Semi-join membership via a boolean LUT (one scatter, one bounded
    gather)."""
    lut = dense_membership_table(build_key, build_sel, lo, span)
    return dense_membership_probe(lut, probe_key, lo)


def gather_columns(
    cols: List[Lowered], rows: jnp.ndarray, matched: jnp.ndarray
) -> List[Lowered]:
    """Gather build columns to probe positions in ONE random-HBM pass per
    dtype (ranks.batched_gather) — separate computed-index gathers don't
    fuse and cost ~40 ms per 6M rows each on v5e. Unmatched rows become
    NULL (consumed by inner-join sel or left-join null masks)."""
    if not cols:
        return []
    n = cols[0][0].shape[0]
    safe = jnp.clip(rows, 0, n - 1)
    arrays = [vals for vals, _ in cols] + [
        valid for _, valid in cols if valid is not None
    ]
    gathered = ranks.batched_gather(arrays, safe)
    out: List[Lowered] = []
    vi = len(cols)
    for i, (_, valid) in enumerate(cols):
        if valid is None:
            out.append((gathered[i], matched))
        else:
            out.append((gathered[i], gathered[vi] & matched))
            vi += 1
    return out
