"""Pipelined cold staging: the engine every staging tier runs.

A statement whose tables are not resident stages them every time:
``tpch_sf10.q3`` spends 4.2 s of its 8.7 s staging 1.35 GB (ledger, PR 31;
``PERF.md`` section 5) — the warm-HBM device cache (PR 7) only fixes the
second run. In the reference this work is inherently
parallel: the connector SPI hands out *splits* and tasks run concurrent
page-source drivers over them. This module is that split-driver plane for
the staged-execution model, used by all three staging tiers (eager /
compiled phase-1 in ``exec/executor.py``, worker fragments in
``server/task.py``, SPMD shards in ``parallel/spmd.py``):

- **parallel split reads** — ``stage_splits`` fans ``connector.scan`` +
  host-applied domain pruning out over a shared process-wide IO pool (the
  PR 12 ``io_pool`` pattern), so scan+decode of split k+2 overlaps the
  decode/transfer of split k; results assemble in split order, so the
  staged arrays are BIT-IDENTICAL to the serial path;
- **a host-RAM columnar cache consult per split** — misses fill
  :data:`~trino_tpu.devcache.hostcache.HOST_CACHE` (single-flight), hits
  skip the connector entirely, so an HBM eviction or a re-sharding pays
  transfer only (``staging/host-cache`` span);
- **double-buffered host->device transfer** — ``blocked_transfer`` chunks
  the assembled columns into byte-bounded row blocks and issues the async
  ``jax.device_put`` for block k+1 before block k is consumed by the
  device-side assembly, bounding pinned-host pressure and overlapping
  PCIe/ICI DMA with host work on real accelerators (CPU meshes degrade to
  a plain copy); the pre-transfer projection (scan's column list) and the
  host-applied constraint pruning mean only needed columns/rows cross;
- **adaptive split sizing** — ``target_split_count`` derives the
  ``get_splits`` target from estimated table bytes / the
  ``staging_split_bytes`` session property, so tiny tables don't pay
  fan-out overhead and huge tables don't underparallelize.

Observability: the ``device/staging`` wall decomposes into the
``staging/scan`` / ``staging/decode`` / ``staging/transfer`` /
``staging/host-cache`` sub-spans (all mapped into the phase ledger's
``device-staging`` bucket) and the
``trino_tpu_staging_phase_seconds_total{phase}`` counter;
``trino_tpu_staging_seconds_total`` keeps its exact per-tier charging
semantics (``phase1_s + df_apply_s``, drift-tested).
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from trino_tpu.obs import metrics as M
from trino_tpu.obs import trace as tracing

# default target bytes per split when the session does not override
# staging_split_bytes — sized so a handful of splits cover a warm L3-sized
# table and a TPC-H sf10 lineitem fans out to tens of splits
DEFAULT_SPLIT_BYTES = 64 << 20
# fan-out ceiling: beyond this, per-split constant costs (gencache entry
# churn, dictionary merges) dominate any remaining overlap win
MAX_TARGET_SPLITS = 64
# target bytes per double-buffered transfer block
TRANSFER_BLOCK_BYTES = 32 << 20
# above this, a column transfers single-shot instead of blocked: the
# blocked path's device-side concat transiently holds blocks + output
# (~2x the column) — a peak the eviction machinery cannot see — so giant
# columns keep the 1x-peak path until the hardware round sizes a real
# bound (env TRINO_TPU_STAGING_BLOCKED_MAX_BYTES)
BLOCKED_MAX_BYTES = int(os.environ.get(
    "TRINO_TPU_STAGING_BLOCKED_MAX_BYTES") or 256 << 20)
# double-buffer depth: un-materialized device_puts allowed in flight
# before the next block issues (bounds pinned-host/DMA-staging memory)
_INFLIGHT_PUTS = 2
# shared scan pool capacity (all sessions of this process; per-staging
# concurrency is bounded separately by staging_parallelism)
POOL_WORKERS = max(4, int(os.environ.get("TRINO_TPU_STAGING_POOL") or 16))

_pool_cell: List = []
_pool_lock = threading.Lock()


def staging_pool():
    """The process-wide staging IO pool, created on first use (the PR 12
    ``CoordinatorServer.io_pool`` pattern: one long-lived pool instead of
    per-staging thread churn)."""
    if _pool_cell:
        return _pool_cell[0]
    from concurrent.futures import ThreadPoolExecutor

    with _pool_lock:
        if not _pool_cell:
            _pool_cell.append(ThreadPoolExecutor(
                max_workers=POOL_WORKERS, thread_name_prefix="staging-io"))
    return _pool_cell[0]


def staging_parallelism(session) -> int:
    """Per-staging fan-out width: the ``staging_parallelism`` session
    property, or (0 = auto) min(8, cpu count). 1 = the serial path."""
    props = getattr(session, "properties", None) or {}
    v = int(props.get("staging_parallelism") or 0)
    if v > 0:
        return v
    return min(8, os.cpu_count() or 1)


def split_bytes_target(session) -> int:
    props = getattr(session, "properties", None) or {}
    return int(props.get("staging_split_bytes") or DEFAULT_SPLIT_BYTES)


# (connector -> {(schema, table): (estimate, monotonic stamp)}): the
# estimate is consulted on three paths per query (coordinator split
# assignment, phase-1 host evaluation, the staging loaders) and some
# connectors' table_row_count is a real query (sqlite: COUNT(*)) —
# memoized briefly since split sizing only needs the order of magnitude
# (correctness always comes from data_version keys, never split counts)
_estimate_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_estimate_lock = threading.Lock()
_ESTIMATE_TTL_S = 10.0


def estimated_table_bytes(conn, schema: str, table: str) -> Optional[int]:
    """Row-count × FULL-table-width estimate (8 bytes/column —
    dictionary codes and narrowed ints are smaller, limbed decimals
    bigger; split sizing only needs the order of magnitude). Width comes
    from the table metadata, NOT the scan's projection: split boundaries
    must be projection-INVARIANT so two scans of the same table (Q18's
    double lineitem read) request identical ranges and the generator
    range cache (connector/gencache.py) accumulates their columns in one
    entry instead of re-synthesizing per projection."""
    now = time.monotonic()
    try:
        with _estimate_lock:
            per = _estimate_cache.get(conn)
            hit = per.get((schema, table)) if per else None
    except TypeError:  # non-weakrefable connector: probe uncached
        per, hit = None, None
    if hit is not None and now - hit[1] <= _ESTIMATE_TTL_S:
        return hit[0]
    try:
        rows = conn.table_row_count(schema, table)
    except Exception:  # noqa: BLE001 — stats are best-effort
        rows = None
    if not rows:
        est = None
    else:
        try:
            meta = conn.get_table(schema, table)
            width = len(meta.columns) if meta is not None else None
        except Exception:  # noqa: BLE001
            width = None
        est = int(rows) * 8 * max(int(width or 4), 1)
    try:
        with _estimate_lock:
            _estimate_cache.setdefault(conn, {})[(schema, table)] = (est, now)
    except TypeError:
        pass
    return est


def target_split_count(session, conn, schema: str, table: str,
                       floor: int = 1, handle=None) -> int:
    """Adaptive ``get_splits`` target: ceil(estimated bytes /
    staging_split_bytes), clamped to [floor, MAX_TARGET_SPLITS]. Unknown
    row counts keep the caller's floor (no fan-out gamble on tables the
    connector cannot size). A pushdown ``handle`` disables the
    adaptation entirely (the caller's floor stands): a pushed
    aggregation/TopN/limit is a GLOBAL statement whose guarantee would
    become per-split — the guard lives HERE so no call site can forget
    it."""
    if handle is not None:
        return max(1, floor)
    est = estimated_table_bytes(conn, schema, table)
    if est is None:
        return max(1, floor)
    per = max(1, split_bytes_target(session))
    target = (est + per - 1) // per
    return max(max(1, floor), min(MAX_TARGET_SPLITS, int(target)))


# ------------------------------------------------------------- fan-out
# scan_one marker: this split is mid-flight in ANOTHER staging; the
# calling thread joins that flight after the fan-out drains
_INFLIGHT = object()


@dataclasses.dataclass
class StageProfile:
    """Per-staging timing/disposition record. ``scan_s``/``prune_s`` are
    CUMULATIVE thread seconds (the host work done, however overlapped);
    the ``*_wall_s`` fields are calling-thread wall. overlap =
    (scan_s + prune_s) / fanout_wall_s > 1 means the fan-out genuinely
    ran split reads concurrently."""

    splits: int = 0
    parallelism: int = 1
    host_hits: int = 0
    scan_s: float = 0.0
    prune_s: float = 0.0
    hostcache_wall_s: float = 0.0
    fanout_wall_s: float = 0.0
    decode_wall_s: float = 0.0
    transfer_wall_s: float = 0.0
    transfer_blocks: int = 0

    def overlap(self) -> float:
        if self.fanout_wall_s <= 0:
            return 0.0
        return (self.scan_s + self.prune_s) / self.fanout_wall_s


def _map_ordered(fn: Callable[[int], object], n: int, width: int) -> List:
    """Run ``fn(0..n-1)`` with at most ``width`` in flight on the shared
    pool, returning results in index order (completion order never leaks
    into the output — the bit-identity contract). width<=1 degrades to
    the plain serial loop."""
    if width <= 1 or n <= 1:
        return [fn(i) for i in range(n)]
    from concurrent.futures import FIRST_COMPLETED, wait

    pool = staging_pool()
    results: List = [None] * n
    pending = {}
    nxt = 0
    try:
        while nxt < n and len(pending) < width:
            pending[pool.submit(fn, nxt)] = nxt
            nxt += 1
        while pending:
            done, _ = wait(list(pending), return_when=FIRST_COMPLETED)
            for fut in done:
                i = pending.pop(fut)
                results[i] = fut.result()  # re-raises the worker error
                if nxt < n:
                    pending[pool.submit(fn, nxt)] = nxt
                    nxt += 1
    finally:
        for fut in pending:
            fut.cancel()
    return results


def stage_splits(session, node, conn, splits, constraint,
                 prune: Optional[Callable] = None,
                 applied_domains: Optional[Dict] = None,
                 ) -> Tuple[List[Dict], StageProfile]:
    """Scan + decode every split, pipelined: host-tier probe first (hits
    skip the connector), then the missing splits fan out over the shared
    pool — each running ``conn.scan`` + ``prune`` (the tier's host-applied
    domain subset, which is also baked into the host-cache key) and
    filling the host tier single-flighted. Returns the per-split decoded
    column dicts IN SPLIT ORDER plus the profile."""
    from trino_tpu import devcache

    prof = StageProfile(splits=len(splits),
                        parallelism=staging_parallelism(session))
    if not splits:
        return [], prof
    datas: List = [None] * len(splits)
    keys = devcache.host_split_keys(session, node, constraint,
                                    applied_domains or {}, splits)
    if any(k is not None for k in keys):
        t0 = time.perf_counter()
        with tracing.span("staging/host-cache", table=node.table) as sp:
            for i, k in enumerate(keys):
                if k is None:
                    continue
                ent = devcache.HOST_CACHE.peek(k)
                if ent is not None:
                    datas[i] = ent.value
                    prof.host_hits += 1
            sp.set("hits", prof.host_hits)
            sp.set("splits", len(splits))
        prof.hostcache_wall_s = time.perf_counter() - t0
        M.STAGING_PHASE_SECONDS.inc(prof.hostcache_wall_s, "host-cache")
    missing = [i for i in range(len(splits)) if datas[i] is None]
    if not missing:
        return datas, prof
    acc_lock = threading.Lock()
    columns = list(node.column_names)

    def make_loader(i: int):
        def loader():
            t0 = time.perf_counter()
            data = conn.scan(splits[i], columns, constraint=constraint)
            t1 = time.perf_counter()
            if prune is not None:
                (data,) = prune([data])
            t2 = time.perf_counter()
            with acc_lock:
                prof.scan_s += t1 - t0
                prof.prune_s += t2 - t1
            rows = len(next(iter(data.values())).values) if data else 0
            return data, rows, devcache.split_data_bytes(data), 1

        return loader

    def scan_one(i: int):
        loader = make_loader(i)
        if keys[i] is not None:
            # wait=False: a split another staging is already loading must
            # not park this shared-pool thread behind that flight (one
            # slow cold staging would otherwise pin every pool slot and
            # freeze the process's whole staging plane) — in-flight
            # splits resolve on the calling thread below
            ent, _disposition = devcache.HOST_CACHE.lookup_or_stage(
                keys[i], loader, wait=False,
                admit_bytes=devcache.host_admit_budget(session))
            return ent.value if ent is not None else _INFLIGHT
        return loader()[0]

    t0 = time.perf_counter()
    with tracing.span("staging/scan", table=node.table) as sp:
        for j, data in zip(missing,
                           _map_ordered(lambda k: scan_one(missing[k]),
                                        len(missing), prof.parallelism)):
            datas[j] = data
        for j in missing:
            if datas[j] is _INFLIGHT:
                # follower wait happens HERE, on the staging's own calling
                # thread — bounded by FLIGHT_WAIT_S with the stuck-leader
                # bypass, and never occupying a shared pool slot
                ent, _disposition = devcache.HOST_CACHE.lookup_or_stage(
                    keys[j], make_loader(j),
                    admit_bytes=devcache.host_admit_budget(session))
                datas[j] = ent.value
        prof.fanout_wall_s = time.perf_counter() - t0
        sp.set("splits", len(missing))
        sp.set("parallelism", prof.parallelism)
        sp.set("scan_s", round(prof.scan_s, 6))
        sp.set("prune_s", round(prof.prune_s, 6))
        sp.set("overlap", round(prof.overlap(), 3))
    M.STAGING_PHASE_SECONDS.inc(prof.fanout_wall_s, "scan")
    return datas, prof


# ----------------------------------------------------------- assembly
def assemble_host_columns(column_names, column_types, datas):
    """Concat the per-split decoded columns host-side (merging varchar
    dictionaries via spi.concat_column_data — split order is preserved,
    so sortedness survives and the result is bit-identical to a serial
    single-shot scan). Returns the ColumnData list, or None for the
    empty/all-dead case."""
    from trino_tpu.connector.spi import concat_column_data

    if not datas:
        return None
    cols = []
    for name in column_names:
        cols.append(concat_column_data([d[name] for d in datas]))
    if cols and len(np.asarray(cols[0].values)) == 0:
        return None
    return cols


def blocked_transfer(profile: Optional[StageProfile] = None,
                     block_bytes: int = TRANSFER_BLOCK_BYTES):
    """A ``transfer(np.ndarray) -> device array`` that double-buffers:
    rows chunk into ~``block_bytes`` blocks, every block's async
    ``jax.device_put`` is issued before the first is consumed, and the
    device-side concat assembles them — so DMA of block k+1 overlaps the
    consumption of block k, and the result is bitwise identical to a
    single-shot put. Arrays at/below two blocks take the single-shot fast
    path (no device-side copy for the small-table common case), and
    arrays over BLOCKED_MAX_BYTES do too: the blocked path's device-side
    concat transiently holds blocks + output (~2x the column) regardless
    of the put window — see the constant. The in-flight PUT window is
    what is double-buffered: at most _INFLIGHT_PUTS un-materialized
    host->device copies exist at once, bounding pinned-host/DMA-staging
    pressure while the transfer engine runs ahead of the consumer. The
    rows axis is the LAST axis (flat columns are 1-D; SPMD stacked
    shards are [ndev, rows])."""
    import jax
    import jax.numpy as jnp

    from trino_tpu.obs.flowledger import FLOW_LEDGER
    from trino_tpu.obs.memledger import MEMORY_LEDGER, POOL_DEVICE

    def transfer(arr: np.ndarray):
        arr = np.asarray(arr)
        n = arr.shape[-1] if arr.ndim else 0
        row_bytes = (arr.nbytes // n) if n else 0
        block_rows = max(1, block_bytes // max(1, row_bytes)) if n else 0
        t0 = time.perf_counter()
        if not n or n <= 2 * block_rows or arr.nbytes > BLOCKED_MAX_BYTES:
            out = jnp.asarray(arr)
            FLOW_LEDGER.record_transfer(
                "staging-transfer", "staging", int(arr.nbytes),
                time.perf_counter() - t0, pages=1, src="host", dst="device",
                direction="send", status="single-shot")
            return out
        axis = arr.ndim - 1
        # the blocked path's transient scratch (blocks + concat output,
        # ~2x the column — the BLOCKED_MAX_BYTES comment) is attributed
        # to the ledger's staging owner for its lifetime: this is
        # device-pool pressure the eviction machinery cannot see
        MEMORY_LEDGER.record_event(
            "reserve", POOL_DEVICE, "staging", int(arr.nbytes))
        try:
            blocks = []
            for bi, i in enumerate(range(0, n, block_rows)):
                idx = (slice(None),) * axis + (slice(i, i + block_rows),)
                # force block bi - _INFLIGHT_PUTS resident BEFORE issuing
                # block bi, so at most _INFLIGHT_PUTS un-materialized puts
                # ever exist at once (forcing after the issue would briefly
                # hold one extra)
                if bi >= _INFLIGHT_PUTS:
                    blocks[bi - _INFLIGHT_PUTS].block_until_ready()
                blocks.append(jax.device_put(arr[idx]))
            if profile is not None:
                profile.transfer_blocks += len(blocks)
            out = jnp.concatenate(blocks, axis=axis)
            FLOW_LEDGER.record_transfer(
                "staging-transfer", "staging", int(arr.nbytes),
                time.perf_counter() - t0, pages=len(blocks), src="host",
                dst="device", direction="send", status="blocked")
            return out
        finally:
            MEMORY_LEDGER.record_event(
                "release", POOL_DEVICE, "staging", int(arr.nbytes))

    return transfer


def row_bucket(rows: int) -> int:
    """``rows`` rounded up to one of eight lengths per octave (at most
    12.5% padding). The splits of one table differ by a few rows (TPC-H
    draws the lines of each order), and every distinct length is a
    distinct shape: each operator program behind the scan compiles again
    for each split. Measured on the v5e (PR 25): q6 at tpch.sf1 through
    worker tasks, 12 lineitem splits of 499,146..500,830 rows, 489 XLA
    compiles taking 366 s of a 372 s query."""
    granule = 1 << max(0, int(rows).bit_length() - 4)
    return -(-rows // granule) * granule


def pad_to_row_bucket(column_types, host_cols):
    """``(host_cols padded with zero rows to row_bucket, live rows)``, or
    ``(host_cols, None)`` where nothing is padded: an empty scan, a length
    that is its own bucket, or a nested / two-limb column (their child
    layout is recursive)."""
    if not host_cols or any(typ.is_nested or cd.hi is not None
                            for typ, cd in zip(column_types, host_cols)):
        return host_cols, None
    rows = len(host_cols[0].values)
    extra = row_bucket(rows) - rows
    if not extra:
        return host_cols, None

    def pad(arr):
        arr = np.asarray(arr)
        return np.concatenate([arr, np.zeros(extra, arr.dtype)])

    return [dataclasses.replace(
        cd, values=pad(cd.values),
        nulls=None if cd.nulls is None else pad(cd.nulls))
        for cd in host_cols], rows


def page_from_host_columns(column_types, host_cols, transfer):
    """Host ColumnData list -> device Page: physical int32 narrowing for
    provably-fitting int64 columns (table-wide vrange, the
    data/page.py rule: table-wide ranges keep every split and shard
    dtype-uniform), then the injected transfer per array.
    Nested and two-limb columns take the single-shot path (their
    children/limb layout is recursive)."""
    from trino_tpu.data.page import Column, Page, fits_int32
    from trino_tpu.exec.executor import _column_from_data

    if host_cols is None:
        return Page.all_dead(column_types)
    cols = []
    for typ, cd in zip(column_types, host_cols):
        if typ.is_nested or cd.hi is not None:
            cols.append(_column_from_data(cd))
            continue
        vals = np.asarray(cd.values)
        if vals.dtype == np.int64 and fits_int32(cd.vrange):
            vals = vals.astype(np.int32)
        cols.append(Column(
            typ,
            transfer(vals),
            transfer(np.asarray(cd.nulls)) if cd.nulls is not None else None,
            cd.dictionary,
            cd.vrange,
            ascending=bool(getattr(cd, "sorted", False)),
        ))
    return Page(cols)


def staged_scan_page(session, node, conn, splits, constraint,
                     prune: Optional[Callable] = None,
                     applied_domains: Optional[Dict] = None,
                     bucket_rows: bool = False,
                     ) -> Tuple[object, int, StageProfile]:
    """The whole pipeline for one scan: parallel split reads (host tier
    consulted per split) -> host assembly -> double-buffered transfer.
    Returns ``(Page, scanned_rows, StageProfile)``. This is the loader
    body behind every device-cache miss in the eager/compiled and worker
    tiers (the SPMD tier shares stage_splits + blocked_transfer but owns
    its shard stacking). ``bucket_rows`` (the worker tier, which compiles
    each operator program once per shape) stages at ``row_bucket`` rows."""
    datas, prof = stage_splits(session, node, conn, splits, constraint,
                               prune=prune, applied_domains=applied_domains)
    scanned = sum(
        len(next(iter(d.values())).values) if d else 0 for d in datas)
    t0 = time.perf_counter()
    with tracing.span("staging/decode", table=node.table) as sp:
        host_cols = assemble_host_columns(
            node.column_names, node.column_types, datas)
        prof.decode_wall_s = time.perf_counter() - t0
        sp.set("rows", scanned)
    M.STAGING_PHASE_SECONDS.inc(prof.decode_wall_s, "decode")
    t0 = time.perf_counter()
    with tracing.span("staging/transfer", table=node.table) as sp:
        from trino_tpu.data.page import Page

        live = None
        if bucket_rows:
            host_cols, live = pad_to_row_bucket(node.column_types, host_cols)
        transfer = blocked_transfer(prof)
        page = page_from_host_columns(node.column_types, host_cols, transfer)
        if live is not None:  # the pad is a dead tail, as compact_to leaves
            page = Page(page.columns,
                        transfer(np.arange(page.num_rows) < live),
                        live_prefix=True)
        prof.transfer_wall_s = time.perf_counter() - t0
        sp.set("blocks", prof.transfer_blocks)
    M.STAGING_PHASE_SECONDS.inc(prof.transfer_wall_s, "transfer")
    return page, scanned, prof
