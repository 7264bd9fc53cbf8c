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
- **one host pass and one put an array** — ``put_page`` makes each
  column ready in ONE host pass (narrowed and padded to its row bucket
  at once, or put as it is where it already is), the columns side by
  side on the shared pool, then issues every array's
  ``jax.device_put`` before it waits, once, for the whole page
  (``PagePuts``); the pre-transfer projection (scan's column list) and
  the host-applied constraint pruning mean only needed columns/rows
  cross;
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
import functools
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


class PagePuts:
    """The host->device puts of one staged page, used as a context: each
    array is put ONCE (``jax.device_put``, asynchronous), every array of
    the page is issued before any is waited on, and leaving the context
    waits once for all of them, also when a put failed. Per put: one
    memory-ledger ``reserve`` under the ``staging`` owner while it is in
    flight, released by the wait, and one flow-ledger record on the
    ``staging-transfer`` link. Calling-thread wall seconds: ``host_s``
    making the host arrays ready, ``put_s`` issuing, ``wait_s`` the one
    wait; ``nbytes`` is what crossed, pad included."""

    def __init__(self):
        self.host_s = self.put_s = self.wait_s = 0.0
        self.nbytes = self.count = 0
        self._inflight: List[Tuple[object, int, float]] = []

    def put(self, arr: np.ndarray, sharding=None):
        import jax

        from trino_tpu.obs.memledger import MEMORY_LEDGER, POOL_DEVICE

        t0 = time.perf_counter()
        out = jax.device_put(arr, sharding)
        self.put_s += time.perf_counter() - t0
        nbytes = int(arr.nbytes)
        MEMORY_LEDGER.record_event("reserve", POOL_DEVICE, "staging", nbytes)
        self._inflight.append((out, nbytes, t0))
        self.nbytes += nbytes
        self.count += 1
        return out

    def __enter__(self) -> "PagePuts":
        return self

    def __exit__(self, *exc) -> None:
        """Block until every put is on the device, then charge the
        executing scan's kernel row ``stagingPuts`` with the puts
        (obs/devprofiler.py)."""
        import jax

        from trino_tpu.obs.devprofiler import count_charged
        from trino_tpu.obs.flowledger import FLOW_LEDGER
        from trino_tpu.obs.memledger import MEMORY_LEDGER, POOL_DEVICE

        t0 = time.perf_counter()
        try:
            jax.block_until_ready([out for out, _, _ in self._inflight])
        finally:
            done = time.perf_counter()
            for _out, nbytes, _issued in self._inflight:
                MEMORY_LEDGER.record_event(
                    "release", POOL_DEVICE, "staging", nbytes)
        self.wait_s = done - t0
        for _out, nbytes, issued in self._inflight:
            FLOW_LEDGER.record_transfer(
                "staging-transfer", "staging", nbytes, done - issued,
                pages=1, src="host", dst="device", direction="send",
                status="page")
        count_charged("stagingPuts", self.count)
        self._inflight = []


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


def _one_pass(arr: np.ndarray, rows: int, dtype) -> np.ndarray:
    """``arr`` as ``rows`` rows of ``dtype``: ``arr`` itself where it
    already is, else ONE host pass into a fresh array (an unsafe cast: the
    caller proved the values fit) with a zeroed tail."""
    if arr.dtype == dtype and len(arr) == rows:
        return arr
    out = np.empty(rows, dtype)
    np.copyto(out[:len(arr)], arr, casting="unsafe")
    out[len(arr):] = 0
    return out


def _live_mask(rows: int, live: int) -> np.ndarray:
    mask = np.zeros(rows, bool)
    mask[:live] = True
    return mask


def _put_column(cd, put):
    """A nested / two-limb ColumnData as a device Column, every array put
    as it is (their child layout is recursive: no narrowing, no pad)."""
    from trino_tpu.data.page import Column

    return Column(
        cd.type,
        put(np.asarray(cd.values)),
        put(np.asarray(cd.nulls)) if cd.nulls is not None else None,
        cd.dictionary,
        cd.vrange,
        ascending=bool(getattr(cd, "sorted", False)),
        children=([_put_column(k, put) for k in cd.children]
                  if cd.children is not None else None),
        hi=put(np.asarray(cd.hi)) if cd.hi is not None else None,
    )


def put_page(column_types, host_cols, bucket_rows: bool = False,
             width: int = 1) -> Tuple[object, PagePuts]:
    """Host ColumnData list -> device Page, each array made ready in ONE
    host pass and put ONCE, one wait for the page. A provably-fitting
    int64 column is narrowed to int32 (table-wide vrange, the
    data/page.py rule: table-wide ranges keep every split and shard
    dtype-uniform). With ``bucket_rows`` (the worker tier, which compiles
    each operator program once per shape) the page is ``row_bucket`` rows
    long, the pad zeros and a dead tail under a ``live_prefix`` mask, as
    ``compact_to`` leaves one; a page holding a nested or two-limb column
    is not padded. The host arrays are made on the shared staging pool,
    ``width`` at a time (numpy's copies release the interpreter lock).
    Returns ``(Page, PagePuts)``; ``host_cols`` None is the all-dead
    page."""
    from trino_tpu.data.page import Column, Page, fits_int32

    with PagePuts() as puts:
        if host_cols is None:
            return Page.all_dead(column_types), puts
        flat = [not (typ.is_nested or cd.hi is not None)
                for typ, cd in zip(column_types, host_cols)]
        live = len(host_cols[0].values)
        rows = row_bucket(live) if bucket_rows and all(flat) else live
        jobs = []
        for is_flat, cd in zip(flat, host_cols):
            if not is_flat:
                continue
            vals = np.asarray(cd.values)
            dtype = (np.int32 if vals.dtype == np.int64
                     and fits_int32(cd.vrange) else vals.dtype)
            jobs.append(functools.partial(_one_pass, vals, rows, dtype))
            if cd.nulls is not None:
                nulls = np.asarray(cd.nulls)
                jobs.append(functools.partial(
                    _one_pass, nulls, rows, nulls.dtype))
        if rows != live:
            jobs.append(functools.partial(_live_mask, rows, live))
        t0 = time.perf_counter()
        host = _map_ordered(lambda i: jobs[i](), len(jobs), width)
        puts.host_s = time.perf_counter() - t0
        arrays = iter([puts.put(a) for a in host])
        cols = []
        for is_flat, typ, cd in zip(flat, column_types, host_cols):
            if not is_flat:
                cols.append(_put_column(cd, puts.put))
                continue
            cols.append(Column(
                typ, next(arrays),
                next(arrays) if cd.nulls is not None else None,
                cd.dictionary, cd.vrange,
                ascending=bool(getattr(cd, "sorted", False))))
        page = (Page(cols, next(arrays), live_prefix=True) if rows != live
                else Page(cols))
    return page, puts


def staged_scan_page(session, node, conn, splits, constraint,
                     prune: Optional[Callable] = None,
                     applied_domains: Optional[Dict] = None,
                     bucket_rows: bool = False,
                     ) -> Tuple[object, int, StageProfile]:
    """The whole pipeline for one scan: parallel split reads (host tier
    consulted per split) -> host assembly -> ``put_page``.
    Returns ``(Page, scanned_rows, StageProfile)``. This is the loader
    body behind every device-cache miss in the eager/compiled and worker
    tiers (the SPMD tier shares stage_splits + PagePuts but owns its
    shard stacking). ``bucket_rows`` (the worker tier, which compiles
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
        page, puts = put_page(node.column_types, host_cols,
                              bucket_rows=bucket_rows,
                              width=prof.parallelism)
        prof.transfer_wall_s = time.perf_counter() - t0
        sp.set("host_s", round(puts.host_s, 6))
        sp.set("put_s", round(puts.put_s, 6))
        sp.set("wait_s", round(puts.wait_s, 6))
        sp.set("puts", puts.count)
        sp.set("bytes", puts.nbytes)
    M.STAGING_PHASE_SECONDS.inc(prof.transfer_wall_s, "transfer")
    return page, scanned, prof
