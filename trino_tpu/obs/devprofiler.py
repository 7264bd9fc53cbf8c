"""Device execution profiler: kernel ledger, compile ledger, utilization.

Reference role: the device half of Trino's operator stats — Trino's
``OperatorStats`` carries ``addInputWall``/``getOutputWall`` per driver;
here the analogous split is *wall vs device* per dispatch.  PAPER.md's
framing maps Trino's runtime codegen onto XLA/Pallas compilation, which
makes compile events and kernel launches first-class engine work.  The
phase ledger (obs/timeline.py) made every wall-clock millisecond
attributable and the memory ledger (obs/memledger.py) every byte; this
module attributes the *inside* of the ``device-execute`` and
``device-staging`` phases.

Three stores per process (design mirrors obs/memledger.py: bounded
rings, O(1) append under a short lock, fan-out outside the lock):

- a **kernel ledger** — per-query rollups keyed
  ``(plan_node_id, operator, tier)`` recording launch count, wall
  seconds, device seconds, and input/output bytes.  ``wall − device`` is
  the per-operator dispatch overhead — the number ROADMAP item 2's
  fragment megakernels must beat.  Device seconds are
  ``block_until_ready``-bracketed only when the ``device_profiling``
  session property is on; otherwise they are estimated from wall
  (``estimated=True`` rows) so the serving plane never pays a sync.
- a **compile ledger** — a bounded ring of jit/Pallas compile events,
  each naming its tier (``eager``/``compiled``/``spmd``), plan
  fingerprint (cache/plan_key.py spine), shape signature, compile
  seconds, and cache ``hit``/``miss``.  Mirrored into the flight
  recorder so FAILED-query postmortems show recompile storms.
- a **utilization sampler** — monotonic process counters (launches,
  busy seconds, compiles in flight) sampled on the worker announce tick
  into a watermark-style ring (launches/sec, device-busy fraction).

Kernel rows also count what the host did around the device: every
blocking device->host read goes through :func:`host_read` (``hostSyncs``,
``hostSyncS``, ``d2hBytes``, and the same three per ``site`` under
``hostSyncSites``) and every XLA backend compile is heard by one
``jax.monitoring`` listener (``compiles``, ``compileS``); both charge
the row of the operator that is executing on the thread
(:func:`charge_to`). So do the aggregation bodies of the eager tier
(:func:`count_charged`): ``aggPrograms`` ran as one compiled program
(exec/executor.py ``direct_aggregation``), ``aggEager`` dispatched their
primitives one by one, and ``prefixCompactions``, the pages
``Executor.compact_to`` squeezed to their live rows (positions from
prefix counts, ops/ranks.py ``true_positions``; a page it returns as it
came counts nothing). A Join's row counts ``compactedJoins``: lookup
joins that squeezed the probe's match to the capacity of the Compact
above them BEFORE gathering a build payload
(``Executor.compacted_lookup_join``; that Compact's row still counts the
page under ``prefixCompactions``). An Aggregation's row counts
``colocatedAggs``: executions of a single-step aggregation the fragmenter
finished inside the source fragment that scans its table
(sql/planner/fragmenter.py ``_colocated_aggregation``: no partial/final
cut, no exchange under it). The row of a fragment's ROOT operator counts
``exchangedRows``: the live rows its task handed to its output buffer
(server/task.py ``SqlTask._output_path``), what crosses an exchange, and
``outputFetches``: the pages that path fetched whole, every leaf of the
page's tree in ONE batched read (:func:`host_read_all`, site
``output-fetch``), after which it works on the host copy and never goes
back to the device. A
scan's row counts what the device
cache did for it (devcache/keys.py ``cached_stage``): ``cacheHits`` /
``cacheMisses``
(lookups by disposition; a bypass counts neither) and ``stagedBytes``,
the bytes it copied host -> device (0 on a hit), and ``cacheBypasses``:
scans the cache was on for and did not keep, because the staged table is
over the admission cap (``device_cache_max_bytes``, the pool's budget)
or no key could be made for it, and ``stagingPuts``: the host -> device
puts a fresh staging issued (exec/staging.py ``PagePuts``: one an array
of the page; 0 on a hit). A Join's row counts ``joinProbeSlots``
and ``joinBuildSlots``: the row capacities (static shapes, no read) of
the probe and build pages of every execution, the work its place in the
join ORDER makes it carry whatever the kernels do.

Hot-path contract: ``count_launch`` is a couple of integer adds under
one short lock — safe on the point-lookup serving path.  Metrics and
recorder fan-out happen at *fold* time (query completion) or compile
time (rare), never per-dispatch.

This module is import-clean standalone (stdlib only at import time) so
doc gates can load it without the package/jax; jax, numpy and
``obs/trace.py`` are imported where a function first needs them.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

# compile events are rare (one per fresh jit); 256 ≈ hours of history
COMPILE_CAPACITY = 256
# announce loop samples every 0.5 s -> ~2 minutes of per-node history
UTILIZATION_CAPACITY = 240
# per-query kernel rollups kept after the query folds (LRU). A row is
# about 0.5 KB as a dict and a statement folds 1 to 30 of them: 512
# statements hold under 8 MB. A reader that asks for the last N answered
# statements by ITS clock (the benchmark: 64, from 32 sender threads)
# must find them whatever order the server reached their terminal states
MAX_QUERY_PROFILES = 512

TIERS = ("eager", "compiled", "spmd")


def merge_platforms(a: str, b: str) -> str:
    """Where a kernel row's launches left their output: the ``+``-joined
    set of device platforms (``"host"`` for a numpy array) — anything
    but the accelerator's name alone means some launch computed
    elsewhere."""
    return "+".join(sorted(set(filter(None, a.split("+") + b.split("+")))))


def new_kernel_row(plan_node_id: str, operator: str, tier: str,
                   node_id: Optional[str] = None,
                   estimated: bool = False) -> dict:
    """An empty kernel row: the one place that knows the fields."""
    row = {"planNodeId": plan_node_id, "operator": operator, "tier": tier,
           "launches": 0, "wallS": 0.0, "deviceS": 0.0, "inputBytes": 0,
           "outputBytes": 0, "estimated": estimated, "platform": "",
           "hostSyncs": 0, "hostSyncS": 0.0, "d2hBytes": 0,
           "compiles": 0, "compileS": 0.0, "hostSyncSites": {},
           "aggPrograms": 0, "aggEager": 0,
           "cacheHits": 0, "cacheMisses": 0, "stagedBytes": 0,
           "prefixCompactions": 0, "compactedJoins": 0,
           "colocatedAggs": 0, "exchangedRows": 0, "outputFetches": 0,
           "joinProbeSlots": 0, "joinBuildSlots": 0, "cacheBypasses": 0,
           "stagingPuts": 0}
    if node_id is not None:
        row["nodeId"] = node_id
    return row


def copy_kernel_row(row: dict, **fields) -> dict:
    """A snapshot of ``row`` that shares nothing with it."""
    return dict(row, hostSyncSites={
        k: list(v) for k, v in row.get("hostSyncSites", {}).items()},
        **fields)


def merge_sync_sites(dst: Dict[str, list], sites: Dict[str, list]) -> None:
    """Add ``site -> [count, seconds, bytes]`` tables."""
    # list(): the owning thread may add a site while a status poll merges
    for site, (n, s, b) in list((sites or {}).items()):
        have = dst.setdefault(site, [0, 0.0, 0])
        have[0] += int(n)
        have[1] += float(s)
        have[2] += int(b)


def merge_kernel_rows(dst: Dict[tuple, dict],
                      rows: List[dict]) -> Dict[tuple, dict]:
    """Fold serialized kernel rows (``kernel_rows`` wire shape) into a
    ``(planNodeId, operator, tier, nodeId)``-keyed accumulator."""
    for row in rows or []:
        key = (row.get("planNodeId", ""), row.get("operator", ""),
               row.get("tier", "eager"), row.get("nodeId", ""))
        agg = dst.get(key)
        if agg is None:
            agg = dst[key] = new_kernel_row(key[0], key[1], key[2], key[3])
        for field in ("launches", "inputBytes", "outputBytes", "hostSyncs",
                      "d2hBytes", "compiles", "aggPrograms", "aggEager",
                      "cacheHits", "cacheMisses", "stagedBytes",
                      "prefixCompactions", "compactedJoins",
                      "colocatedAggs", "exchangedRows", "outputFetches",
                      "joinProbeSlots", "joinBuildSlots", "cacheBypasses",
                      "stagingPuts"):
            agg[field] += int(row.get(field, 0))
        for field in ("wallS", "deviceS", "hostSyncS", "compileS"):
            agg[field] += float(row.get(field, 0.0))
        agg["estimated"] = bool(agg["estimated"] or row.get("estimated"))
        agg["platform"] = merge_platforms(agg["platform"],
                                          row.get("platform", ""))
        merge_sync_sites(agg["hostSyncSites"], row.get("hostSyncSites"))
    return dst


def sync_sites_of(rows: List[dict]) -> Dict[str, dict]:
    """The profile's ``hostSyncSites`` block: the rows' per-site tables
    summed, ``site -> {count, seconds, bytes}``."""
    total: Dict[str, list] = {}
    for row in rows:
        merge_sync_sites(total, row.get("hostSyncSites"))
    return {site: {"count": n, "seconds": round(s, 6), "bytes": b}
            for site, (n, s, b) in sorted(total.items())}


# ------------------------------------------------- the row being charged
# The kernel row of the operator executing on this thread (a contextvar,
# like the ambient tracer): host_read and the compile listener add to it.
_CHARGED: "contextvars.ContextVar" = contextvars.ContextVar(
    "trino_tpu_kernel_row", default=None)


@contextlib.contextmanager
def charge_to(row: dict):
    """Charge this thread's device->host reads and compiles to ``row``
    (``new_kernel_row`` shape) until the block ends; nests."""
    token = _CHARGED.set(row)
    try:
        yield row
    finally:
        _CHARGED.reset(token)


def count_charged(field: str, amount: int = 1) -> None:
    """``amount`` more of ``field`` on the kernel row being charged on
    this thread, if there is one."""
    row = _CHARGED.get()
    if row is not None:
        row[field] += amount


def host_read(x, site: str):
    """THE blocking device->host read of the served path: ``x`` as a numpy
    array. A numpy input (or a Python scalar) returns at once and counts
    nothing. A device array is fetched under an explicit
    ``jax.transfer_guard_device_to_host("allow")`` (so the process's guard
    stays silent about it and speaks only of reads that bypass this
    function; a third of ``jax.device_get``'s cost a read), timed, and added
    (count, seconds, bytes) to the charged kernel row and to its
    ``hostSyncSites[site]``; ``site`` is a short static label of the
    call site. A read of ``MIN_STORED_SPAN_S`` and more is also stored as
    a ``host/sync`` span, back-to-back reads of one site as one span
    (``trace.record_burst``)."""
    import numpy as np

    if isinstance(x, (np.ndarray, np.generic, int, float, bool)):
        return np.asarray(x)
    import jax  # lint: allow(jnp-in-host-module) the served path's one device->host read lives with the ledger that counts it; imported on first use, never at module import

    if not isinstance(x, jax.Array):
        return np.asarray(x)
    if isinstance(x, jax.core.Tracer):
        return x  # under a trace there is nothing to read: the caller's
        # conversion raises jax's own concretization error
    return host_read_all([x], site)[0]


def host_read_all(arrays, site: str) -> list:
    """``arrays`` with the device arrays among them as numpy, fetched in ONE
    batched read: every copy is started (``copy_to_host_async``) before the
    first is taken, and the batch is guarded, timed, counted and stored as
    :func:`host_read` says, as ONE ``hostSyncs`` with the summed
    ``d2hBytes`` under ``site``. Whatever else the list holds (numpy
    arrays, None) stays as it is; a list with no device array in it counts
    nothing."""
    import numpy as np

    import jax  # lint: allow(jnp-in-host-module) as host_read, whose read this is

    from trino_tpu.obs import trace as tracing

    out = list(arrays)
    on_device = [i for i, x in enumerate(out)
                 if isinstance(x, jax.Array)
                 and not isinstance(x, jax.core.Tracer)]
    if not on_device:
        return out
    ann = tracing.annotation("host/sync")
    start = time.time()
    t0 = time.perf_counter()
    with jax.transfer_guard_device_to_host("allow"):
        if len(on_device) > 1:
            for i in on_device:
                out[i].copy_to_host_async()
        for i in on_device:
            out[i] = np.asarray(out[i])
    seconds = time.perf_counter() - t0
    if ann is not None:
        ann.__exit__(None, None, None)
    nbytes = sum(int(out[i].nbytes) for i in on_device)
    row = _CHARGED.get()
    if row is not None:
        row["hostSyncs"] += 1
        row["hostSyncS"] += seconds
        row["d2hBytes"] += nbytes
        have = row["hostSyncSites"].setdefault(site, [0, 0.0, 0])
        have[0] += 1
        have[1] += seconds
        have[2] += nbytes
    if seconds >= tracing.MIN_STORED_SPAN_S:
        tracing.record_burst("host/sync", start, seconds, site, nbytes)
    return out


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_hooks_lock = threading.Lock()
_hooks_installed = False


def _on_compile(event: str, duration: float, **_kw) -> None:
    """``jax.monitoring`` listener: an XLA backend compile (or its load
    from the persistent cache) on this thread, charged to the executing
    operator and stored as an ``xla/compile`` span."""
    if event != _BACKEND_COMPILE:
        return
    from trino_tpu.obs import trace as tracing

    row = _CHARGED.get()
    if row is not None:
        row["compiles"] += 1
        row["compileS"] += duration
    tracing.record("xla/compile", time.time() - duration, duration)


def install_process_hooks() -> None:
    """Once per process, at a server's start: the compile listener and the
    GC pause recorder (obs/trace.py). Nothing is registered at import."""
    global _hooks_installed
    with _hooks_lock:
        if _hooks_installed:
            return
        _hooks_installed = True
    import jax  # lint: allow(jnp-in-host-module) registers the compile listener at a server's start, in a process that runs the engine; never at module import

    from trino_tpu.obs import trace as tracing

    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    tracing.GC_RECORDER.install()


class DeviceProfiler:
    """One process's device profiler (coordinator AND every worker —
    same pattern as the per-process memory ledger)."""

    def __init__(self, node_id: str = "",
                 compile_capacity: int = COMPILE_CAPACITY,
                 utilization_capacity: int = UTILIZATION_CAPACITY,
                 max_query_profiles: int = MAX_QUERY_PROFILES):
        self.node_id = node_id
        self._lock = threading.Lock()
        self._compiles: "deque[dict]" = deque(maxlen=compile_capacity)
        self._utilization: "deque[dict]" = deque(
            maxlen=utilization_capacity)
        # queryId -> {(planNodeId, operator, tier, nodeId) -> rollup}
        self._queries: "OrderedDict[str, Dict[tuple, dict]]" = OrderedDict()
        self._max_query_profiles = max_query_profiles
        # monotonic utilization counters (cheap adds on the hot path)
        self._launches_total = 0
        self._busy_s_total = 0.0
        self._compiles_total = 0
        self._compile_inflight = 0
        # previous sample point for rate computation
        self._last_sample_ts: Optional[float] = None
        self._last_launches = 0
        self._last_busy_s = 0.0
        self._recorder = None

    # ------------------------------------------------------------ wiring
    def attach_recorder(self, recorder) -> None:
        """Mirror compile events into the process flight recorder so a
        FAILED-query postmortem shows whether a recompile storm preceded
        the failure (satellite of the flight-recorder contract)."""
        self._recorder = recorder

    # --------------------------------------------------------- hot path
    def count_launch(self, wall_s: float, busy_s: float,
                     n: int = 1) -> None:
        """Zero-sync accounting for one (or ``n``) device dispatches:
        two adds under a short lock, no metrics fan-out.  Safe on the
        point-lookup serving path with ``device_profiling`` off."""
        with self._lock:
            self._launches_total += n
            self._busy_s_total += busy_s if busy_s > 0 else wall_s

    # ----------------------------------------------------- compile ring
    def compile_started(self) -> None:
        with self._lock:
            self._compile_inflight += 1

    def record_compile(self, tier: str, fingerprint: str, shape_sig: str,
                       compile_s: float, cache: str,
                       query_id: str = "", started: bool = False) -> None:
        """Append one compile event (``cache`` is ``"hit"`` or
        ``"miss"``); fan out to the tiered compile-seconds histogram and
        the flight recorder OUTSIDE the ledger lock.

        ``started=True`` pairs with a prior :meth:`compile_started` and
        decrements the in-flight gauge counter."""
        rec = {"ts": time.time(), "nodeId": self.node_id,
               "queryId": query_id, "tier": tier,
               "fingerprint": fingerprint, "shapeSig": shape_sig,
               "compileS": round(float(compile_s), 6), "cache": cache}
        with self._lock:
            self._compiles.append(rec)
            self._compiles_total += 1
            if started and self._compile_inflight > 0:
                self._compile_inflight -= 1
        # fan-out outside the lock — accounting never fails work
        try:
            from trino_tpu.obs import metrics as M

            M.COMPILE_SECONDS_TIERED.observe(float(compile_s), tier, cache)
        except Exception:  # noqa: BLE001 — accounting never fails work
            pass
        if self._recorder is not None:
            try:
                self._recorder.record(
                    "compile", "device/compile-event", tier=tier,
                    cache=cache, fingerprint=fingerprint,
                    shapeSig=shape_sig, compileS=round(float(compile_s), 6),
                    queryId=query_id)
            except Exception:  # noqa: BLE001 — best-effort forensics
                pass

    # ------------------------------------------------------- query fold
    def record_query_kernels(self, query_id: str, rows: List[dict],
                             node_id: Optional[str] = None) -> None:
        """Fold a query's kernel rows (from executors / task rollups)
        into the per-query store, and bump the per-operator launch and
        dispatch-overhead metrics ONCE per fold — not per dispatch."""
        if not rows:
            return
        node = node_id if node_id is not None else self.node_id
        stamped = [dict(r, nodeId=r.get("nodeId") or node) for r in rows]
        with self._lock:
            store = self._queries.get(query_id)
            if store is None:
                store = {}
                self._queries[query_id] = store
                while len(self._queries) > self._max_query_profiles:
                    self._queries.popitem(last=False)
            else:
                self._queries.move_to_end(query_id)
            merge_kernel_rows(store, stamped)
        # metrics fan-out outside the lock, once per fold
        try:
            from trino_tpu.obs import metrics as M

            for row in stamped:
                op = row.get("operator", "")
                launches = int(row.get("launches", 0))
                if launches:
                    M.KERNEL_LAUNCHES.inc(launches, op)
                overhead = max(
                    0.0, float(row.get("wallS", 0.0))
                    - float(row.get("deviceS", 0.0)))
                if overhead > 0:
                    M.KERNEL_DISPATCH_OVERHEAD.inc(overhead, op)
            for site, body in sync_sites_of(stamped).items():
                M.HOST_SYNCS.inc(body["count"], site)
                M.HOST_SYNC_SECONDS.inc(body["seconds"], site)
        except Exception:  # noqa: BLE001 — accounting never fails work
            pass

    # ----------------------------------------------------- announce tick
    def sample_utilization(self) -> dict:
        """One announce-loop tick: turn the monotonic counters into
        launches/sec and device-busy fraction since the last tick."""
        now = time.time()
        with self._lock:
            launches = self._launches_total
            busy_s = self._busy_s_total
            inflight = self._compile_inflight
            prev_ts = self._last_sample_ts
            dt = (now - prev_ts) if prev_ts is not None else 0.0
            d_launches = launches - self._last_launches
            d_busy = busy_s - self._last_busy_s
            self._last_sample_ts = now
            self._last_launches = launches
            self._last_busy_s = busy_s
            sample = {
                "ts": now, "nodeId": self.node_id,
                "launchesTotal": launches,
                "launchesPerS": round(d_launches / dt, 3) if dt > 0 else 0.0,
                "busyFraction": round(min(1.0, d_busy / dt), 4)
                if dt > 0 else 0.0,
                "compileInflight": inflight,
                "compilesTotal": self._compiles_total,
            }
            self._utilization.append(sample)
        return sample

    # ------------------------------------------------------------- reads
    def kernel_rows(self, query_id: Optional[str] = None) -> List[dict]:
        """Per-(query, planNode, operator, tier, node) rollup rows — the
        ``system.runtime.kernels`` source."""
        with self._lock:
            if query_id is not None:
                stores = {query_id: self._queries.get(query_id, {})}
            else:
                stores = {qid: dict(s) for qid, s in self._queries.items()}
            rows = []
            for qid, store in stores.items():
                for agg in store.values():
                    row = copy_kernel_row(agg, queryId=qid)
                    row["dispatchOverheadS"] = round(
                        max(0.0, row["wallS"] - row["deviceS"]), 6)
                    rows.append(row)
        rows.sort(key=lambda r: (r["queryId"], r["planNodeId"],
                                 r["operator"], r["nodeId"]))
        return rows

    def compile_rows(self, query_id: Optional[str] = None,
                     limit: Optional[int] = None) -> List[dict]:
        """Oldest-first copy of the compile ring (optionally filtered
        to one query) — the ``system.runtime.compiles`` source."""
        with self._lock:
            records = list(self._compiles)
        if query_id is not None:
            records = [r for r in records if r.get("queryId") == query_id]
        if limit is not None and len(records) > limit:
            records = records[-limit:]
        return records

    def utilization_rows(self, limit: Optional[int] = None) -> List[dict]:
        with self._lock:
            samples = list(self._utilization)
        if limit is not None and len(samples) > limit:
            samples = samples[-limit:]
        return samples

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return {"launchesTotal": self._launches_total,
                    "busySTotal": round(self._busy_s_total, 6),
                    "compilesTotal": self._compiles_total,
                    "compileInflight": self._compile_inflight}

    def profile_snapshot(self, query_id: str) -> dict:
        """The ``/v1/query/{id}/profile`` block for THIS process: the
        query's kernel rollups + its compile events + recent
        utilization."""
        return {"nodeId": self.node_id,
                "kernels": self.kernel_rows(query_id),
                "compiles": self.compile_rows(query_id),
                "utilization": self.utilization_rows(limit=8)}

    def __len__(self) -> int:
        with self._lock:
            return len(self._compiles)


def shape_signature(arrays) -> str:
    """Short, stable signature of input array shapes/dtypes — the compile
    ledger's ``shapeSig`` (mirrors jit's retrace key conceptually)."""
    import hashlib

    parts = []
    for arr in arrays:
        shape = tuple(getattr(arr, "shape", ()) or ())
        dtype = str(getattr(arr, "dtype", type(arr).__name__))
        parts.append(f"{dtype}{list(shape)}")
    sig = ";".join(parts)
    digest = hashlib.sha256(sig.encode()).hexdigest()[:12]
    return f"{digest}:{len(parts)}"


# the per-process profiler (coordinator AND every worker — same pattern
# as MEMORY_LEDGER); servers stamp node_id at startup
DEVICE_PROFILER = DeviceProfiler()
