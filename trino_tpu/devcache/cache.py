"""Warm-HBM device table cache: the worker-side buffer pool.

Reference role: the classical buffer pool (and Trino's split/page caching
proposals) redesigned for the staged-execution model: the unit of caching
is a fully staged DEVICE artifact — an assembled scan ``Page`` (eager /
compiled tiers), a per-split worker page, or the stacked shard arrays of
an SPMD scan — so a warm query skips the whole host pipeline (connector
scan, dynamic-domain pruning, dictionary merge, host->device transfer),
which is half of a q3 at SF 10: ``tpch_sf10.q3`` takes 8.7 s a statement
and ``tpch_sf10_resident.q3``, the same statements with this cache on,
4.1 s (ledger, PR 31).

Correctness comes from the connector SPI's ``data_version()`` token
(trino_tpu/connector/spi.py): the version rides inside every cache key,
so any INSERT/UPDATE/DELETE/DROP/CTAS changes the key and the stale entry
can never be served again (lookup additionally drops same-table entries
whose version moved, reclaiming their HBM immediately). Unversioned
connectors (``data_version() is None`` — e.g. the live ``system``
catalog, or a transaction overlay) bypass the cache entirely.

Memory discipline: the cache is the cluster's REVOCABLE tier.

- byte-budgeted LRU (budget sized from real device memory when
  discoverable, see :func:`device_memory_bytes`);
- ``yield_bytes`` sheds entries under pressure — called by the spill
  decision (exec/memory.py: a query about to spill reclaims cache HBM
  first) and by the worker announce loop when the node's pool is over
  its limit, BEFORE the coordinator's low-memory killer would consider
  killing a query;
- admission is SINGLE-FLIGHT: concurrent queries staging the same table
  produce one transfer — followers park on the leader's flight and are
  served the same entry (the request-coalescing role of any serving
  cache, same shape as cache/result_cache.py).
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

# the single-flight holder is shared with the result cache — ONE
# implementation of the wait/resolve protocol in the tree (its payload
# field is generic: here it carries the CacheEntry)
from trino_tpu.cache.result_cache import _Flight
from trino_tpu.obs import metrics as M

# budget on backends that report no device memory: the CPU meshes of the
# tests. Never a TPU's budget — see device_memory_bytes
DEFAULT_DEVICE_CACHE_BYTES = 256 << 20
# fraction of discovered device memory the cache may hold: running
# queries own the rest (the cache yields even that share under pressure)
DEVICE_MEMORY_FRACTION = 4  # budget = HBM / 4

_device_memory_cell: List = []  # lazily computed once per process


def device_memory_bytes() -> Optional[int]:
    """This process's per-device accelerator memory capacity (HBM bytes),
    or None on a backend that has none to report (the CPU). Sources, in
    order: the ``TRINO_TPU_DEVICE_MEMORY_BYTES`` env override, then the
    backend's ``memory_stats()['bytes_limit']``. A TPU that does not
    report it raises: the CPU-mesh default budget must never be applied
    to a chip in silence. Computed once and cached — the worker announce
    loop reads it every heartbeat."""
    if _device_memory_cell:
        return _device_memory_cell[0]
    cap: Optional[int] = None
    env = os.environ.get("TRINO_TPU_DEVICE_MEMORY_BYTES")
    if env:
        try:
            cap = int(env)
        except ValueError:
            cap = None
    if cap is None:
        import jax

        device = jax.local_devices()[0]
        stats = device.memory_stats()  # None on the CPU backend
        if stats and stats.get("bytes_limit"):
            cap = int(stats["bytes_limit"])
        elif device.platform == "tpu":
            raise RuntimeError(
                f"{device} reports no memory_stats()['bytes_limit'] "
                f"(got {stats!r}): cannot size the device cache")
    _device_memory_cell.append(cap)
    return cap


def _default_budget() -> int:
    env = os.environ.get("TRINO_TPU_DEVICE_CACHE_BYTES")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    cap = device_memory_bytes()
    if cap:
        return max(cap // DEVICE_MEMORY_FRACTION, 64 << 20)
    return DEFAULT_DEVICE_CACHE_BYTES


@dataclasses.dataclass(frozen=True)
class CacheKey:
    """Identity of one staged device artifact. ``signature`` digests the
    projection, pushdown handle, the connector-enforced part of the
    effective constraint, and the host-applied dynamic domains
    (trino_tpu/devcache/keys.py); ``shard`` distinguishes
    staging shapes of the same table (whole-table vs a worker task's split
    set vs an SPMD mesh width); ``conn_token`` pins process-local
    connectors (the memory connector's version counter is instance state —
    two sessions' private catalogs must never alias)."""

    catalog: str
    schema: str
    table: str
    data_version: str
    signature: str
    shard: str
    conn_token: int = 0

    def table_id(self) -> Tuple[str, str, str, int]:
        return (self.catalog, self.schema, self.table, self.conn_token)


@dataclasses.dataclass
class CacheEntry:
    """One resident entry: ``value`` is the tier-specific staged artifact
    (Page, or (arrays, spec, rows) for SPMD), ``rows`` the live staged
    rows it holds, ``nbytes`` its exact device bytes."""

    key: CacheKey
    value: object
    rows: int
    nbytes: int
    splits: int = 0
    hits: int = 0
    created_at: float = 0.0
    last_used_at: float = 0.0
    # resource group whose query staged this entry (None outside a lane):
    # drives the per-group carve-out eviction preference and the ledger
    # owner suffix (``device-cache:<group>``)
    group: Optional[str] = None


def _current_group() -> Optional[str]:
    """The resource group of the query running on THIS thread (set by the
    dispatcher lane around execution), or None outside a lane. Lazy so the
    cache stays importable without the server package."""
    try:
        from trino_tpu.server.resource_groups import current_group

        return current_group()
    except Exception:  # noqa: BLE001 — attribution never fails staging
        return None




class DeviceTableCache:
    """Byte-budgeted LRU of staged device tables with single-flight
    admission and version-based invalidation. The metric hooks are class
    attributes so the host-RAM tier (devcache/hostcache.py) reuses the
    whole LRU/flight/invalidation machinery under its own counters."""

    # followers give a slow leader this long before re-staging themselves
    # (staging alone is tens of seconds at sf10)
    FLIGHT_WAIT_S = 600.0

    M_HITS = M.DEVICE_CACHE_HITS
    M_MISSES = M.DEVICE_CACHE_MISSES
    M_EVICTIONS = M.DEVICE_CACHE_EVICTIONS
    M_BYTES = M.DEVICE_CACHE_BYTES

    # memory-ledger attribution (obs/memledger.py): which pool this
    # tier's bytes live in and the owner its events carry — the host
    # tier overrides both
    LEDGER_POOL = "device"
    LEDGER_OWNER = "device-cache"

    def __init__(self, max_bytes: Optional[int] = None):
        self._max_bytes = max_bytes
        self._lock = threading.Lock()
        self._entries: "OrderedDict[CacheKey, CacheEntry]" = OrderedDict()
        self._bytes = 0
        self._flights: Dict[CacheKey, _Flight] = {}
        # table_id -> resident keys: keeps the per-lookup stale-version
        # sweep O(entries-for-this-table), not O(all entries) under the
        # global lock (worker split-set shards accumulate many keys)
        self._by_table: Dict[tuple, set] = {}
        # lifetime hit count of THIS pool (the worker announce payload's
        # per-tier column — the process-global metric cannot distinguish
        # tiers once both exist)
        self._hit_count = 0
        # resident bytes per resource group (None = ungrouped): the
        # carve-out ground truth the over-share eviction preference and
        # ``system.runtime.resource_groups`` read
        self._group_bytes: Dict[Optional[str], int] = {}

    def _default_max_bytes(self) -> int:
        """Budget when the constructor did not pin one (subclass hook)."""
        return _default_budget()

    def _ledger_event(self, kind: str, nbytes: int,
                      reason: Optional[str] = None,
                      group: Optional[str] = None) -> None:
        """One memory-ledger event for this tier. Callers MUST have
        released ``self._lock`` first (the emission discipline
        ``tools/lint/lock_discipline.py`` enforces): bytes are collected
        inside the lock, the event is emitted after — which is also what
        gives pressure sheds their exactly-one-event contract. Entries
        staged under a resource group carry the group as an owner SUFFIX
        (``device-cache:<group>``) symmetric across admit/evict/shed, so
        the ledger's live bytes attribute carve-out occupancy per tenant;
        ungrouped entries keep the bare tier owner."""
        if nbytes <= 0:
            return
        from trino_tpu.obs.memledger import MEMORY_LEDGER

        owner = (f"{self.LEDGER_OWNER}:{group}" if group
                 else self.LEDGER_OWNER)
        MEMORY_LEDGER.record_event(
            kind, self.LEDGER_POOL, owner, nbytes, reason=reason)

    def _ledger_events(self, kind: str, by_group: Dict[Optional[str], int],
                       reason: Optional[str] = None) -> None:
        """Per-group ledger emission for a batch of freed entries: one
        event per owning group (lock released first, as above)."""
        for group, nbytes in by_group.items():
            self._ledger_event(kind, nbytes, reason=reason, group=group)

    # ---------------------------------------------------------- inspection
    @property
    def max_bytes(self) -> int:
        if self._max_bytes is None:
            self._max_bytes = self._default_max_bytes()
        return self._max_bytes

    def hit_count(self) -> int:
        with self._lock:
            return self._hit_count

    def cached_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def group_bytes(self) -> Dict[Optional[str], int]:
        """Resident bytes per owning resource group (None = ungrouped) —
        the carve-out occupancy snapshot."""
        with self._lock:
            return dict(self._group_bytes)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def snapshot(self) -> List[dict]:
        """Row-shaped entry list (system.runtime.device_cache), MRU
        first."""
        with self._lock:
            entries = list(reversed(self._entries.values()))
        return [
            {
                "catalog": e.key.catalog,
                "schema": e.key.schema,
                "table": e.key.table,
                "version": e.key.data_version,
                "shard": e.key.shard,
                "signature": e.key.signature,
                "bytes": e.nbytes,
                "rows": e.rows,
                "hits": e.hits,
                "createdAt": e.created_at,
                "lastUsedAt": e.last_used_at,
            }
            for e in entries
        ]

    # ----------------------------------------------------------- lifecycle
    def lookup_or_stage(
        self, key: CacheKey, loader: Callable[[], Tuple[object, int, int, int]],
        admit_bytes: Optional[int] = None, wait: bool = True,
    ) -> Tuple[Optional[CacheEntry], str]:
        """``(entry, "hit"|"miss")``. ``loader() -> (value, rows, nbytes,
        splits)`` runs OUTSIDE the cache lock (staging is the slow path);
        concurrent callers of the same key single-flight: exactly one
        loader runs, followers are served its entry as hits (they paid no
        transfer). A failed leader wakes followers empty-handed and they
        race again.

        ``wait=False``: when another caller is already staging this key,
        return ``(None, "inflight")`` immediately instead of parking as a
        follower. Shared-pool worker threads use this so one slow staging
        can never pin every pool slot behind its flight (the staging
        fan-out, exec/staging.py) — the caller re-resolves in-flight keys
        on its OWN thread afterwards with a blocking call."""
        while True:
            inflight = False
            with self._lock:
                stale_freed = self._drop_stale_locked(key)
                ent = self._entries.get(key)
                if ent is not None:
                    self._entries.move_to_end(key)
                    ent.hits += 1
                    ent.last_used_at = time.time()
                    self._hit_count += 1
                    self.M_HITS.inc()
                else:
                    flight = self._flights.get(key)
                    if flight is None:
                        flight = self._flights[key] = _Flight()
                        lead = True
                    else:
                        if not wait:
                            inflight = True
                        lead = False
            self._ledger_events("evict", stale_freed, reason="stale")
            if ent is not None:
                return ent, "hit"
            if inflight:
                return None, "inflight"
            if not lead:
                if not flight.wait(self.FLIGHT_WAIT_S):
                    # the leader is alive but STUCK (e.g. blocked in a
                    # connector read): bypass the pool and stage privately
                    # rather than hanging every query on that table behind
                    # one wedged staging
                    value, rows, nbytes, splits = loader()
                    now = time.time()
                    self.M_MISSES.inc()
                    return CacheEntry(key, value, rows, int(nbytes), splits,
                                      created_at=now, last_used_at=now), "miss"
                if flight.ok and flight.value is not None:
                    ent = flight.value
                    with self._lock:
                        ent.hits += 1
                        ent.last_used_at = time.time()
                        self._hit_count += 1
                    self.M_HITS.inc()
                    return ent, "hit"
                continue  # leader failed: race for leadership
            try:
                value, rows, nbytes, splits = loader()
            except BaseException:
                with self._lock:
                    flight = self._flights.pop(key, None)
                if flight is not None:
                    flight._resolve(None, ok=False)
                raise
            now = time.time()
            ent = CacheEntry(key, value, rows, int(nbytes), splits,
                             created_at=now, last_used_at=now)
            self._admit(ent, admit_bytes)
            with self._lock:
                flight = self._flights.pop(key, None)
            if flight is not None:
                flight._resolve(ent, ok=True)
            self.M_MISSES.inc()
            return ent, "miss"

    def peek(self, key: CacheKey) -> Optional[CacheEntry]:
        """Resident entry for ``key`` (counted + LRU-bumped as a hit), or
        None — WITHOUT staging on a miss and without joining a flight. The
        staging pipeline probes the host tier this way up front (under the
        ``staging/host-cache`` span) and routes only the missing splits
        into the scan fan-out; a racing ``lookup_or_stage`` on the same
        key stays correct (it re-checks residency under the lock)."""
        with self._lock:
            stale_freed = self._drop_stale_locked(key)
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                ent.hits += 1
                ent.last_used_at = time.time()
                self._hit_count += 1
        self._ledger_events("evict", stale_freed, reason="stale")
        if ent is None:
            return None
        self.M_HITS.inc()
        return ent

    def _admit(self, ent: CacheEntry, admit_bytes: Optional[int]) -> None:
        """Admit under the budget. The session's ``admit_bytes`` is a
        PER-ENTRY size filter only — over-cap entries are returned to the
        caller but not retained; the eviction loop always targets the
        shared server-wide budget, so one tenant's tight cap can never
        flush other tenants' warm tables."""
        cap = (self.max_bytes if admit_bytes is None
               else min(self.max_bytes, int(admit_bytes)))
        if ent.nbytes > cap:
            return
        if ent.group is None:
            ent.group = _current_group()
        evicted: Dict[Optional[str], int] = {}
        with self._lock:
            replaced = self._remove_locked(ent.key)
            while self._bytes + ent.nbytes > self.max_bytes and self._entries:
                nbytes, group = self._evict_victim_locked()
                evicted[group] = evicted.get(group, 0) + nbytes
            self._entries[ent.key] = ent
            self._bytes += ent.nbytes
            self._group_bytes[ent.group] = (
                self._group_bytes.get(ent.group, 0) + ent.nbytes)
            self._by_table.setdefault(ent.key.table_id(), set()).add(ent.key)
            self.M_BYTES.set(self._bytes)
        # ledger emission happens OUTSIDE the lock: bytes collected above,
        # one aggregated evict event per victim group for however many
        # LRU/over-share victims made room
        self._ledger_events("evict", evicted, reason="lru")
        if replaced is not None:
            self._ledger_event("release", replaced.nbytes, reason="replace",
                               group=replaced.group)
        self._ledger_event("admit", ent.nbytes, group=ent.group)

    def _remove_locked(self, key: CacheKey) -> Optional[CacheEntry]:
        ent = self._entries.pop(key, None)
        if ent is None:
            return None
        self._bytes -= ent.nbytes
        remaining = self._group_bytes.get(ent.group, 0) - ent.nbytes
        if remaining > 0:
            self._group_bytes[ent.group] = remaining
        else:
            self._group_bytes.pop(ent.group, None)
        keys = self._by_table.get(key.table_id())
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_table[key.table_id()]
        return ent

    def _evict_victim_locked(self) -> Tuple[int, Optional[str]]:
        """Evict one entry and return ``(bytes, group)``. Carve-out
        preference: the oldest entry belonging to a group holding MORE
        than its configured cache share goes first, so one tenant's
        staging storm reclaims its own over-share bytes before touching
        another tenant's warm state; plain LRU head when nobody is over
        (or no shares are configured)."""
        victim_key = None
        try:
            from trino_tpu.server.resource_groups import CACHE_SHARES

            for k, e in self._entries.items():  # LRU order
                if CACHE_SHARES.over_share(
                        e.group, self._group_bytes.get(e.group, 0),
                        self.max_bytes):
                    victim_key = k
                    break
        except Exception:  # noqa: BLE001 — carve-outs never wedge eviction
            victim_key = None
        if victim_key is None:
            victim_key = next(iter(self._entries))
        victim = self._remove_locked(victim_key)
        self.M_EVICTIONS.inc()
        self.M_BYTES.set(self._bytes)
        return victim.nbytes, victim.group

    def _evict_lru_locked(self) -> int:
        """Back-compat shim over ``_evict_victim_locked`` (bytes only)."""
        nbytes, _ = self._evict_victim_locked()
        return nbytes

    def _drop_stale_locked(self, key: CacheKey) -> Dict[Optional[str], int]:
        """Drop every entry of the same table whose data_version differs
        from the version the caller just observed: a mutation moved the
        version, so those arrays can never be served again — reclaim
        their HBM now instead of waiting for LRU age-out. Returns bytes
        freed per owning group so the caller can emit the ledger events
        AFTER releasing the lock."""
        keys = self._by_table.get(key.table_id())
        if not keys:
            return {}
        stale = [k for k in keys if k.data_version != key.data_version]
        freed: Dict[Optional[str], int] = {}
        for k in stale:
            victim = self._remove_locked(k)
            if victim is not None:
                freed[victim.group] = (
                    freed.get(victim.group, 0) + victim.nbytes)
            self.M_EVICTIONS.inc()
        if stale:
            self.M_BYTES.set(self._bytes)
        return freed

    # ------------------------------------------------------------ pressure
    def yield_bytes(self, nbytes: int, reason: str = "yield") -> int:
        """Revocable-tier contract: shed at least ``nbytes`` of cached
        tables (LRU-first) for a running query's benefit; returns the
        bytes actually freed. Never blocks on staging flights. Each call
        that frees anything emits EXACTLY ONE ledger ``shed`` event
        carrying the reclaiming ``reason`` (``spill`` / ``pool-overflow``
        / ``host-pressure`` / ``rss-escalation`` / ...)."""
        if nbytes <= 0:
            return 0
        freed = 0
        by_group: Dict[Optional[str], int] = {}
        with self._lock:
            while freed < nbytes and self._entries:
                n, group = self._evict_victim_locked()
                freed += n
                by_group[group] = by_group.get(group, 0) + n
        self._ledger_events("shed", by_group, reason=reason)
        return freed

    def evict_to(self, target_bytes: int, reason: str = "trim") -> int:
        """Evict LRU entries until the cache holds at most
        ``target_bytes``; returns bytes freed."""
        freed = 0
        by_group: Dict[Optional[str], int] = {}
        with self._lock:
            while self._bytes > max(0, int(target_bytes)) and self._entries:
                n, group = self._evict_victim_locked()
                freed += n
                by_group[group] = by_group.get(group, 0) + n
        self._ledger_events("evict", by_group, reason=reason)
        return freed

    def invalidate_all(self) -> None:
        with self._lock:
            by_group = dict(self._group_bytes)
            self._entries.clear()
            self._by_table.clear()
            self._group_bytes.clear()
            self._bytes = 0
            self.M_BYTES.set(0)
        self._ledger_events("release", by_group, reason="invalidate")


# the process-wide pool: coordinator-local execution, the compiled tier,
# and every task on a worker share one budget (one device per process)
DEVICE_CACHE = DeviceTableCache()


# --------------------------------------------------- connector identity
# Process-local connectors (coordinator_only: the memory connector, whose
# version counter is instance state) get a per-instance token so two
# sessions' PRIVATE catalog maps never alias in the cache. Monotonic ids
# (never reused, unlike id()) via a weak map: a collected connector's
# entries become unreachable keys and age out by LRU.
_conn_tokens: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_conn_token_lock = threading.Lock()
_conn_token_next = [1]


def instance_token(conn) -> int:
    """0 for connectors whose data_version is globally meaningful (file
    state, immutable generators); a unique per-instance token for
    process-local ones."""
    if not getattr(conn, "coordinator_only", False):
        return 0
    with _conn_token_lock:
        tok = _conn_tokens.get(conn)
        if tok is None:
            tok = _conn_tokens[conn] = _conn_token_next[0]
            _conn_token_next[0] += 1
        return tok
