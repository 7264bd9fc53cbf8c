"""Host-RAM columnar page cache: the staging tier UNDER the warm-HBM pool.

Reference role: the buffer-pool tier hierarchy every disk engine has
(HBM ≈ buffer pool, host RAM ≈ OS page cache), rebuilt for the staged
execution model. The unit of caching is one SPLIT's decoded numpy column
set — the output of ``connector.scan`` + host-applied domain pruning,
BEFORE dictionary-merge/narrowing/transfer — keyed by the same
``(catalog, schema, table, data_version, signature, shard)`` identity the
device cache uses (trino_tpu/devcache/keys.py), with the split's own
boundary digest as the shard component. Because the key is per split, the
host tier survives re-shardings the HBM tier cannot: an HBM eviction, a
mesh-width change, or a different worker split grouping re-stages from
host memory (concat + transfer only) instead of re-running the connector
scan and decode.

Semantics are inherited wholesale from :class:`DeviceTableCache`:
byte-budgeted LRU, SINGLE-FLIGHT admission (concurrent stagings of the
same split run one scan), and data_version invalidation (any
INSERT/UPDATE/DELETE/DROP/CTAS moves the version; stale same-table
entries are reclaimed on the next lookup). Only the metric hooks and the
budget source differ.

Memory discipline: the host tier is the SECOND revocable tier — under
node pressure it sheds BEFORE the HBM tier does (:func:`shed_revocable`):
losing a host page costs one transfer to rebuild; losing a warm HBM page
costs the whole scan→decode→transfer path when the host tier is gone too.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from trino_tpu.devcache.cache import DeviceTableCache
from trino_tpu.obs import metrics as M

# fallback server-wide budget (env TRINO_TPU_HOST_CACHE_BYTES overrides):
# host RAM is plentiful relative to HBM, but the cache must never crowd
# out the engine's own working set
DEFAULT_HOST_CACHE_BYTES = 1 << 30


def _default_budget() -> int:
    env = os.environ.get("TRINO_TPU_HOST_CACHE_BYTES")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    return DEFAULT_HOST_CACHE_BYTES


def column_data_bytes(cd) -> int:
    """Approximate host bytes of one decoded ColumnData (arrays exact,
    dictionary vocab estimated) — the host cache's accounting unit."""
    n = int(np.asarray(cd.values).nbytes)
    if cd.nulls is not None:
        n += int(np.asarray(cd.nulls).nbytes)
    if getattr(cd, "hi", None) is not None:
        n += int(np.asarray(cd.hi).nbytes)
    d = getattr(cd, "dictionary", None)
    if d is not None:
        n += sum(len(v) + 8 for v in d.values)
    for k in getattr(cd, "children", None) or ():
        n += column_data_bytes(k)
    return n


def split_data_bytes(data: dict) -> int:
    """Host bytes of one split's decoded column set."""
    return sum(column_data_bytes(cd) for cd in data.values())


class HostColumnCache(DeviceTableCache):
    """The host-RAM tier: same machinery, host metrics, host budget.
    Entry values are ``{column name: ColumnData}`` dicts of decoded numpy
    arrays — consumers must treat them as immutable (assembly concats and
    narrows into FRESH arrays; nothing writes back)."""

    M_HITS = M.HOST_CACHE_HITS
    M_MISSES = M.HOST_CACHE_MISSES
    M_EVICTIONS = M.HOST_CACHE_EVICTIONS
    M_BYTES = M.HOST_CACHE_BYTES

    # this tier's pages are host RAM: its ledger events land in the host
    # pool under the host-cache owner (obs/memledger.py taxonomy)
    LEDGER_POOL = "host"
    LEDGER_OWNER = "host-cache"

    def _default_max_bytes(self) -> int:
        return _default_budget()


# the process-wide host tier: every staging tier in this process (eager,
# compiled phase-1, SPMD shards, worker task splits) fills and consults
# one pool, exactly like DEVICE_CACHE
HOST_CACHE = HostColumnCache()


def host_admit_budget(session) -> Optional[int]:
    """Per-entry admission cap from the ``host_cache_max_bytes`` session
    property (min-ed with the server-wide budget at admit time — mirrors
    device_cache_max_bytes semantics)."""
    props = getattr(session, "properties", None) or {}
    v = props.get("host_cache_max_bytes")
    return int(v) if v is not None else None


def shed_revocable(nbytes: int) -> int:
    """NODE-level (host-RAM) pressure shed across BOTH revocable tiers,
    host tier first: host pages are the cheapest to rebuild (one
    transfer), warm HBM pages the most valuable to keep (zero work on
    the next query) — so pressure eats the cheap tier before it touches
    the expensive one. The worker invokes this when its process RSS
    crosses ``TRINO_TPU_HOST_MEMORY_LIMIT_BYTES`` (server/worker.py
    announce loop). NOTE: callers that specifically need DEVICE bytes
    back (the device-pool overflow check, the spill path in
    exec/memory.py) must keep calling ``DEVICE_CACHE.yield_bytes``
    directly — freeing host RAM cannot satisfy an HBM reservation, and
    counting host bytes against the device pool would thrash this tier
    for nothing."""
    from trino_tpu.devcache.cache import DEVICE_CACHE

    if nbytes <= 0:
        return 0
    freed = HOST_CACHE.yield_bytes(nbytes, reason="host-pressure")
    if freed < nbytes and _device_memory_host_backed():
        # escalate into the device tier ONLY where its arrays live in
        # host RAM (CPU meshes — no discoverable HBM): there, evicting
        # warm "device" pages genuinely relieves RSS. On a real
        # accelerator they are HBM-resident: evicting them would free
        # device memory, not host RSS, so a persistent RSS overage
        # would thrash the warm tier every announce cycle for nothing.
        # Each tier's yield emits its own single shed event, so the
        # ledger shows the escalation ORDER (host first, then device
        # under the rss-escalation reason).
        freed += DEVICE_CACHE.yield_bytes(nbytes - freed,
                                          reason="rss-escalation")
    return freed


def _device_memory_host_backed() -> bool:
    """True when this process's jax device memory is host RAM (no
    discoverable accelerator HBM) — the precondition for host-RAM
    pressure to escalate into the device tier."""
    from trino_tpu.devcache.cache import device_memory_bytes

    return device_memory_bytes() is None
