"""Device-memory accounting and the spill decision.

Reference: ``lib/trino-memory-context`` (``AggregatedMemoryContext.java:30``,
``LocalMemoryContext.java:31``) + ``memory/QueryContext.java:58`` — operator
reservations roll up to a per-query pool; exceeding revocable memory
triggers spill (``HashBuilderOperator.java:162-177`` FSM,
``SpillableHashAggregationBuilder``).

TPU-first redesign (SURVEY.md §7.2 step 9): page shapes are static, so
"reservation" is exact arithmetic on array bytes — no JVM-style object
walking. The spill tier is HOST RAM, not disk: an over-budget join or
aggregation hash-partitions its inputs host-side into P passes and runs
each pass on device (the partitioned-spill design of
``GenericPartitioningSpiller`` collapsed into a loop over compiled kernels).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from trino_tpu.obs import trace as tracing
from trino_tpu.obs.memledger import MEMORY_LEDGER, POOL_DEVICE


def page_bytes(page) -> int:
    """Exact device bytes of a Page (static shapes make this precise)."""
    total = 0
    for c in page.columns:
        total += c.values.size * c.values.dtype.itemsize
        if c.nulls is not None:
            total += c.nulls.size  # bool = 1 byte
    if page.sel is not None:
        total += page.sel.size
    return total


@dataclasses.dataclass
class SpillEvent:
    node_id: int
    kind: str  # 'join' | 'aggregation'
    partitions: int
    projected_bytes: int


class MemoryContext:
    """Per-query device-memory budget + peak tracking + spill log.

    ``owner`` is the memory-ledger attribution tag (``query:<id>``):
    when set, every peak INCREASE lands in the process
    :data:`~trino_tpu.obs.memledger.MEMORY_LEDGER` as a ``reserve``
    event for that owner (deltas, so the owner's live bytes track the
    peak), and the spill decision's cache yield is charged to the query
    (``shed_bytes`` / ``yields`` feed queryStats.memory through the
    stats spine)."""

    MAX_SPILL_PARTITIONS = 64

    def __init__(self, budget_bytes: Optional[int] = None,
                 owner: Optional[str] = None):
        self.budget = int(budget_bytes) if budget_bytes else None
        self.owner = owner
        self.peak = 0
        self.spills: List[SpillEvent] = []
        # revocable bytes shed on THIS query's behalf + yield-event count
        self.shed_bytes = 0
        self.yields = 0

    @property
    def enabled(self) -> bool:
        return self.budget is not None

    def observe(self, nbytes: int) -> None:
        if nbytes > self.peak:
            delta = nbytes - self.peak
            self.peak = nbytes
            if self.owner:
                MEMORY_LEDGER.record_event(
                    "reserve", POOL_DEVICE, self.owner, delta)

    def release(self) -> None:
        """Query done: the owner's live bytes drop to zero (its peak and
        event history stay in the ledger for attribution)."""
        if self.owner and self.peak:
            MEMORY_LEDGER.record_event(
                "release", POOL_DEVICE, self.owner, self.peak, reason="done")

    def spill_partitions(self, projected_bytes: int) -> int:
        """1 = fits in budget; else the number of hash partitions (power of
        two) whose per-pass working set fits."""
        self.observe(projected_bytes)
        if self.budget is None or projected_bytes <= self.budget:
            with tracing.span("memory/reserve") as sp:
                sp.set("bytes", int(projected_bytes))
                if self.owner:
                    sp.set("owner", self.owner)
            return 1
        parts = 1
        while parts < self.MAX_SPILL_PARTITIONS and projected_bytes // parts > self.budget:
            parts *= 2
        # the device table cache is the REVOCABLE tier: a query about to
        # pay a spill reclaims warm-table HBM first, so cached tables
        # yield to running work instead of competing with it. The yield is
        # sized to the PER-PASS working set — what will actually be
        # resident once the join runs partitioned — never the raw
        # projection (a 64 GB projection over an 8 GB budget must not
        # flush a whole warm cache its passes will never displace).
        from trino_tpu.devcache import DEVICE_CACHE

        with tracing.span("memory/shed") as sp:
            freed = DEVICE_CACHE.yield_bytes(
                projected_bytes // parts, reason="spill")
            sp.set("requested", int(projected_bytes // parts))
            sp.set("freed", int(freed))
            sp.set("partitions", parts)
            if self.owner:
                sp.set("owner", self.owner)
        self.shed_bytes += freed
        self.yields += 1
        return parts

    def record_spill(self, node_id: int, kind: str, partitions: int, projected: int) -> None:
        self.spills.append(SpillEvent(node_id, kind, partitions, projected))


# ------------------------------------------------- host-side partitioning

_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB
_NULL_HASH = 0x9E3779B97F4A7C15
_MASK = (1 << 64) - 1


def _mix64_np(x):
    import numpy as np

    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(_M1)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(_M2)
        return x ^ (x >> np.uint64(31))


def partition_page_host(page, key_channels, parts: int, pid=None):
    """Split a page into ``parts`` hash partitions by key columns, host-side
    (numpy) — the spill write path. Equal keys co-locate (same splitmix64
    combine as the device exchange, parallel/exchange.py, so a spilled join
    and an exchanged join agree on placement); dead rows are dropped.

    Returns a list of ``parts`` compacted Pages (1-row all-dead when empty).
    """
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.data.page import Column, Page
    from trino_tpu.obs.devprofiler import host_read

    n = page.num_rows
    site = "partition"
    live = np.ones(n, bool) if page.sel is None else host_read(page.sel, site)
    if pid is None:
        h = np.zeros(n, np.uint64)
        for ch in key_channels:
            col = page.columns[ch]
            # hash the LOW limb only: equal values always share it, and a
            # column's hi-limb PRESENCE is data-dependent (one join side may
            # carry it while the other doesn't) — mixing hi in would place
            # equal keys in different partitions across sides/producers
            k = _mix64_np(host_read(col.values, site).astype(np.int64))
            if col.nulls is not None:
                k = np.where(host_read(col.nulls, site),
                             np.uint64(_NULL_HASH), k)
            h = _mix64_np(h ^ k)
        pid = (h % np.uint64(parts)).astype(np.int64)
    else:
        pid = np.asarray(pid)
    from trino_tpu.data.page import host_take

    out = []
    for p in range(parts):
        idx = np.nonzero(live & (pid == p))[0]
        if len(idx) == 0:
            out.append(_pad_like(page))
            continue
        # host_take handles two-limb and nested columns uniformly
        out.append(Page([host_take(c, idx, site=site) for c in page.columns],
                        None, page.replicated))
    return out


def _pad_like(page):
    """1-row all-dead page with the same column dtypes/dictionaries."""
    import jax.numpy as jnp

    from trino_tpu.data.page import Column, Page

    cols = [
        Column(
            c.type,
            jnp.zeros((1,) + c.values.shape[1:], c.values.dtype),
            None,
            c.dictionary,
            c.vrange,
        )
        for c in page.columns
    ]
    return Page(cols, jnp.zeros((1,), bool), page.replicated)
