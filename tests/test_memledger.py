"""Cluster memory ledger (trino_tpu/obs/memledger.py) + its producers.

Covers the PR's acceptance matrix:

- ledger unit contract: bounded ring, typed kinds (unknown kinds are
  rejected), per-(pool, owner) live/peak accounting, ground-truth
  ``sync_pool`` reconciliation, watermark sampling with per-pool peaks;
- ``memory_snapshot`` (the postmortem block): top-N consumers ranked by
  peak, pool watermark rows, the newest shed events, and the flight-
  recorder mirror for shed events;
- shed-escalation ORDER through the ledger: a node-pressure shed eats
  the host tier (reason ``host-pressure``) before the HBM tier (reason
  ``rss-escalation``), and each tier's yield emits EXACTLY ONE ``shed``
  event;
- per-query attribution: ``MemoryContext(owner=...)`` reserve deltas
  never double-count a growing peak, and ``release`` zeroes live bytes
  while keeping the peak for attribution;
- the FAILED-query postmortem carries the merged memory snapshot naming
  the shed tier and the top consumers.
"""
import itertools
import time

import pytest

from trino_tpu.devcache import DEVICE_CACHE, HOST_CACHE, CacheKey
from trino_tpu.obs.memledger import (
    MEMORY_LEDGER, MemoryLedger, POOL_DEVICE, POOL_HOST, TOTAL_OWNER)


@pytest.fixture(autouse=True)
def fresh_caches():
    DEVICE_CACHE.invalidate_all()
    HOST_CACHE.invalidate_all()
    yield
    DEVICE_CACHE.invalidate_all()
    HOST_CACHE.invalidate_all()


_marker_seq = itertools.count()


def _mark() -> str:
    """Drop a uniquely-owned marker event into the PROCESS ledger so a
    test can read back only its own events: index-slicing the ring by a
    remembered length breaks once the shared ring has wrapped (its
    length pins at capacity while old events fall off the front)."""
    owner = f"test-marker:{next(_marker_seq)}"
    MEMORY_LEDGER.record_event("watermark", POOL_DEVICE, owner, 0)
    return owner


def _events_since(marker: str):
    events = MEMORY_LEDGER.snapshot()
    for i in range(len(events) - 1, -1, -1):
        if events[i]["owner"] == marker:
            return events[i + 1:]
    return events  # marker already evicted: everything left is newer


# ----------------------------------------------------------- unit contract
def test_event_ring_is_bounded():
    led = MemoryLedger(capacity=8)
    for i in range(50):
        led.record_event("reserve", POOL_DEVICE, "query:q", 1)
    assert len(led) == 8
    assert len(led.snapshot()) == 8
    # owner accounting keeps the FULL history even after ring wrap
    row = next(r for r in led.owner_rows() if r["owner"] == "query:q")
    assert row["events"] == 50
    assert row["bytes"] == 50


def test_unknown_event_kind_rejected():
    led = MemoryLedger()
    with pytest.raises(ValueError, match="unknown memory-ledger event kind"):
        led.record_event("borrow", POOL_DEVICE, "query:q", 1)


def test_live_and_peak_accounting():
    led = MemoryLedger()
    led.record_event("reserve", POOL_DEVICE, "query:a", 1000)
    led.record_event("admit", POOL_DEVICE, "device-cache", 400)
    led.record_event("release", POOL_DEVICE, "query:a", 600)
    rows = {r["owner"]: r for r in led.owner_rows()}
    assert rows["query:a"]["bytes"] == 400
    assert rows["query:a"]["peakBytes"] == 1000  # peak survives the release
    assert rows["device-cache"]["bytes"] == 400
    # releases can never drive live bytes negative
    led.record_event("evict", POOL_DEVICE, "device-cache", 9999)
    rows = {r["owner"]: r for r in led.owner_rows()}
    assert rows["device-cache"]["bytes"] == 0
    assert rows["device-cache"]["peakBytes"] == 400


def test_sync_pool_reconciles_to_ground_truth():
    led = MemoryLedger()
    led.record_event("reserve", POOL_DEVICE, "query:done", 500)
    led.record_event("reserve", POOL_DEVICE, "query:live", 300)
    # announce tick: only query:live still holds bytes; the finished
    # query's live bytes drop to 0 but its peak/history stays
    led.sync_pool(POOL_DEVICE, {"query:live": 800}, prefix="query:")
    rows = {r["owner"]: r for r in led.owner_rows()}
    assert rows["query:live"]["bytes"] == 800
    assert rows["query:live"]["peakBytes"] == 800
    assert rows["query:done"]["bytes"] == 0
    assert rows["query:done"]["peakBytes"] == 500


def test_watermark_sampling_tracks_pool_peaks():
    led = MemoryLedger(watermark_capacity=4)
    for total in (100, 900, 300):
        led.sample_watermarks({POOL_DEVICE: total, POOL_HOST: total // 2},
                              rss_bytes=10_000)
    assert led.pool_peaks() == {POOL_DEVICE: 900, POOL_HOST: 450}
    samples = led.watermarks()
    assert len(samples) == 3
    assert samples[-1][POOL_DEVICE] == 300
    assert samples[-1]["rssBytes"] == 10_000
    # the synthetic total rows make attribution computable from the table
    totals = {r["pool"]: r for r in led.owner_rows()
              if r["owner"] == TOTAL_OWNER}
    assert totals[POOL_DEVICE]["bytes"] == 300
    assert totals[POOL_DEVICE]["peakBytes"] == 900
    for _ in range(10):
        led.sample_watermarks({POOL_DEVICE: 1})
    assert len(led.watermarks()) == 4  # watermark ring is bounded too


def test_memory_snapshot_ranks_top_consumers():
    led = MemoryLedger(node_id="n1")
    for owner, peak in (("query:a", 100), ("query:b", 900),
                        ("query:c", 500), ("query:d", 300)):
        led.record_event("reserve", POOL_DEVICE, owner, peak)
    led.sample_watermarks({POOL_DEVICE: 1800})
    led.record_event("shed", POOL_HOST, "host-cache", 64,
                     reason="host-pressure")
    snap = led.memory_snapshot(top=3)
    assert snap["nodeId"] == "n1"
    assert snap["pools"][POOL_DEVICE]["peakBytes"] == 1800
    top = [r["owner"] for r in snap["topConsumers"][POOL_DEVICE]]
    assert top == ["query:b", "query:c", "query:d"]  # ranked, capped at 3
    assert snap["sheds"][-1]["pool"] == POOL_HOST
    assert snap["sheds"][-1]["reason"] == "host-pressure"


def test_shed_events_mirror_into_flight_recorder():
    class FakeRecorder:
        def __init__(self):
            self.records = []

        def record(self, category, name, **attrs):
            self.records.append((category, name, attrs))

    led = MemoryLedger()
    rec = FakeRecorder()
    led.attach_recorder(rec)
    led.record_event("reserve", POOL_DEVICE, "query:q", 10)  # not mirrored
    led.record_event("shed", POOL_DEVICE, "device-cache", 2048,
                     reason="spill")
    assert rec.records == [("memory", "memory/shed",
                            {"pool": POOL_DEVICE, "owner": "device-cache",
                             "bytes": 2048, "reason": "spill"})]


# ------------------------------------------------- shed-escalation ordering
def _fill_both_tiers():
    for i in range(4):
        HOST_CACHE.lookup_or_stage(
            CacheKey("c", "s", f"h{i}", "v1", "sig", f"host:{i}", 1),
            lambda: (object(), 1, 1000, 1))
        DEVICE_CACHE.lookup_or_stage(
            CacheKey("c", "s", f"d{i}", "v1", "sig", "table", 1),
            lambda: (object(), 1, 1000, 1))


def test_shed_escalation_order_in_ledger(monkeypatch):
    """The ledger records the pressure-shed CONTRACT: the host tier sheds
    first under ``host-pressure``, the HBM tier only for the remainder
    under ``rss-escalation``, and each tier's yield emits exactly ONE
    ``shed`` event (bytes are collected under the cache lock, the event
    is emitted once after — the lock-discipline emission rule)."""
    from trino_tpu.devcache import shed_revocable
    from trino_tpu.devcache import hostcache as hc

    monkeypatch.setattr(hc, "_device_memory_host_backed", lambda: True)
    _fill_both_tiers()

    mark = _mark()
    assert shed_revocable(2500) == 3000
    sheds = [r for r in _events_since(mark) if r["kind"] == "shed"]
    # host tier satisfied the request alone: one event, HBM untouched
    assert [(s["pool"], s["owner"], s["bytes"], s["reason"])
            for s in sheds] == [(POOL_HOST, "host-cache", 3000,
                                 "host-pressure")]

    mark = _mark()
    assert shed_revocable(3000) == 3000
    sheds = [r for r in _events_since(mark) if r["kind"] == "shed"]
    # host emptied first (1000 left), THEN the HBM tier for the rest —
    # exactly one event per tier, in escalation order
    assert [(s["pool"], s["owner"], s["bytes"], s["reason"])
            for s in sheds] == [
        (POOL_HOST, "host-cache", 1000, "host-pressure"),
        (POOL_DEVICE, "device-cache", 2000, "rss-escalation")]


def test_shed_that_frees_nothing_emits_no_event(monkeypatch):
    from trino_tpu.devcache import shed_revocable
    from trino_tpu.devcache import hostcache as hc

    monkeypatch.setattr(hc, "_device_memory_host_backed", lambda: True)
    mark = _mark()
    assert shed_revocable(1000) == 0  # both tiers empty
    assert [r for r in _events_since(mark) if r["kind"] == "shed"] == []


# -------------------------------------------------- per-query attribution
def test_memory_context_owner_deltas_never_double_count():
    from trino_tpu.exec.memory import MemoryContext

    ctx = MemoryContext(owner="query:ledger-ut")
    mark = _mark()
    ctx.observe(1000)
    ctx.observe(700)    # below peak: no new reservation
    ctx.observe(1500)   # +500 delta only
    events = [r for r in _events_since(mark)
              if r["owner"] == "query:ledger-ut"]
    assert [(e["kind"], e["bytes"]) for e in events] == [
        ("reserve", 1000), ("reserve", 500)]
    row = next(r for r in MEMORY_LEDGER.owner_rows()
               if r["owner"] == "query:ledger-ut")
    assert row["bytes"] == 1500 and row["peakBytes"] == 1500
    ctx.release()
    row = next(r for r in MEMORY_LEDGER.owner_rows()
               if r["owner"] == "query:ledger-ut")
    assert row["bytes"] == 0
    assert row["peakBytes"] == 1500  # attribution history survives


def test_staging_scratch_attributed_and_released():
    import numpy as np

    from trino_tpu.exec.staging import PagePuts

    # a page's puts are the staging owner's while in flight: one reserve
    # a put when it is issued, released by the page's one wait
    mark = _mark()
    with PagePuts() as puts:
        out = puts.put(np.arange(1024, dtype=np.int64))
        puts.put(np.zeros(512, bool))
    assert out.shape == (1024,)
    events = [r for r in _events_since(mark) if r["owner"] == "staging"]
    assert [(e["kind"], e["bytes"]) for e in events] == [
        ("reserve", 8192), ("reserve", 512), ("release", 8192),
        ("release", 512)]
    row = next(r for r in MEMORY_LEDGER.owner_rows()
               if r["owner"] == "staging" and r["pool"] == POOL_DEVICE)
    assert row["bytes"] == 0  # scratch never outlives the transfer


def test_staging_scratch_released_when_a_page_fails():
    import numpy as np

    from trino_tpu.exec.staging import PagePuts

    mark = _mark()
    with pytest.raises(ValueError):
        with PagePuts() as puts:
            puts.put(np.arange(256, dtype=np.int64))
            raise ValueError("a later column of the page failed")
    events = [r for r in _events_since(mark) if r["owner"] == "staging"]
    assert [(e["kind"], e["bytes"]) for e in events] == [
        ("reserve", 2048), ("release", 2048)]
    assert puts.count == 1


# ------------------------------------------------------------- postmortem
def test_postmortem_names_shed_tier_and_top_consumers():
    """The OOM-postmortem surface: after a forced pressure shed, a
    query's flight-recorder postmortem carries the memory snapshot —
    pool watermarks, top consumers per pool, and the shed events naming
    the shed TIER and reclaiming reason."""
    from trino_tpu.devcache import shed_revocable
    from trino_tpu.server.coordinator import CoordinatorServer

    for i in range(3):
        HOST_CACHE.lookup_or_stage(
            CacheKey("c", "s", f"pm{i}", "v1", "sig", f"host:{i}", 1),
            lambda: (object(), 1, 1000, 1))
    assert shed_revocable(1500) >= 1500  # forced pressure shed

    coord = CoordinatorServer()
    coord.start()
    try:
        # a system-catalog scan runs coordinator-local: no workers needed
        ex = coord.submit("select count(*) from nodes",
                          {"catalog": "system", "schema": "runtime"})
        deadline = time.time() + 60
        while not ex.state.is_terminal() and time.time() < deadline:
            time.sleep(0.05)
        assert ex.state.get() == "FINISHED", ex.failure
        pm = ex.capture_postmortem(store=False)
    finally:
        coord.stop()

    mem = pm["coordinator"]["memory"]
    assert set(mem) == {"nodeId", "pools", "topConsumers", "sheds"}
    shed = next(s for s in reversed(mem["sheds"])
                if s["reason"] == "host-pressure")
    assert shed["pool"] == POOL_HOST and shed["owner"] == "host-cache"
    host_top = mem["topConsumers"].get(POOL_HOST) or []
    assert len(host_top) <= 3
    assert any(r["owner"] == "host-cache" and r["peakBytes"] >= 3000
               for r in host_top)
