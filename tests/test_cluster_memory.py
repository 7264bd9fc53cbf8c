"""Cluster memory management (round-4 verdict item 7): workers report
per-query reservations in their announce, the coordinator aggregates them,
and a worker over its pool triggers the low-memory killer on the largest
query while smaller queries keep running.

Reference test-strategy analog: TestClusterMemoryManager /
TestTotalReservationOnBlockedNodesLowMemoryKiller
(core/trino-main/src/test/java/io/trino/memory/).
"""
import time

import pytest

from trino_tpu import Session
from trino_tpu.server.cluster_memory import (
    ClusterMemoryManager, total_reservation_killer)
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer


def test_killer_policy_picks_largest_reservation():
    assert total_reservation_killer({"a": 10, "b": 99, "c": 5}) == "b"
    assert total_reservation_killer({}) is None


def test_manager_kills_once_per_pressure_window():
    killed = []
    mgr = ClusterMemoryManager(kill=lambda q, r: killed.append((q, r)))
    mgr.update("w0", {"queryMemory": {"q1": 100, "q2": 900},
                      "memoryBytes": 1000, "memoryLimit": 500})
    assert [q for q, _ in killed] == ["q2"]
    assert "EXCEEDED_CLUSTER_MEMORY" in killed[0][1]
    # after forgetting q2's reservations the worker is under limit: the
    # same pressure window must not take a second victim
    mgr.update("w0", {"queryMemory": {"q1": 100},
                      "memoryBytes": 100, "memoryLimit": 500})
    assert len(killed) == 1


def test_revocable_bytes_staleness_guard():
    """A dead worker's cache bytes must not keep counting as reclaimable
    headroom: announces older than STALE_HEARTBEATS missed heartbeats
    drop out of revocable_bytes, and a fresh announce restores them."""
    mgr = ClusterMemoryManager(kill=lambda q, r: None,
                               heartbeat_interval_s=0.05)
    payload = {"queryMemory": {}, "memoryBytes": 0, "memoryLimit": None,
               "deviceCacheBytes": 4096, "hostCacheBytes": 1024}
    mgr.update("w0", payload)
    assert mgr.revocable_bytes() == 5120
    # wait past the staleness horizon (3 missed heartbeats)
    time.sleep(ClusterMemoryManager.STALE_HEARTBEATS * 0.05 + 0.1)
    assert mgr.revocable_bytes() == 0
    mgr.update("w0", payload)  # the worker comes back
    assert mgr.revocable_bytes() == 5120


def test_dispatch_gate_blocks_over_cluster_limit():
    mgr = ClusterMemoryManager(kill=lambda q, r: None,
                               cluster_limit_bytes=1000)
    assert mgr.has_headroom()
    mgr.update("w0", {"queryMemory": {"q": 2000}, "memoryBytes": 2000,
                      "memoryLimit": None})
    assert not mgr.has_headroom()


@pytest.fixture()
def tight_cluster():
    """2-worker cluster whose workers declare a 64 KiB memory pool — any
    real scan blows it, so the killer must fire."""
    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"mw{i}",
                     memory_limit_bytes=64 * 1024)
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _heartbeat(coord, workers):
    """The memory half of a worker's announce tick, run now: sample the
    memory ledger, then tell the killer what is reserved. The announce
    loop beats every 0.5 s and reservations decay when a task body ends: a
    process whose programs are already compiled finishes the join between
    two beats, and a killer that never saw the reservation kills nothing."""
    for w in workers:
        reserved = w.tasks.query_memory()
        coord.cluster_memory.update(w.node_id, {
            "queryMemory": reserved, "memoryBytes": sum(reserved.values()),
            "memoryLimit": w.memory_limit_bytes,
            "memoryOwners": w._sample_memory(reserved, None)})


def _wait_for(condition, what, beat=lambda: None):
    """One generous deadline for every wait of the test: it bounds a hang,
    it is not tuned to how fast an idle box gets there."""
    deadline = time.time() + 300
    while not condition():
        assert time.time() < deadline, f"timed out waiting for {what}"
        beat()
        time.sleep(0.005)


def test_oversized_query_killed_small_query_finishes(tight_cluster):
    coord, workers = tight_cluster
    # a JOIN fragment executes as one bulk unit (split-at-a-time
    # streaming applies only to single-scan chains), so its executor holds
    # multi-MB scan pages while RUNNING — far over the 64 KiB pools
    props = {"catalog": "tpch", "schema": "tiny"}
    big = coord.submit(
        "select o_orderpriority, count(*) c, sum(l_quantity) q "
        "from orders, lineitem where o_orderkey = l_orderkey "
        "group by o_orderpriority order by o_orderpriority", props)
    _wait_for(big.state.is_terminal, "the oversized query to end",
              beat=lambda: _heartbeat(coord, workers))
    assert big.state.get() == "FAILED", big.state.get()
    assert "EXCEEDED_CLUSTER_MEMORY" in (big.failure or ""), big.failure
    assert coord.cluster_memory.kills
    # the FAILED query stores a flight-recorder postmortem whose memory
    # snapshot names per-pool watermarks and top consumers; the terminal
    # event listener captures it asynchronously
    _wait_for(lambda: big.postmortem is not None, "the postmortem")
    pm = big.postmortem
    assert pm and pm["state"] == "FAILED"
    mem = pm["coordinator"]["memory"]
    assert set(mem) == {"nodeId", "pools", "topConsumers", "sheds"}
    assert mem["topConsumers"]  # someone held memory when the query died
    for rows in mem["topConsumers"].values():
        assert 0 < len(rows) <= 3
    # the cluster remains usable: a small query completes normally
    small = coord.submit("select count(*) from nation",
                         {"catalog": "tpch", "schema": "tiny"})
    _wait_for(small.state.is_terminal, "the small query to end")
    assert small.state.get() == "FINISHED", small.failure
    assert small.rows == [(25,)]
