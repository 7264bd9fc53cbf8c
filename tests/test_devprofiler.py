"""Device execution profiler (obs/devprofiler.py): units + acceptance.

Acceptance (ISSUE 18): with ``device_profiling`` on, the phase ledger
still attributes >=95% of query wall on (a) a distributed TPC-H Q1 and
(b) a fast-path point query — the profiler's sync bracketing must not
open unattributed holes — and the kernel ledger's per-query device
seconds never exceed the ledger's ``device-execute`` phase.
``system.runtime.kernels`` and ``system.runtime.compiles`` return rows
over real SQL; a rerun of a compiled query records a compile-cache
``hit`` with ZERO new miss events; EXPLAIN ANALYZE VERBOSE carries the
per-node ``launches=``/``dispatch_overhead=`` annotation; and for the point,
q1 and q3 shapes the kernel rows cover most of the device phases.
"""
import time
import urllib.request

import pytest

from trino_tpu.client.remote import StatementClient
from trino_tpu.obs.devprofiler import (
    DeviceProfiler, merge_kernel_rows, shape_signature)
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer

from tests.tpch_sql import QUERIES as TPCH


# ------------------------------------------------------------------ units
def _row(node="3", op="TableScan", tier="eager", nid="w0", launches=1,
         wall=0.01, device=0.002, inb=100, outb=50, estimated=False):
    return {"planNodeId": node, "operator": op, "tier": tier,
            "nodeId": nid, "launches": launches, "wallS": wall,
            "deviceS": device, "inputBytes": inb, "outputBytes": outb,
            "estimated": estimated}


def test_merge_kernel_rows_accumulates_by_key():
    dst = {}
    merge_kernel_rows(dst, [_row(), _row(wall=0.02, launches=2)])
    merge_kernel_rows(dst, [_row(nid="w1", estimated=True)])
    assert len(dst) == 2  # same (node, op, tier) on two NODES stays split
    same = dst[("3", "TableScan", "eager", "w0")]
    assert same["launches"] == 3
    assert same["wallS"] == pytest.approx(0.03)
    assert same["inputBytes"] == 200 and same["outputBytes"] == 100
    assert same["estimated"] is False
    # estimated is sticky-OR: one estimated contribution taints the rollup
    assert dst[("3", "TableScan", "eager", "w1")]["estimated"] is True


def test_shape_signature_tracks_shapes_and_dtypes():
    import numpy as np

    a = [np.zeros((4, 2), np.float32), np.zeros(3, np.int64)]
    assert shape_signature(a) == shape_signature(list(a))
    assert shape_signature(a).endswith(":2")
    assert shape_signature(a) != shape_signature(
        [np.zeros((4, 3), np.float32), np.zeros(3, np.int64)])
    assert shape_signature(a) != shape_signature(
        [np.zeros((4, 2), np.float64), np.zeros(3, np.int64)])


def test_profiler_counters_and_utilization_sampler():
    p = DeviceProfiler(node_id="n1")
    p.count_launch(0.01, 0.0)          # no measured busy: wall estimates
    p.count_launch(0.02, 0.005, n=3)   # measured busy wins
    c = p.counters()
    assert c["launchesTotal"] == 4
    assert c["busySTotal"] == pytest.approx(0.015)
    first = p.sample_utilization()
    assert first["nodeId"] == "n1" and first["launchesPerS"] == 0.0
    time.sleep(0.02)
    p.count_launch(0.001, 0.001)
    second = p.sample_utilization()
    assert second["launchesTotal"] == 5
    assert second["launchesPerS"] > 0
    assert 0.0 <= second["busyFraction"] <= 1.0
    assert p.utilization_rows() == [first, second]


def test_compile_ring_bounded_and_mirrored_to_flight_recorder():
    from trino_tpu.obs.flightrecorder import FlightRecorder

    p = DeviceProfiler(node_id="n1", compile_capacity=4)
    rec = FlightRecorder()
    p.attach_recorder(rec)
    p.compile_started()
    assert p.counters()["compileInflight"] == 1
    for i in range(6):
        p.record_compile("compiled", f"fp{i}", "sig:1", 0.1, "miss",
                         started=(i == 0))
    assert p.counters()["compileInflight"] == 0
    rows = p.compile_rows()
    assert len(rows) == 4  # bounded ring dropped the oldest
    assert [r["fingerprint"] for r in rows] == ["fp2", "fp3", "fp4", "fp5"]
    assert p.counters()["compilesTotal"] == 6
    # the flight-recorder mirror (FAILED-query postmortems see recompile
    # storms) carries the same identifying fields
    mirrored = [r for r in rec.snapshot()
                if r.get("kind") == "compile"]
    assert len(mirrored) == 6
    assert mirrored[-1]["fingerprint"] == "fp5"
    assert mirrored[-1]["cache"] == "miss"


# ------------------------------------------------- acceptance, live cluster
@pytest.fixture(scope="module")
def cluster():
    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"prof-w{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _wait_terminal(q, timeout=90.0):
    deadline = time.time() + timeout
    while not q.state.is_terminal() and time.time() < deadline:
        time.sleep(0.02)
    return q.state.get()


def _profile(coord, query_id):
    import json

    req = urllib.request.Request(
        f"{coord.base_url}/v1/query/{query_id}/profile",
        headers={"X-Trino-User": "test"})
    return json.loads(urllib.request.urlopen(req).read())


def _assert_profiled(coord, q, where):
    """The satellite-3 invariants for one profiled query."""
    tl = q.timeline_dict()
    assert tl["coverage"] >= 0.95, (
        f"{where}: profiling on dropped attribution to "
        f"{tl['coverage'] * 100:.1f}%: {tl['phases']}")
    prof = _profile(coord, q.query_id)
    kernels = prof["kernels"]
    assert kernels, f"{where}: no kernel rows"
    assert all(k["queryId"] == q.query_id for k in kernels)
    # sync-bracketed rows are MEASURED, and the measured device seconds
    # can never exceed the phase ledger's device-execute wall
    assert any(not k["estimated"] for k in kernels)
    device_s = sum(k["deviceS"] for k in kernels if not k["estimated"])
    assert device_s <= tl["phases"]["device-execute"] + 1e-6, (
        f"{where}: kernel device {device_s}s > device-execute phase "
        f"{tl['phases']['device-execute']}s")
    for k in kernels:
        assert k["dispatchOverheadS"] == pytest.approx(
            max(0.0, k["wallS"] - k["deviceS"]), abs=1e-6)
    return prof


def test_profiled_distributed_q1(cluster):
    coord, _ = cluster
    q = coord.submit(TPCH[1], {"catalog": "tpch", "schema": "tiny",
                               "device_profiling": "true"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    prof = _assert_profiled(coord, q, "distributed q1")
    # both workers AND the coordinator root attributed by node
    nodes = {k["nodeId"] for k in prof["kernels"]}
    assert "coordinator" in nodes
    assert sum(1 for n in nodes if n != "coordinator") >= 2
    ops = {k["operator"] for k in prof["kernels"]}
    assert "TableScan" in ops and "Aggregation" in ops
    # the profile endpoint also carries utilization + process counters
    assert prof["counters"]["launchesTotal"] > 0
    # the kernel ledger rides SQL: system.runtime.kernels has this query
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    _, rows = client.execute(
        "select operator, launches, wall_seconds, device_seconds, "
        "dispatch_overhead_seconds, estimated from system.runtime.kernels "
        f"where query_id = '{q.query_id}'")
    assert rows, "system.runtime.kernels returned no rows for q1"
    by_op = {r[0] for r in rows}
    assert "TableScan" in by_op and "Aggregation" in by_op
    for _op, launches, wall, device, overhead, estimated in rows:
        assert launches >= 1
        assert overhead == pytest.approx(max(0.0, wall - device), abs=1e-5)
        assert estimated is False


def test_profiled_fast_path_point_query(cluster):
    coord, _ = cluster
    q = coord.submit(
        "select n_name from nation where n_nationkey = 7",
        {"catalog": "tpch", "schema": "tiny",
         "short_query_fast_path": "true", "device_profiling": "true"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    assert q.fast_path == "fast-path"
    prof = _assert_profiled(coord, q, "fast-path point query")
    assert {k["nodeId"] for k in prof["kernels"]} == {"coordinator"}


def test_profiling_off_estimates_without_sync(cluster):
    """The sync-cost contract: with ``device_profiling`` off (default),
    kernel rows still exist (zero-sync counting) but device seconds are
    ESTIMATED from wall — flagged so consumers can't mistake them for
    measurements."""
    coord, _ = cluster
    q = coord.submit(TPCH[1], {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    kernels = _profile(coord, q.query_id)["kernels"]
    assert kernels
    assert all(k["estimated"] for k in kernels)


def test_compiled_rerun_hits_cache_and_compiles_table(cluster):
    """The prepared-EXECUTE reuse story at the jit-cache layer: one
    CompiledQuery run twice records ``miss`` then ``hit`` with zero new
    miss events, and the events surface in ``system.runtime.compiles``
    (the embedded run shares the coordinator process's ledger)."""
    from trino_tpu import Session
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.obs.devprofiler import DEVICE_PROFILER

    coord, _ = cluster
    session = Session(properties={"catalog": "tpch", "schema": "tiny"})
    root = plan_sql(session,
                    "select o_orderstatus, count(*), sum(o_totalprice) "
                    "from orders group by o_orderstatus")
    cq = CompiledQuery.build(session, root)
    n0 = len(DEVICE_PROFILER.compile_rows())
    cq.run()
    first = DEVICE_PROFILER.compile_rows()[n0:]
    assert [e["cache"] for e in first] == ["miss"]
    assert first[0]["tier"] == "compiled"
    assert first[0]["fingerprint"] and first[0]["shapeSig"]
    n1 = len(DEVICE_PROFILER.compile_rows())
    cq.run()
    second = DEVICE_PROFILER.compile_rows()[n1:]
    assert [e["cache"] for e in second] == ["hit"]
    assert second[0]["compileS"] == 0.0
    assert second[0]["fingerprint"] == first[0]["fingerprint"]
    assert sum(1 for e in second if e["cache"] == "miss") == 0
    # the ledger rides SQL: both events, named by fingerprint
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    _, rows = client.execute(
        "select cache, tier, compile_seconds from system.runtime.compiles "
        f"where fingerprint = '{first[0]['fingerprint']}'")
    caches = sorted(r[0] for r in rows)
    assert "hit" in caches and "miss" in caches
    assert all(r[1] == "compiled" for r in rows)


def test_explain_analyze_verbose_kernel_annotations(cluster):
    coord, _ = cluster
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    _, rows = client.execute(
        "explain analyze verbose select l_returnflag, count(*) "
        "from lineitem group by l_returnflag")
    text = "\n".join(r[0] for r in rows)
    scan_line = next(line for line in text.split("\n")
                     if "TableScan" in line)
    assert "launches=" in scan_line and "dispatch_overhead=" in scan_line


# ------------------------------ kernel rows attribute the device phases
POINT_SQL = ("select o_orderkey, o_totalprice, o_orderstatus "
             "from orders where o_orderkey = ?")
# result cache off: a HIT never executes, so its profile has no kernels
SHAPE_PROPS = dict(result_cache_enabled="false", device_cache_enabled="true",
                   device_profiling="true")


@pytest.mark.parametrize("shape,statements,fast_path", [
    # prepared lookups on the coordinator-local path, the serving shape
    ("point", [(POINT_SQL, (1_000_000 + i,)) for i in range(4)], True),
    ("q1", [(TPCH[1], None)] * 2, False),
    ("q3", [(TPCH[3], None)] * 2, False),
])
def test_kernel_rows_attribute_the_device_phases(cluster, shape, statements,
                                                 fast_path):
    """Every statement of the shape has kernel rows, and their wall covers
    at least half of the ledger's ``device-staging`` + ``device-execute``
    (TableScan's kernel wall covers the staging read; capped at 1 a
    statement, since worker kernels overlap in wall time)."""
    from trino_tpu.client import dbapi

    coord, _ = cluster
    conn = dbapi.connect(
        coordinator_url=coord.base_url, user="test",
        short_query_fast_path="true" if fast_path else "false",
        **SHAPE_PROPS)
    cur = conn.cursor()
    shares = []
    for sql, params in statements:
        cur.execute(sql, params)
        assert cur.stats["fastPath"] == (
            "fast-path" if fast_path else "distributed")
        prof = _profile(coord, conn._client.query_id)
        assert prof["kernels"], f"{shape}: no kernel rows"
        phases = prof["timeline"]["phases"]
        phase = phases["device-execute"] + phases.get("device-staging", 0.0)
        assert phase > 0
        shares.append(
            min(1.0, sum(k["wallS"] for k in prof["kernels"]) / phase))
    assert sum(shares) / len(shares) >= 0.5, shares


# --------------------------------------- host_read and the charged kernel row
def test_host_read_on_a_numpy_input_counts_nothing():
    import numpy as np

    from trino_tpu.obs.devprofiler import charge_to, host_read, new_kernel_row

    row = new_kernel_row("1", "Filter", "eager")
    arr = np.arange(5)
    with charge_to(row):
        assert host_read(arr, "compact") is arr
        assert host_read(np.int64(3), "compact") == 3
        assert host_read(True, "error-flags")
    assert (row["hostSyncs"], row["hostSyncS"], row["d2hBytes"]) == (0, 0.0, 0)
    assert row["hostSyncSites"] == {}


def test_host_read_charges_the_innermost_row_and_its_site():
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.obs import trace as tracing
    from trino_tpu.obs.devprofiler import (
        charge_to, host_read, new_kernel_row, sync_sites_of)

    outer = new_kernel_row("1", "Aggregation", "eager")
    inner = new_kernel_row("2", "Filter", "eager")
    mask = jnp.arange(1000) % 2 == 0
    counts = mask.astype(jnp.int32)   # compiled out here, not under the tracer
    tracer = tracing.Tracer()
    with tracing.activate(tracer), charge_to(outer):
        with charge_to(inner):
            got = host_read(mask, "operator-stats")
            host_read(mask, "compact")
        host_read(counts, "join-emit-count")
    assert isinstance(got, np.ndarray) and int(got.sum()) == 500
    assert inner["hostSyncs"] == 2 and inner["d2hBytes"] == 2000
    assert outer["hostSyncs"] == 1 and outer["d2hBytes"] == 4000
    assert inner["hostSyncSites"]["operator-stats"][0] == 1
    assert set(outer["hostSyncSites"]) == {"join-emit-count"}
    sites = sync_sites_of([outer, inner])
    assert {s: v["count"] for s, v in sites.items()} == {
        "operator-stats": 1, "compact": 1, "join-emit-count": 1}
    assert sum(v["bytes"] for v in sites.values()) == 6000
    # only reads of 50 us and more are stored as spans; all are counted
    for sp in tracer.to_dicts():
        assert sp["name"] == "host/sync"
        assert sp["durationS"] >= tracing.MIN_STORED_SPAN_S
    assert len(tracer.to_dicts()) <= 3
    # outside any charged row a read is still done, and counted nowhere
    assert int(host_read(mask, "compact").sum()) == 500


def test_merge_kernel_rows_adds_the_sync_and_compile_fields():
    a = dict(_row(), hostSyncs=2, hostSyncS=0.5, d2hBytes=10, compiles=1,
             compileS=0.25, hostSyncSites={"compact": [2, 0.5, 10]})
    b = dict(_row(), hostSyncs=1, hostSyncS=0.25, d2hBytes=6,
             hostSyncSites={"compact": [1, 0.25, 6]})
    old = _row()   # a row from before the fields existed
    (row,) = merge_kernel_rows({}, [a, b, old]).values()
    assert (row["hostSyncs"], row["d2hBytes"], row["compiles"]) == (3, 16, 1)
    assert row["hostSyncS"] == pytest.approx(0.75)
    assert row["compileS"] == pytest.approx(0.25)
    assert row["hostSyncSites"] == {"compact": [3, 0.75, 16]}
    assert a["hostSyncSites"] == {"compact": [2, 0.5, 10]}, "inputs untouched"


def test_compile_listener_charges_the_executing_operator():
    import jax
    import jax.numpy as jnp

    from trino_tpu.obs import trace as tracing
    from trino_tpu.obs.devprofiler import (
        charge_to, install_process_hooks, new_kernel_row)

    install_process_hooks()
    install_process_hooks()   # once per process, however often it is asked
    row = new_kernel_row("9", "Project", "eager")
    tracer = tracing.Tracer()
    x = jnp.arange(7)
    with tracing.activate(tracer), charge_to(row):
        jax.jit(lambda x: x * 3 + 41)(x).block_until_ready()
    assert row["compiles"] == 1 and row["compileS"] > 0
    assert [s["name"] for s in tracer.to_dicts()] == ["xla/compile"]


def test_operator_stats_read_is_charged_to_the_node_that_made_the_page(cluster):
    """A filter-then-aggregate statement: the blocking read of the
    selection mask that counts a page's live rows sits in the row (and
    inside the clock) of the node that produced the page, not its parent's."""
    coord, _ = cluster
    client = StatementClient(coord.base_url, {
        "catalog": "tpch", "schema": "tiny", "result_cache_enabled": "false"})
    client.execute(
        "select l_returnflag, count(*) from lineitem "
        "where l_quantity < 10 group by l_returnflag")
    prof = _profile(coord, client.query_id)
    rows = [k for k in prof["kernels"] if k["launches"]]
    filters = [k for k in rows if k["operator"] == "Filter"]
    assert filters
    for k in filters:
        n, seconds, nbytes = k["hostSyncSites"]["operator-stats"]
        assert n == k["launches"], "one read per execution, in its own row"
        assert nbytes > 0 and k["hostSyncS"] >= seconds
        # the wait is inside the node's own wall
        assert k["wallS"] >= seconds
    for k in rows:
        n = k["hostSyncSites"].get("operator-stats", [0])[0]
        assert n <= k["launches"], (k["operator"], "charged a child's read")
    # the new columns ride SQL
    _cols, table = client.execute(
        "select operator, host_syncs, host_sync_seconds, d2h_bytes, compiles, "
        "compile_seconds from system.runtime.kernels "
        f"where query_id = '{client.query_id}'")
    assert sum(r[1] for r in table) == sum(k["hostSyncs"] for k in prof["kernels"])
    assert sum(r[3] for r in table) == sum(k["d2hBytes"] for k in prof["kernels"])
