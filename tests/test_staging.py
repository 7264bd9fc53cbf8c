"""Pipelined cold staging (trino_tpu/exec/staging.py) + the host-RAM
columnar cache tier (trino_tpu/devcache/hostcache.py).

Covers the PR's acceptance matrix:

- pipelined-vs-serial BIT-IDENTICAL staged arrays across all three
  staging tiers (eager, compiled phase-1, SPMD sharded);
- host-cache DML invalidation matrix (INSERT/UPDATE/DELETE/DROP/CTAS on
  the memory AND filesystem connectors);
- single-flight under 4 concurrent stagings of the same splits (one
  connector scan per split);
- HBM-evict -> host-refill with ZERO connector scan calls;
- revocable budget-shed order (host tier empties before the HBM tier);
- adaptive split sizing from estimated table bytes / staging_split_bytes;
- the staging sub-phase spans and their phase-ledger mapping;
- cluster-memory/system-table surfacing of the host tier.
"""
import threading
import time

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.client.session import Session
from trino_tpu.devcache import DEVICE_CACHE, HOST_CACHE
from trino_tpu.obs import metrics as M

from tests.tpch_sql import QUERIES as TPCH_SQL


@pytest.fixture(autouse=True)
def fresh_caches():
    DEVICE_CACHE.invalidate_all()
    HOST_CACHE.invalidate_all()
    yield
    DEVICE_CACHE.invalidate_all()
    HOST_CACHE.invalidate_all()


def _session(**props):
    return Session({"catalog": "memory", "schema": "db",
                    "device_cache_enabled": True, **props})


def _tables(session, n_lineitem=4000):
    rng = np.random.default_rng(7)
    n_cust, n_ord = 120, 900
    mem = session.catalogs["memory"]
    mem.create_table(
        "db", "customer", [("c_custkey", T.BIGINT), ("c_seg", T.VARCHAR)],
        [(i, "BUILDING" if i % 5 == 0 else "AUTO") for i in range(n_cust)])
    mem.create_table(
        "db", "orders",
        [("o_orderkey", T.BIGINT), ("o_custkey", T.BIGINT),
         ("o_pri", T.BIGINT)],
        [(i, int(rng.integers(0, n_cust)), i % 3) for i in range(n_ord)])
    mem.create_table(
        "db", "lineitem", [("l_orderkey", T.BIGINT), ("l_price", T.BIGINT)],
        [(int(rng.integers(0, n_ord)), int(rng.integers(1, 100)))
         for _ in range(n_lineitem)])


Q3 = ("select l_orderkey, sum(l_price) rev, o_pri "
      "from customer, orders, lineitem "
      "where c_seg = 'BUILDING' and c_custkey = o_custkey "
      "and l_orderkey = o_orderkey group by l_orderkey, o_pri "
      "order by rev desc limit 10")


def _scan_node(session, sql):
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.sql.planner import plan as P

    root = plan_sql(session, sql)
    return root, [n for n in P.walk_plan(root)
                  if isinstance(n, P.TableScanNode)]


def _page_arrays(page):
    out = []
    for c in page.columns:
        out.append(np.asarray(c.values))
        out.append(None if c.nulls is None else np.asarray(c.nulls))
    return out


def _assert_same_arrays(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        if x is None or y is None:
            assert x is None and y is None
            continue
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


def _count_scans(conn):
    """Wrap conn.scan with an invocation counter; returns a cell whose
    [0] is the call count and [1] the set of scanned table names."""
    calls = [0, set()]
    inner = conn.scan

    def scan(split, columns, constraint=None):
        calls[0] += 1
        calls[1].add(split.table)
        return inner(split, columns, constraint=constraint)

    conn.scan = scan
    return calls


# ----------------------------------------------- bit-identity, three tiers
def test_pipelined_serial_bit_identical_eager():
    """The eager tier's staged Page is bitwise identical whether split
    scans run serial or 4-wide (fan-out order never leaks into assembly),
    including with the fan-out forced over many tiny splits."""
    from trino_tpu.exec.executor import Executor

    pages = []
    for par in (1, 4):
        s = _session(device_cache_enabled=False, staging_parallelism=par,
                     staging_split_bytes=1 << 12)
        _tables(s)
        root, scans = _scan_node(s, Q3)
        ex = Executor(s)
        pages.append([ex._exec_TableScanNode(n) for n in scans])
    for serial, pipelined in zip(*pages):
        _assert_same_arrays(_page_arrays(serial), _page_arrays(pipelined))


def test_pipelined_serial_bit_identical_compiled():
    """Compiled phase-1 staging (dynamic-filter host pruning included):
    the flattened input arrays of the compiled artifact are bitwise equal
    serial vs pipelined."""
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql

    arrays = []
    for par in (1, 4):
        s = _session(device_cache_enabled=False, staging_parallelism=par,
                     staging_split_bytes=1 << 12)
        _tables(s)
        cq = CompiledQuery.build(s, plan_sql(s, Q3))
        arrays.append([np.asarray(a) for a in cq.input_arrays])
    _assert_same_arrays(arrays[0], arrays[1])


def test_pipelined_serial_bit_identical_spmd():
    """SPMD sharded staging: stacked shard arrays (incl. the sel plane)
    are bitwise equal serial vs pipelined, with the adaptive target
    forcing more fine splits than devices (contiguous grouping)."""
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.parallel.spmd import stage_sharded_scans

    staged = []
    for par in (1, 4):
        s = _session(device_cache_enabled=False, staging_parallelism=par,
                     staging_split_bytes=1 << 12)
        _tables(s)
        root = plan_sql(s, Q3)
        arrays, specs = stage_sharded_scans(s, root, 4)
        flat = [np.asarray(a) for nid in sorted(arrays)
                for a in arrays[nid]]
        staged.append(flat)
    _assert_same_arrays(staged[0], staged[1])


# ------------------------------------------------- host tier: refill path
def test_hbm_evict_refills_from_host_with_zero_connector_scans():
    """The tentpole's point: after an HBM eviction, staging refills from
    the host-RAM tier — zero connector scan calls, bit-identical rows."""
    s = _session(staging_split_bytes=1 << 12)
    _tables(s)
    r1 = s.execute(Q3).rows
    assert HOST_CACHE.cached_bytes() > 0  # decoded splits retained
    DEVICE_CACHE.invalidate_all()  # the HBM eviction
    calls = _count_scans(s.catalogs["memory"])
    hits_before = HOST_CACHE.hit_count()
    r2 = s.execute(Q3).rows
    assert calls[0] == 0
    assert HOST_CACHE.hit_count() > hits_before
    assert r1 == r2


def test_host_tier_serves_across_shard_shapes():
    """A DIFFERENT shard signature (the SPMD tier after the eager tier)
    re-stages from host memory: the per-split host entries are shared, so
    the mesh staging runs zero connector scans."""
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.parallel.spmd import stage_sharded_scans

    s = _session(staging_split_bytes=1 << 12)
    _tables(s)
    sql = "select l_orderkey, l_price from lineitem"
    s.execute(sql)  # fills host tier split-by-split (eager staging)
    DEVICE_CACHE.invalidate_all()
    calls = _count_scans(s.catalogs["memory"])
    root = plan_sql(s, sql)
    arrays, _specs = stage_sharded_scans(s, root, 4)
    assert arrays and calls[0] == 0


# ------------------------------------------------- DML invalidation matrix
def _dml_matrix(s_cached, s_plain, probe, mutate_ops):
    """Shared body: after every mutation, the host-tier-cached session
    must return EXACTLY what an uncached session over the same connector
    returns — a stale host entry would diverge. The HBM tier is evicted
    before each probe so the host tier (not the device cache) answers."""
    for name, op in mutate_ops:
        probe(s_cached)  # warm both tiers at the current version
        op()
        DEVICE_CACHE.invalidate_all()
        got = probe(s_cached)
        want = probe(s_plain)
        assert got == want, (name, got, want)


def test_host_cache_dml_invalidation_matrix_memory():
    s = _session(staging_split_bytes=1 << 12)
    _tables(s)
    plain = Session({"catalog": "memory", "schema": "db"})
    plain.catalogs["memory"] = s.catalogs["memory"]

    def probe(sess):
        return sess.execute(
            "select l_orderkey, sum(l_price) rev from lineitem "
            "group by l_orderkey order by rev desc, l_orderkey limit 5"
        ).rows

    ops = [
        ("insert", lambda: s.execute(
            "insert into lineitem values (1, 100000)")),
        ("update", lambda: s.execute(
            "update lineitem set l_price = 200000 where l_price = 100000")),
        ("delete", lambda: s.execute(
            "delete from lineitem where l_price = 200000")),
        ("ctas", lambda: s.execute(
            "create table lineitem2 as select * from lineitem")),
        ("drop", lambda: s.execute("drop table lineitem")),
    ]
    # recreate via CTAS after the DROP and probe the recreated table:
    # the fresh version must not be served the dropped table's entries
    _dml_matrix(s, plain, probe, ops[:4])
    s.execute("drop table lineitem")
    s.execute("create table lineitem as "
              "select l_orderkey, l_price + 1 as l_price from lineitem2")
    DEVICE_CACHE.invalidate_all()
    assert probe(s) == probe(plain)
    # stale-version host entries are reclaimed, not just missed: no
    # resident lineitem entry carries more than the live version
    versions = {e["version"] for e in HOST_CACHE.snapshot()
                if e["table"] == "lineitem"}
    assert len(versions) <= 1

    # host-warm dimensions: an INSERT into lineitem re-scans ONLY the
    # mutated table's splits — customer/orders stay host-warm
    s.execute(Q3)
    s.execute("insert into lineitem values (2, 3)")
    DEVICE_CACHE.invalidate_all()
    conn = s.catalogs["memory"]
    calls = _count_scans(conn)
    try:
        s.execute(Q3)
        assert calls[0] >= 1  # the mutated table re-scanned...
        assert calls[1] == {"lineitem"}  # ...and nothing else did
    finally:
        conn.scan = type(conn).scan.__get__(conn)


def test_host_cache_dml_invalidation_matrix_filesystem(tmp_path):
    from trino_tpu.connector.filesystem.connector import FileSystemConnector

    conn = FileSystemConnector(str(tmp_path))
    s = Session({"catalog": "filesystem", "schema": "lake",
                 "device_cache_enabled": True,
                 "staging_split_bytes": 1 << 12})
    s.catalogs["filesystem"] = conn
    plain = Session({"catalog": "filesystem", "schema": "lake"})
    plain.catalogs["filesystem"] = conn
    s.execute("create table t (a bigint, b bigint)")
    s.execute("insert into t values " + ",".join(
        f"({i}, {i % 13})" for i in range(2000)))

    def probe(sess):
        return sess.execute(
            "select b, count(*) c from t group by b order by b").rows

    ops = [
        ("insert", lambda: s.execute("insert into t values (9999, 1)")),
        ("update", lambda: s.execute("update t set b = 2 where a = 9999")),
        ("delete", lambda: s.execute("delete from t where a = 9999")),
        ("ctas", lambda: s.execute("create table t2 as select * from t")),
        ("drop", lambda: s.execute("drop table t")),
    ]
    _dml_matrix(s, plain, probe, ops[:4])
    # drop + recreate under the same name: fresh file state, fresh
    # version — the recreated table must never see the old entries
    s.execute("drop table t")
    s.execute("create table t as select a, b + 1 as b from t2")
    DEVICE_CACHE.invalidate_all()
    assert probe(s) == probe(plain)


# ----------------------------------------------------------- single-flight
def test_single_flight_four_concurrent_stagings():
    """4 threads staging the same table through the host tier produce
    exactly ONE connector scan per split — followers are served the
    leader's decoded columns."""
    from trino_tpu.exec import staging

    s = _session(staging_split_bytes=1 << 12, staging_parallelism=2)
    _tables(s)
    root, scans = _scan_node(s, "select l_orderkey, l_price from lineitem")
    node = scans[0]
    conn = s.catalogs["memory"]
    n_splits = len(conn.get_splits("db", "lineitem", staging.target_split_count(
        s, conn, "db", "lineitem")))
    assert n_splits > 1
    calls = _count_scans(conn)
    results = [None] * 4
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        splits = conn.get_splits("db", "lineitem", staging.target_split_count(
            s, conn, "db", "lineitem"))
        datas, _prof = staging.stage_splits(s, node, conn, splits, None)
        results[i] = datas

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert calls[0] == n_splits, (calls[0], n_splits)
    base = [np.asarray(d["l_orderkey"].values) for d in results[0]]
    for r in results[1:]:
        got = [np.asarray(d["l_orderkey"].values) for d in r]
        for x, y in zip(base, got):
            assert np.array_equal(x, y)


def test_inflight_split_never_parks_a_pool_caller():
    """``lookup_or_stage(wait=False)`` returns (None, "inflight")
    immediately while another caller leads the flight — the guarantee
    that one wedged cold staging can't pin shared staging-pool threads
    behind its flight (followers re-resolve on their own thread)."""
    from trino_tpu.devcache import CacheKey
    from trino_tpu.devcache.hostcache import HostColumnCache

    cache = HostColumnCache(max_bytes=1 << 20)
    key = CacheKey("c", "s", "t", "v1", "sig", "host:0", 1)
    leading = threading.Event()
    release = threading.Event()

    def slow_loader():
        leading.set()
        assert release.wait(30)
        return {"x": 1}, 1, 100, 1

    leader = threading.Thread(
        target=lambda: cache.lookup_or_stage(key, slow_loader))
    leader.start()
    try:
        assert leading.wait(30)
        t0 = time.perf_counter()
        ent, disp = cache.lookup_or_stage(
            key, lambda: pytest.fail("follower must not load"), wait=False)
        assert (ent, disp) == (None, "inflight")
        assert time.perf_counter() - t0 < 5  # no FLIGHT_WAIT_S park
    finally:
        release.set()
        leader.join()
    ent, disp = cache.lookup_or_stage(
        key, lambda: pytest.fail("resident entry must serve"))
    assert disp == "hit" and ent.value == {"x": 1}


# -------------------------------------------------------- budget + shedding
def test_shed_revocable_host_tier_first(monkeypatch):
    """Pressure eats the host tier before the HBM tier: shed_revocable
    frees host pages first and touches the device pool only for the
    remainder — and only where device arrays are host-backed (forced
    here so accelerator-attached test runs exercise the same branch)."""
    from trino_tpu.devcache import CacheKey, shed_revocable
    from trino_tpu.devcache import hostcache as hc

    monkeypatch.setattr(hc, "_device_memory_host_backed", lambda: True)

    for i in range(4):
        HOST_CACHE.lookup_or_stage(
            CacheKey("c", "s", f"h{i}", "v1", "sig", f"host:{i}", 1),
            lambda: (object(), 1, 1000, 1))
        DEVICE_CACHE.lookup_or_stage(
            CacheKey("c", "s", f"d{i}", "v1", "sig", "table", 1),
            lambda: (object(), 1, 1000, 1))
    assert HOST_CACHE.cached_bytes() == 4000
    assert DEVICE_CACHE.cached_bytes() == 4000
    freed = shed_revocable(2500)
    assert freed == 3000
    assert HOST_CACHE.cached_bytes() == 1000  # host shed first
    assert DEVICE_CACHE.cached_bytes() == 4000  # HBM untouched
    freed = shed_revocable(3000)
    assert HOST_CACHE.cached_bytes() == 0  # host emptied first...
    assert DEVICE_CACHE.cached_bytes() == 2000  # ...then HBM for the rest


def test_host_cache_budget_lru():
    from trino_tpu.devcache import CacheKey
    from trino_tpu.devcache.hostcache import HostColumnCache

    cache = HostColumnCache(max_bytes=3000)
    for i in range(5):
        cache.lookup_or_stage(
            CacheKey("c", "s", f"t{i}", "v1", "sig", f"host:{i}", 1),
            lambda: (object(), 1, 1000, 1))
    assert cache.cached_bytes() == 3000
    left = {e["table"] for e in cache.snapshot()}
    assert left == {"t2", "t3", "t4"}  # LRU evicted


# ------------------------------------------------------ adaptive split sizing
def test_adaptive_split_sizing():
    from trino_tpu.exec import staging

    s = _session()
    _tables(s, n_lineitem=4000)
    conn = s.catalogs["memory"]
    # big table / small split bytes -> fan out, capped
    s.properties["staging_split_bytes"] = 1 << 10
    t = staging.target_split_count(s, conn, "db", "lineitem")
    assert 1 < t <= staging.MAX_TARGET_SPLITS
    # huge split bytes -> tiny tables stay single-split (no fan-out tax)
    s.properties["staging_split_bytes"] = 1 << 30
    assert staging.target_split_count(s, conn, "db", "lineitem") == 1
    # unknown row count -> caller's floor
    class NoStats:
        def table_row_count(self, schema, table):
            return None

        def get_table(self, schema, table):
            return None

    assert staging.target_split_count(s, NoStats(), "db", "x", floor=3) == 3


# ------------------------------------------------- sub-phase observability
def test_staging_subphase_spans_and_ledger_mapping():
    from trino_tpu.exec.executor import Executor
    from trino_tpu.obs import trace as tracing
    from trino_tpu.obs.timeline import SPAN_PHASE

    s = _session(staging_split_bytes=1 << 12)
    _tables(s)
    root, scans = _scan_node(s, "select l_orderkey, l_price from lineitem")
    tracer = tracing.Tracer()
    with tracer.span("q"):
        Executor(s)._exec_TableScanNode(scans[0])
    DEVICE_CACHE.invalidate_all()
    with tracer.span("q2"):
        Executor(s)._exec_TableScanNode(scans[0])
    names = [sp.name for sp in tracer.spans()]
    for required in ("staging/scan", "staging/decode", "staging/transfer",
                     "staging/host-cache"):
        assert required in names, (required, names)
        # every sub-phase lands in the ledger's device-staging bucket
        assert SPAN_PHASE[required][1] == "device-staging"
    # the warm second staging served every split from the host tier: its
    # host-cache span reports full hits and no scan fan-out follows it
    hc = [sp for sp in tracer.spans() if sp.name == "staging/host-cache"]
    assert hc[-1].attributes["hits"] == hc[-1].attributes["splits"]


def test_blocked_transfer_bit_identical():
    """The double-buffered blocked path (arrays over two blocks) is
    bitwise identical to a single-shot put, counts its blocks, respects
    the BLOCKED_MAX_BYTES single-shot carve-out, and handles the 2-D
    SPMD stacked shape (rows = last axis)."""
    from trino_tpu.exec import staging

    rng = np.random.default_rng(5)
    prof = staging.StageProfile()
    xfer = staging.blocked_transfer(prof, block_bytes=1 << 12)
    flat = rng.integers(-1 << 40, 1 << 40, size=5000, dtype=np.int64)
    out = np.asarray(xfer(flat))
    assert out.dtype == flat.dtype and np.array_equal(out, flat)
    assert prof.transfer_blocks >= 3  # the blocked path actually ran
    stacked = rng.integers(0, 1 << 20, size=(4, 3000), dtype=np.int64)
    out2 = np.asarray(xfer(stacked))
    assert out2.shape == stacked.shape and np.array_equal(out2, stacked)
    # over the cap: single-shot (no extra blocks counted), still exact
    before = prof.transfer_blocks
    cap = staging.BLOCKED_MAX_BYTES
    try:
        staging.BLOCKED_MAX_BYTES = 1 << 10
        big = rng.integers(0, 1 << 30, size=4000, dtype=np.int64)
        out3 = np.asarray(staging.blocked_transfer(
            prof, block_bytes=1 << 12)(big))
        assert np.array_equal(out3, big)
        assert prof.transfer_blocks == before
    finally:
        staging.BLOCKED_MAX_BYTES = cap


def test_staging_phase_seconds_metric():
    before = {p: M.STAGING_PHASE_SECONDS.value(p)
              for p in ("scan", "decode", "transfer")}
    s = _session(device_cache_enabled=False)
    _tables(s)
    s.execute("select l_orderkey from lineitem")
    for p in ("scan", "decode", "transfer"):
        assert M.STAGING_PHASE_SECONDS.value(p) >= before[p]
    assert M.STAGING_PHASE_SECONDS.value("decode") > before["decode"]


# --------------------------------------- cluster memory + system surfacing
def test_cluster_memory_host_tier_revocable():
    from trino_tpu.server.cluster_memory import ClusterMemoryManager

    mgr = ClusterMemoryManager(kill=lambda q, r: None)
    mgr.update("w1", {"queryMemory": {}, "memoryBytes": 0,
                      "deviceCacheBytes": 1000, "hostCacheBytes": 2500})
    assert mgr.revocable_bytes() == 3500


def test_device_cache_system_table_has_host_tier_rows():
    from trino_tpu.connector.system.connector import device_cache_rows

    s = _session(staging_split_bytes=1 << 12)
    _tables(s)
    s.execute(Q3)
    rows = device_cache_rows()
    tiers = {r[-1] for r in rows}
    assert tiers == {"hbm", "host"}
    host_rows = [r for r in rows if r[-1] == "host"]
    assert all(r[4].startswith("host:") for r in host_rows)  # shard col
    assert sum(r[6] for r in host_rows) == HOST_CACHE.cached_bytes()


def test_staging_accounting_identity_with_fanout():
    """The PR 7 drift contract survives the pipeline: STAGING_SECONDS
    still charges exactly phase1_s + df_apply_s for a compiled build,
    with the fan-out active and prune seconds accumulated from worker
    threads."""
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql

    s = _session(device_cache_enabled=False, staging_parallelism=4,
                 staging_split_bytes=1 << 12)
    _tables(s)
    before = M.STAGING_SECONDS.value()
    cq = CompiledQuery.build(s, plan_sql(s, Q3))
    delta = M.STAGING_SECONDS.value() - before
    assert delta == pytest.approx(cq.phase1_s + cq.df_apply_s, abs=1e-9)


# ------------------------------ TPC-H q3's scans through the staging engine
def _stage_tpch_q3(session):
    """Stage q3's three scans as the compiled tier does (phase-1
    dynamic-filter domains applied on the host) through
    ``staging.staged_scan_page``. Returns the staged arrays by table, the
    splits staged and the connector scan calls made."""
    from trino_tpu.exec import host_eval, staging
    from trino_tpu.exec.executor import (
        apply_dynamic_domains, dynamic_domain_map, scan_constraint_with)

    root, scans = _scan_node(session, TPCH_SQL[3])
    dyn = host_eval.resolve_dynamic_filters(session, root)
    conn = session.catalogs["tpch"]
    calls = _count_scans(conn)
    arrays, splits_staged = {}, 0
    try:
        for node in scans:
            constraint = scan_constraint_with(node, dyn)
            splits = conn.get_splits(
                node.schema, node.table,
                staging.target_split_count(
                    session, conn, node.schema, node.table),
                constraint=constraint, handle=node.table_handle)
            page, _rows, prof = staging.staged_scan_page(
                session, node, conn, splits, constraint,
                prune=lambda datas, node=node: apply_dynamic_domains(
                    node, dyn, datas),
                applied_domains=dynamic_domain_map(node, dyn))
            arrays[node.table] = _page_arrays(page)
            splits_staged += prof.splits
    finally:
        del conn.scan  # the counter was set on the instance
    return arrays, splits_staged, calls[0]


@pytest.fixture(scope="module")
def tpch_q3_staging():
    """q3's scans over tpch.tiny staged three ways: serial
    (``staging_parallelism=1``), pipelined at the width the engine picks
    (``=0``), and again after the HBM tier alone is evicted. Each cold arm
    starts with the generator, host and HBM caches empty."""
    from trino_tpu.connector.tpch import generator

    def cold(parallelism):
        DEVICE_CACHE.invalidate_all()
        HOST_CACHE.invalidate_all()
        generator._gen_cache.clear()
        session = Session({"catalog": "tpch", "schema": "tiny",
                           "device_cache_enabled": True,
                           "staging_split_bytes": 1 << 18,
                           "staging_parallelism": parallelism})
        return session, _stage_tpch_q3(session)

    _, (serial, _splits, _scans) = cold(1)
    session, (pipelined, splits, cold_scans) = cold(0)
    host_bytes = HOST_CACHE.cached_bytes()
    DEVICE_CACHE.invalidate_all()
    refill, _splits, refill_scans = _stage_tpch_q3(session)
    return {"serial": serial, "pipelined": pipelined, "refill": refill,
            "splits": splits, "cold_scans": cold_scans,
            "refill_scans": refill_scans, "host_bytes": host_bytes}


def test_tpch_q3_pipelined_staging_bit_identical_to_serial(tpch_q3_staging):
    run = tpch_q3_staging
    assert run["splits"] > 3 and run["cold_scans"] == run["splits"]
    assert set(run["serial"]) == {"customer", "orders", "lineitem"}
    for table, serial in run["serial"].items():
        _assert_same_arrays(serial, run["pipelined"][table])


def test_tpch_q3_first_pass_fills_the_host_tier(tpch_q3_staging):
    assert tpch_q3_staging["host_bytes"] > 0


def test_tpch_q3_host_refill_bit_identical_without_a_connector_scan(
        tpch_q3_staging):
    run = tpch_q3_staging
    assert run["refill_scans"] == 0
    for table, cold in run["pipelined"].items():
        _assert_same_arrays(cold, run["refill"][table])


# ------------------------------------------------------- split row buckets
@pytest.mark.parametrize("rows", [0, 1, 7, 100, 4096, 31869, 500830, 5996577])
def test_row_bucket_pads_at_most_an_eighth(rows):
    from trino_tpu.exec import staging

    bucket = staging.row_bucket(rows)
    assert rows <= bucket <= rows + rows // 8 + 1
    assert staging.row_bucket(bucket) == bucket  # a bucket is its own bucket


def test_row_bucket_gives_sibling_splits_one_shape():
    """tpch.sf1 lineitem reaches a worker as 12 splits of these lengths
    (chip run, PR 25): one shape, so one compile per operator, not 12."""
    from trino_tpu.exec import staging

    lengths = [500830, 499841, 499146, 499559, 499978, 499908, 500392,
               499034, 499173, 499178, 500385, 499160]
    assert {staging.row_bucket(n) for n in lengths} == {524288}


def test_bucketed_page_is_the_exact_page_plus_a_dead_tail():
    import jax.numpy as jnp

    from trino_tpu.connector.spi import ColumnData
    from trino_tpu.data.page import Page
    from trino_tpu.exec import staging

    n = 1000
    rng = np.random.default_rng(3)
    types = [T.BIGINT, T.BIGINT]
    host = [
        ColumnData(T.BIGINT, np.arange(n, dtype=np.int64), vrange=(0, n),
                   sorted=True),
        ColumnData(T.BIGINT, rng.integers(-1 << 40, 1 << 40, n),
                   nulls=rng.random(n) < 0.1),
    ]
    exact = staging.page_from_host_columns(types, host, jnp.asarray)
    padded_host, live = staging.pad_to_row_bucket(types, host)
    padded = staging.page_from_host_columns(types, padded_host, jnp.asarray)
    assert exact.sel is None and exact.num_rows == n == live
    assert padded.num_rows == staging.row_bucket(n) > n
    padded = Page(padded.columns, jnp.arange(padded.num_rows) < live,
                  live_prefix=True)  # as staged_scan_page marks the tail
    assert padded.live_count() == n
    assert padded.columns[0].ascending  # dead rows are a tail
    assert padded.compact().to_pylist() == exact.to_pylist()
