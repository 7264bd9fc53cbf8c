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
    # the transfer says where its time went and what crossed: two int64
    # columns of 4,000 rows (no range to narrow by), one put each, no
    # bucket (eager)
    xfer = [sp for sp in tracer.spans() if sp.name == "staging/transfer"]
    attrs = xfer[-1].attributes
    assert (attrs["puts"], attrs["bytes"]) == (2, 2 * 4000 * 8)
    for key in ("host_s", "put_s", "wait_s"):
        assert 0 <= attrs[key] <= xfer[-1].duration


def test_page_puts_bit_identical_one_put_an_array():
    """``PagePuts`` puts each array once, bitwise identical, 1-D columns
    and the 2-D SPMD stacked shape alike, and counts what it issued."""
    from trino_tpu.exec import staging
    from trino_tpu.obs.devprofiler import charge_to, new_kernel_row

    rng = np.random.default_rng(5)
    flat = rng.integers(-1 << 40, 1 << 40, size=5000, dtype=np.int64)
    stacked = rng.integers(0, 1 << 20, size=(4, 3000), dtype=np.int64)
    row = new_kernel_row("0", "TableScan", "eager")
    with charge_to(row), staging.PagePuts() as puts:
        outs = [puts.put(flat), puts.put(stacked)]
    for out, want in zip(outs, (flat, stacked)):
        got = np.asarray(out)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want)
    assert (puts.count, puts.nbytes) == (2, flat.nbytes + stacked.nbytes)
    assert row["stagingPuts"] == 2


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
    from trino_tpu.connector.spi import ColumnData
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
    exact, _ = staging.put_page(types, host)
    padded, _ = staging.put_page(types, host, bucket_rows=True)
    assert exact.sel is None and exact.num_rows == n
    assert padded.num_rows == staging.row_bucket(n) > n
    assert padded.live_prefix and padded.live_count() == n
    assert padded.columns[0].ascending  # dead rows are a tail
    assert padded.compact().to_pylist() == exact.to_pylist()


# ------------------------------ one host pass and one put an array (PR 37)
def _parent_arrays(types, host, bucket_rows):
    """The parent's staged arrays (PR 36's ``pad_to_row_bucket`` then
    ``page_from_host_columns`` then the mask), in numpy: an all-flat page
    on a worker is padded with zero rows to ``row_bucket``, THEN a fitting
    int64 is narrowed to int32, and the mask is an ``arange`` compared
    with the live rows; nested and two-limb columns go as they are.
    Returns ``(leaves, sel)``, leaves in ``_leaves`` order."""
    from trino_tpu.data.page import fits_int32
    from trino_tpu.exec import staging

    def as_is(cd):
        out = [np.asarray(cd.values),
               None if cd.nulls is None else np.asarray(cd.nulls),
               None if cd.hi is None else np.asarray(cd.hi)]
        for kid in cd.children or ():
            out += as_is(kid)
        return out

    flat = [not (t.is_nested or cd.hi is not None)
            for t, cd in zip(types, host)]
    live = len(host[0].values)
    extra = staging.row_bucket(live) - live if bucket_rows and all(
        flat) else 0

    def pad(a):
        a = np.asarray(a)
        return np.concatenate([a, np.zeros(extra, a.dtype)]) if extra else a

    leaves = []
    for is_flat, cd in zip(flat, host):
        if not is_flat:
            leaves += as_is(cd)
            continue
        vals = pad(cd.values)
        if vals.dtype == np.int64 and fits_int32(cd.vrange):
            vals = vals.astype(np.int32)
        leaves += [vals, None if cd.nulls is None else pad(cd.nulls), None]
    sel = np.arange(live + extra) < live if extra else None
    return leaves, sel


def _leaves(col):
    out = [np.asarray(col.values),
           None if col.nulls is None else np.asarray(col.nulls),
           None if col.hi is None else np.asarray(col.hi)]
    for kid in col.children or ():
        out += _leaves(kid)
    return out


def _staging_case(name):
    """(types, host ColumnData list, bucket_rows) for one case."""
    from decimal import Decimal

    from trino_tpu.connector.spi import ColumnData, column_data_from_column
    from trino_tpu.data.page import Column

    rng = np.random.default_rng(11)
    n = 1000

    def cd_of(typ, values):
        return column_data_from_column(Column.from_python(typ, values))

    keys = ColumnData(T.BIGINT, np.arange(n, dtype=np.int64) * 3,
                      vrange=(0, 3 * n), sorted=True)
    if name == "int64-fits-int32":
        return [T.BIGINT], [keys], True
    if name == "int64-wide":
        wide = rng.integers(-1 << 40, 1 << 40, n)
        return [T.BIGINT], [ColumnData(T.BIGINT, wide,
                                       vrange=(-1 << 40, 1 << 40))], True
    if name == "dictionary-codes":
        return [T.VARCHAR, T.BIGINT], [cd_of(T.VARCHAR, [
            ("AUTO", "BUILDING", "MACHINERY")[i % 3] for i in range(n)]),
            keys], True
    if name == "nullable":
        vals = rng.integers(0, 500, n)
        return [T.BIGINT, T.DOUBLE], [
            ColumnData(T.BIGINT, vals, nulls=rng.random(n) < 0.2,
                       vrange=(0, 500)),
            ColumnData(T.DOUBLE, rng.random(n), nulls=rng.random(n) < 0.3),
        ], True
    if name == "two-limb-decimal":
        dec = T.decimal(30, 2)
        return [dec, T.BIGINT], [cd_of(dec, [
            None if i % 7 == 0 else Decimal(i) * Decimal("1234567.89")
            for i in range(n)]), keys], True
    if name == "nested":
        arr = T.array_of(T.BIGINT)
        return [arr, T.BIGINT], [cd_of(arr, [
            None if i % 5 == 0 else list(range(i % 4)) for i in range(n)]),
            keys], True
    if name == "own-bucket":
        m = 4096
        return [T.BIGINT, T.INTEGER], [
            ColumnData(T.BIGINT, np.arange(m, dtype=np.int64),
                       vrange=(0, m), sorted=True),
            ColumnData(T.INTEGER, rng.integers(0, 9, m).astype(np.int32)),
        ], True
    if name == "empty":
        return [T.BIGINT, T.VARCHAR], None, True
    assert name == "eager-no-bucket"
    return [T.BIGINT, T.BIGINT], [keys, ColumnData(
        T.BIGINT, rng.integers(0, 50, n), vrange=(0, 50))], False


STAGING_CASES = ("int64-fits-int32", "int64-wide", "dictionary-codes",
                 "nullable", "two-limb-decimal", "nested", "own-bucket",
                 "empty", "eager-no-bucket")


@pytest.mark.parametrize("width", [1, 4])
@pytest.mark.parametrize("name", STAGING_CASES)
def test_put_page_is_the_parent_s_page_bit_for_bit(name, width):
    """Every array, dtype, shape, the mask, ``live_prefix``, ``vrange``,
    ``ascending`` and the dictionary of the one-pass page are the parent's;
    it issued one put an array of the page."""
    from trino_tpu.data.page import Page
    from trino_tpu.exec import staging

    types, host, bucket_rows = _staging_case(name)
    page, puts = staging.put_page(types, host, bucket_rows=bucket_rows,
                                  width=width)
    if host is None:
        want = Page.all_dead(types)
        assert puts.count == 0 and page.num_rows == want.num_rows == 1
        _assert_same_arrays(
            [a for c in page.columns for a in _leaves(c)],
            [a for c in want.columns for a in _leaves(c)])
        assert np.array_equal(np.asarray(page.sel), np.asarray(want.sel))
        return
    leaves, sel = _parent_arrays(types, host, bucket_rows)
    got = [a for c in page.columns for a in _leaves(c)]
    _assert_same_arrays(got, leaves)
    if sel is None:
        assert page.sel is None and not page.live_prefix
    else:
        assert page.live_prefix
        _assert_same_arrays([np.asarray(page.sel)], [sel])
    for col, cd in zip(page.columns, host):
        assert col.dictionary is cd.dictionary and col.vrange == cd.vrange
        assert col.ascending == bool(cd.sorted)
    arrays = sum(a is not None for a in leaves) + (sel is not None)
    assert puts.count == arrays
    assert puts.nbytes == sum(a.nbytes for a in leaves if a is not None) + (
        0 if sel is None else sel.nbytes)


def test_a_length_that_is_its_own_bucket_is_put_without_a_copy():
    from trino_tpu.connector.spi import ColumnData
    from trino_tpu.exec import staging

    codes = np.arange(4096, dtype=np.int32)
    assert staging._one_pass(codes, 4096, np.int32) is codes
    page, puts = staging.put_page(
        [T.INTEGER], [ColumnData(T.INTEGER, codes)], bucket_rows=True)
    assert page.sel is None and puts.count == 1


def test_a_worker_scan_charges_one_put_an_array_and_none_on_a_hit():
    """A fresh staging of an N-array page charges N ``stagingPuts`` to the
    scan's kernel row, a device-cache hit none, and the cache entry holds
    the parent's bytes: the same arrays, the same ``nbytes``."""
    from trino_tpu.exec import staging
    from trino_tpu.obs.devprofiler import charge_to, new_kernel_row
    from trino_tpu.server.task import FragmentExecutor

    s = _session(staging_split_bytes=1 << 12)
    _tables(s)
    _root, scans = _scan_node(s, "select l_orderkey, l_price from lineitem")
    node = scans[0]
    conn = s.catalogs["memory"]
    splits = conn.get_splits("db", "lineitem", 4)
    assert len(splits) > 1
    rows, pages = [], []
    for _ in range(2):
        row = new_kernel_row("0", "TableScan", "eager")
        with charge_to(row):
            pages.append(FragmentExecutor(s, {node.id: splits}, {})
                         ._exec_TableScanNode(node))
        rows.append(row)
    host = staging.assemble_host_columns(
        node.column_names, node.column_types,
        [conn.scan(sp, list(node.column_names)) for sp in splits])
    leaves, sel = _parent_arrays(node.column_types, host, True)
    assert sel is not None  # 4,000 rows stage at a 4,096-row bucket
    want = [a for a in leaves if a is not None] + [sel]
    (entry,) = [e for e in DEVICE_CACHE.snapshot() if e["table"] == "lineitem"]
    assert rows[0]["stagingPuts"] == len(want) == 3
    assert rows[0]["stagedBytes"] == entry["bytes"] == sum(
        a.nbytes for a in want)
    assert (rows[1]["cacheHits"], rows[1]["stagingPuts"],
            rows[1]["stagedBytes"]) == (1, 0, 0)
