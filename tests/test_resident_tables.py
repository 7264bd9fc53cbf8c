"""Residency by table (ISSUE 30): the device cache holds ONE copy of a
table's projected columns and serves every binding of a statement from it.

The cache key digests only the part of a scan constraint the connector
ENFORCED on the rows it returned (``Connector.enforced_constraint``; the
tpch generator narrows by a table's monotone key column and by nothing
else), so q3 with another segment or date hits the entries the first
binding staged, while a key range on ``l_orderkey`` still gets its own,
byte-exact entry and a connector that cannot say keeps one entry per
constraint. The served cases run ``POST /v1/statement`` -> worker tasks ->
``FragmentExecutor`` scans -> ``devcache.cached_stage`` at ``tpch.tiny``
against the benchmark's plain reference: exact rows, no tolerance.
"""
import json
import urllib.request
from unittest import mock

import numpy as np
import pytest

import tests.conftest  # noqa: F401 — cpu mesh config
from benchmark.reference import tpch as reference
from trino_tpu import types as T
from trino_tpu.client.session import Session
from trino_tpu.connector import spi
from trino_tpu.connector.predicate import Domain, TupleDomain
from trino_tpu.connector.tpch import generator as gen
from trino_tpu.devcache import DEVICE_CACHE, HOST_CACHE, keys
from trino_tpu.obs import metrics as M
from trino_tpu.obs.timeline import compute_timeline

FIELDS = ("cacheHits", "cacheMisses", "stagedBytes", "stagingPuts")
Q3 = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = '{segment}' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '{date}'
  and l_shipdate > date '{date}'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""
FIRST = {"segment": "BUILDING", "date": "1995-03-15"}
OTHER_DATE = {"segment": "BUILDING", "date": "1995-03-21"}
OTHER_BOTH = {"segment": "HOUSEHOLD", "date": "1995-03-07"}
# (what the cache is to the statement, its binding)
STATEMENTS = [("cold", FIRST), ("warm", FIRST),
              ("warm from another binding", OTHER_DATE),
              ("warm from two other bindings", OTHER_BOTH)]
PROPS = {"catalog": "tpch", "schema": "tiny",
         "result_cache_enabled": "false", "device_cache_enabled": "true"}


def _clear_caches():
    DEVICE_CACHE.invalidate_all()
    HOST_CACHE.invalidate_all()


@pytest.fixture(scope="module")
def served():
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="res-w0")
    worker.start()
    assert coord.registry.wait_for_workers(1, timeout=15.0)
    yield coord
    worker.stop()
    coord.stop()
    _clear_caches()


def _client(coord, **props):
    from trino_tpu.client.remote import StatementClient

    return StatementClient(coord.base_url, dict(PROPS, **props))


def _get(coord, path):
    req = urllib.request.Request(f"{coord.base_url}{path}",
                                 headers={"X-Trino-User": "test"})
    return json.loads(urllib.request.urlopen(req).read())


def _counters(coord, client):
    kernels = _get(coord, f"/v1/query/{client.query_id}/profile")["kernels"]
    for k in kernels:  # the counters sit on the scans' rows and nowhere else
        if k["operator"] != "TableScan":
            assert not any(k[f] for f in FIELDS), k
    return {f: sum(k[f] for k in kernels) for f in FIELDS}


def _span_names(node, out):
    out.add(node["name"])
    for child in node["children"]:
        _span_names(child, out)
    return out


def _detail_sums_to_phases(timeline, tol):
    for phase, seconds in timeline["phases"].items():
        split = sum(v for k, v in timeline["detail"].items()
                    if k.startswith(phase + "/"))
        assert split == pytest.approx(seconds, abs=tol), (
            phase, timeline["detail"])


# ------------------------- q3 under three bindings, one copy of each table
@pytest.fixture(scope="module")
def q3_runs(served):
    """q3 cold, warm, and warm from other bindings with the cache on; then
    the same statements with the cache off, under a ``scan_signature`` that
    raises; what each left behind."""
    _clear_caches()
    on = _client(served)
    runs = []
    for label, binding in STATEMENTS:
        _cols, rows = on.execute(Q3.format(**binding))
        runs.append({
            "label": label, "binding": binding, "rows": rows,
            "counters": _counters(served, on),
            "timeline": on.stats["timeline"],
            "snapshot": DEVICE_CACHE.snapshot(),
            "bytes": DEVICE_CACHE.cached_bytes(),
        })
    off = _client(served, device_cache_enabled="false")
    no_digest = AssertionError("a cache-off statement digested a cache key")
    with mock.patch.object(keys, "scan_signature", side_effect=no_digest):
        for run in runs:
            _cols, run["rows_off"] = off.execute(Q3.format(**run["binding"]))
            run["counters_off"] = _counters(served, off)
            run["timeline_off"] = off.stats["timeline"]
            run["spans_off"] = _span_names(
                _get(served, f"/v1/query/{off.query_id}/trace")["root"],
                set())
    answers = reference.q3("tiny", [r["binding"] for r in runs])
    for run, want in zip(runs, answers):
        run["reference"] = want
    return runs


@pytest.mark.parametrize("which", range(len(STATEMENTS)),
                         ids=[s[0] for s in STATEMENTS])
def test_resident_rows_equal_the_reference(q3_runs, which):
    run = q3_runs[which]
    assert run["rows"], run["binding"]
    assert run["rows"] == run["reference"]
    assert run["rows_off"] == run["reference"]


def test_bindings_differ_in_their_answers(q3_runs):
    # or "the same rows" would prove nothing about the filters' enforcement
    answers = [json.dumps(r["reference"]) for r in q3_runs]
    assert len(set(answers)) == 3


def test_three_bindings_leave_one_entry_per_table_and_shard(q3_runs):
    first, last = q3_runs[0]["snapshot"], q3_runs[-1]["snapshot"]

    def ident(snap):
        return sorted((e["table"], e["shard"]) for e in snap)

    assert ident(first) == ident(last)
    assert len(set(ident(last))) == len(last)       # no duplicate per shard
    assert {e["table"] for e in last} == {"customer", "lineitem", "orders"}
    assert all(e["hits"] >= len(STATEMENTS) - 1 for e in last), last
    # the later bindings staged nothing and hold no copy of their own
    assert q3_runs[-1]["bytes"] == q3_runs[0]["bytes"] > 0


@pytest.mark.parametrize("which", range(len(STATEMENTS)),
                         ids=[s[0] for s in STATEMENTS])
def test_kernel_rows_count_what_the_cache_did(q3_runs, which):
    run = q3_runs[which]
    got = run["counters"]
    scans = len(run["snapshot"])
    if which == 0:
        assert (got["cacheHits"], got["cacheMisses"]) == (0, scans)
        # a miss copies exactly what it admits, an array of it a put
        assert got["stagedBytes"] == run["bytes"]
        assert got["stagingPuts"] >= 2 * scans
    else:
        assert got == {"cacheHits": scans, "cacheMisses": 0,
                       "stagedBytes": 0, "stagingPuts": 0}
        assert got["cacheHits"] >= 3


@pytest.mark.parametrize("which", range(len(STATEMENTS)),
                         ids=[s[0] for s in STATEMENTS])
def test_cache_off_counts_no_lookup_and_stages_every_time(q3_runs, which):
    run = q3_runs[which]
    got = run["counters_off"]
    assert (got["cacheHits"], got["cacheMisses"]) == (0, 0)
    # a bypass copies its scans every time: the bytes a miss admits
    assert got["stagedBytes"] == q3_runs[0]["bytes"] > 0
    assert got["stagingPuts"] == q3_runs[0]["counters"]["stagingPuts"]
    assert "device-staging/cache-lookup" not in run["timeline_off"]["detail"]
    assert "device-cache/lookup" not in run["spans_off"]


@pytest.mark.parametrize("which", [0, 2], ids=["cold", "warm"])
def test_detail_sums_to_phases_with_the_cache_lookup_label(q3_runs, which):
    tl = q3_runs[which]["timeline"]
    assert tl["detail"].get("device-staging/cache-lookup", 0) > 0
    # the protocol rounds each entry to the microsecond
    _detail_sums_to_phases(tl, 1e-5)
    if which:
        # a warm statement's staging is the lookups and the scans' shells
        assert not any(k.startswith("device-staging/") and k.split("/")[1]
                       in ("scan", "decode", "transfer")
                       for k in tl["detail"]), tl["detail"]


# ------------------ a connector that DOES prune: one entry per distinct pruning
def test_key_range_gets_its_own_byte_exact_entry(served):
    _clear_caches()
    client = _client(served)
    ranged = ("select count(*), sum(l_quantity) from lineitem "
              "where l_orderkey between {lo} and {hi}")
    li = gen.generate("lineitem", 0.01, 0, gen.table_row_count("orders", 0.01),
                      ["l_orderkey", "l_quantity"])
    okeys = np.asarray(li["l_orderkey"].values)
    qty = np.asarray(li["l_quantity"].values)

    def want(lo, hi):
        inside = (okeys >= lo) & (okeys <= hi)
        return int(inside.sum()), int(qty[inside].sum())

    def run(lo, hi):
        _cols, rows = client.execute(ranged.format(lo=lo, hi=hi))
        n, total = rows[0]
        return (int(n), int(round(float(total) * 100)),
                _counters(served, client))

    n, total, counters = run(1000, 2000)
    assert (n, total) == want(1000, 2000)
    assert (counters["cacheHits"], counters["cacheMisses"]) == (0, 1)
    (entry,) = DEVICE_CACHE.snapshot()
    assert entry["rows"] == n < len(okeys)       # the narrowed rows, no more
    # the same range again is a hit; another range is another entry
    assert run(1000, 2000)[2]["cacheHits"] == 1
    n2, total2, counters2 = run(3000, 9000)
    assert (n2, total2) == want(3000, 9000)
    assert counters2["cacheMisses"] == 1 and len(DEVICE_CACHE) == 2
    # a domain the generator does not read shares ONE copy of the table
    unkeyed = "select count(*) from lineitem where l_quantity < {q}"
    _cols, rows = client.execute(unkeyed.format(q=10))
    assert int(rows[0][0]) == int((qty < 1000).sum())
    assert _counters(served, client)["cacheMisses"] == 1
    _cols, rows = client.execute(unkeyed.format(q=20))
    assert int(rows[0][0]) == int((qty < 2000).sum())
    got = _counters(served, client)
    assert (got["cacheHits"], got["stagedBytes"]) == (1, 0)
    assert len(DEVICE_CACHE) == 3
    whole = max(DEVICE_CACHE.snapshot(), key=lambda e: e["rows"])
    assert whole["rows"] == len(okeys)


# ------------------------------------------------------ the SPI's new method
_SHIPDATE = Domain.range(low=9204, low_inclusive=False)
_ORDER_RANGE = Domain.range(low=100, high=200)
_ORDER_SET = Domain.from_values([5, 7, 64])


@pytest.mark.parametrize("table, domains, kept", [
    ("lineitem", {"l_shipdate": _SHIPDATE, "l_orderkey": _ORDER_RANGE},
     {"l_orderkey": _ORDER_RANGE}),
    ("lineitem", {"l_orderkey": _ORDER_SET, "l_discount": _ORDER_RANGE},
     {"l_orderkey": _ORDER_SET}),
    ("lineitem", {"l_shipdate": _SHIPDATE}, None),
    ("orders", {"o_orderdate": _SHIPDATE, "o_custkey": _ORDER_SET}, None),
    ("orders", {"o_orderkey": _ORDER_SET, "o_orderdate": _SHIPDATE},
     {"o_orderkey": _ORDER_SET}),
    ("customer", {"c_mktsegment": Domain.from_values(["BUILDING"])}, None),
    ("partsupp", {"ps_partkey": _ORDER_RANGE, "ps_suppkey": _ORDER_SET},
     {"ps_partkey": _ORDER_RANGE}),
])
def test_tpch_enforces_only_the_monotone_key_domain(table, domains, kept):
    from trino_tpu.connector.tpch.connector import TpchConnector

    conn = TpchConnector()
    offered = TupleDomain(dict(domains))
    got = conn.enforced_constraint("tiny", table, offered)
    if kept is None:
        assert got is None
    else:
        assert got.domains == kept
    # what it leaves out really changes nothing the connector returns
    full = conn.get_splits("tiny", table, 4, constraint=offered)
    only = conn.get_splits("tiny", table, 4, constraint=got)
    assert [(s.lo, s.hi) for s in full] == [(s.lo, s.hi) for s in only]
    columns = list(domains)
    for a, b in zip(full, only):
        rows_a = conn.scan(a, columns, constraint=offered)
        rows_b = conn.scan(b, columns, constraint=got)
        for c in columns:
            assert np.array_equal(np.asarray(rows_a[c].values),
                                  np.asarray(rows_b[c].values))
    assert conn.enforced_constraint("tiny", table, None) is None


def test_tpcds_enforces_nothing():
    from trino_tpu.connector.tpcds.connector import TpcdsConnector

    td = TupleDomain({"ss_item_sk": _ORDER_RANGE})
    assert TpcdsConnector().enforced_constraint(
        "tiny", "store_sales", td) is None


@pytest.mark.parametrize("module, cls", [
    ("trino_tpu.connector.memory.connector", "MemoryConnector"),
    ("trino_tpu.connector.sqlite.connector", "SqliteConnector"),
    ("trino_tpu.connector.filesystem.connector", "FileSystemConnector"),
    ("trino_tpu.connector.blackhole.connector", "BlackHoleConnector"),
    ("trino_tpu.connector.system.connector", "SystemConnector"),
])
def test_a_connector_that_cannot_say_keeps_the_whole_constraint(module, cls):
    import importlib

    connector = getattr(importlib.import_module(module), cls)
    assert connector.enforced_constraint is spi.Connector.enforced_constraint
    td = TupleDomain({"k": _ORDER_RANGE, "v": _ORDER_SET})
    assert spi.Connector.enforced_constraint(None, "s", "t", td) is td
    assert spi.Connector.enforced_constraint(None, "s", "t", None) is None


def _memory_session():
    s = Session({"catalog": "memory", "schema": "db",
                 "device_cache_enabled": True})
    s.catalogs["memory"].create_table(
        "db", "t", [("a", T.BIGINT), ("b", T.BIGINT)],
        [(i, i * 2) for i in range(100)])
    return s


def test_memory_keys_still_digest_the_whole_constraint():
    """A connector that keeps the default: statements that differ in a
    pushed constraint keep their own entries, as before, and their rows."""
    _clear_caches()
    s = _memory_session()
    assert s.execute("select count(*) from t where a < 10").rows == [(10,)]
    assert s.execute("select count(*) from t where a < 20").rows == [(20,)]
    assert s.execute("select count(*) from t where a < 10").rows == [(10,)]
    sigs = {e["signature"] for e in DEVICE_CACHE.snapshot()}
    assert len(sigs) == len(DEVICE_CACHE) == 2
    assert sorted(e["hits"] for e in DEVICE_CACHE.snapshot()) == [0, 1]
    _clear_caches()


def test_insert_moves_data_version_and_the_next_read_restages():
    _clear_caches()
    s = _memory_session()
    sql = "select sum(a), count(*) from t"
    assert s.execute(sql).rows == [(4950, 100)]
    (before,) = DEVICE_CACHE.snapshot()
    misses, hits = M.DEVICE_CACHE_MISSES.value(), M.DEVICE_CACHE_HITS.value()
    assert s.execute(sql).rows == [(4950, 100)]                # warm
    assert M.DEVICE_CACHE_MISSES.value() == misses
    assert M.DEVICE_CACHE_HITS.value() == hits + 1
    s.execute("insert into t values (1000, 2000)")
    assert s.execute(sql).rows == [(5950, 101)]
    (after,) = DEVICE_CACHE.snapshot()          # the stale entry is dropped
    assert after["version"] != before["version"]
    assert after["rows"] == 101
    assert M.DEVICE_CACHE_MISSES.value() == misses + 1
    assert M.DEVICE_CACHE_HITS.value() == hits + 1
    _clear_caches()


# ------------------------------------------- level two, the new label, by hand
def _span(name, start, dur, sid):
    return {"name": name, "start": start, "durationS": dur, "spanId": sid,
            "parentId": None, "attributes": {}}


def test_cache_lookup_is_a_staging_detail_and_detail_sums_to_phases():
    """A miss: the loader's ``staging/*`` spans open later, inside the
    lookup, and win; what is left of the lookup (LRU, admission) reads
    ``cache-lookup``. A hit is all ``cache-lookup``."""
    spans = [
        _span("query", 0.0, 10.0, "r"),
        _span("device/execute", 1.0, 8.0, "x"),
        _span("operator/TableScan", 2.5, 2.0, "s"),
        _span("device/staging", 2.6, 1.5, "st"),
        _span("device-cache/lookup", 2.7, 1.3, "lk"),     # a miss
        _span("staging/scan", 2.8, 0.4, "sc"),
        _span("staging/transfer", 3.3, 0.6, "tr"),
        _span("operator/TableScan", 5.0, 0.5, "s2"),
        _span("device/staging", 5.1, 0.3, "st2"),
        _span("device-cache/lookup", 5.2, 0.1, "lk2"),    # a hit
    ]
    d = compute_timeline(spans, 0.0, 10.0).to_dict()
    detail = d["detail"]
    want = {
        "device-staging/cache-lookup": 0.1 + 0.1 + 0.1 + 0.1,
        "device-staging/scan": 0.4,
        "device-staging/transfer": 0.6,
        "device-staging/op:TableScan": 0.1 + 0.1 + 0.1 + 0.1,
    }
    for key, seconds in want.items():
        assert detail[key] == pytest.approx(seconds, abs=1e-9), key
    assert d["phases"]["device-staging"] == pytest.approx(1.8)
    _detail_sums_to_phases(d, 1e-7)
    # level one does not read the label: the same phases without it
    plain = compute_timeline(
        [s for s in spans if s["name"] != "device-cache/lookup"],
        0.0, 10.0).to_dict()
    assert plain["phases"] == d["phases"]
