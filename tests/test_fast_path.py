"""Short-query fast path (server/fastpath.py) + the point-lookup mix (ISSUE 10).

- the eligibility predictor must never drift from the fragmenter: it is
  compared against ``fragment_plan`` across the whole TPC-H suite;
- fast-path runs return EXACTLY the distributed path's rows on TPC-H
  point queries (and a single-stage aggregation), with the decision
  visible in spans, query info, system.runtime.queries, the statement
  stats block, and the CLI summary;
- multi-stage plans and over-threshold scans stay distributed;
- the point-lookup mix a serving deployment sends (prepared lookups by
  unique key, concurrent clients) fails no statement and takes the fast
  path on every statement when it is on, on none when it is off.
"""
from __future__ import annotations

import pytest

import tests.conftest  # noqa: F401 — cpu mesh config
from trino_tpu.obs import metrics as M


# --------------------------------------------------------------- predictor
def test_predictor_never_drifts_from_fragmenter():
    """predicted_stage_count == len(fragment_plan) - 1 for every TPC-H
    query (the root single fragment is not counted): the fast-path
    decision mirrors the fragmenter's cut logic exactly."""
    from tests import tpch_sql
    from trino_tpu.client.session import Session
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.server.fastpath import predicted_stage_count
    from trino_tpu.sql.planner.fragmenter import fragment_plan

    s = Session({"catalog": "tpch", "schema": "tiny"})
    checked = 0
    for qnum, sql in sorted(tpch_sql.QUERIES.items()):
        root = plan_sql(s, sql)
        pred = predicted_stage_count(s, root)
        actual = len(fragment_plan(root, s)) - 1
        assert pred == actual, f"Q{qnum}: predicted {pred}, actual {actual}"
        checked += 1
    assert checked >= 20  # the full TPC-H suite participated


def test_decision_gates():
    from trino_tpu.client.session import Session
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.server.fastpath import fast_path_decision

    off = Session({"catalog": "tpch", "schema": "tiny"})
    root = plan_sql(off, "select 1")
    take, reason = fast_path_decision(off, root)
    assert not take and "disabled" in reason

    on = Session({"catalog": "tpch", "schema": "tiny",
                  "short_query_fast_path": True})
    root = plan_sql(on, "select o_orderkey from orders where o_orderkey = 7")
    take, reason = fast_path_decision(on, root)
    assert take and "single-stage" in reason

    # a non-colocated join fragments into >1 stage: stays distributed
    # (orders JOIN lineitem on orderkey is COLOCATED in the tpch
    # connector — same partitioning family — and legitimately single-
    # stage; customer joins on custkey are not)
    root = plan_sql(on, "select count(*) from orders o, customer c "
                        "where o.o_custkey = c.c_custkey")
    take, reason = fast_path_decision(on, root)
    assert not take and "stages" in reason

    # scan-size guard
    tiny_cap = Session({"catalog": "tpch", "schema": "tiny",
                        "short_query_fast_path": True,
                        "fast_path_max_scan_rows": 10})
    root = plan_sql(tiny_cap,
                    "select o_orderkey from orders where o_orderkey = 7")
    take, reason = fast_path_decision(tiny_cap, root)
    assert not take and "fast_path_max_scan_rows" in reason


# ------------------------------------------------------------ cluster tests
@pytest.fixture(scope="module")
def cluster():
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"fw{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _client(coord, fast: bool, **props):
    from trino_tpu.client.remote import StatementClient

    return StatementClient(coord.base_url, {
        "catalog": "tpch", "schema": "tiny",
        "short_query_fast_path": "true" if fast else "false", **props})


def _last_query(coord):
    return coord.queries[sorted(coord.queries)[-1]]


POINT_QUERIES = (
    "select o_orderkey, o_totalprice, o_orderstatus from orders "
    "where o_orderkey = 7",
    "select l_orderkey, l_linenumber, l_quantity from lineitem "
    "where l_orderkey = 1 order by l_linenumber",
    "select c_custkey, c_name from customer where c_custkey = 19",
    # single-stage aggregation (partial on workers, final on coordinator
    # — still one distributed stage, so the fast path claims it)
    "select o_orderstatus, count(*), sum(o_totalprice) from orders "
    "group by o_orderstatus order by o_orderstatus",
)


def test_fast_path_equals_distributed_on_point_queries(cluster):
    """Result equality: every point query returns bit-identical rows on
    both control-plane paths, with the right spans on each."""
    coord, _ = cluster
    fast = _client(coord, True)
    dist = _client(coord, False)
    for sql in POINT_QUERIES:
        cols_f, rows_f = fast.execute(sql)
        qf = _last_query(coord)
        names_f = {s["name"] for s in qf.tracer.to_dicts()}
        assert "fastpath/execute" in names_f, sql
        assert "schedule" not in names_f and "fragment" not in names_f
        assert qf.fast_path == "fast-path"
        assert fast.stats.get("fastPath") == "fast-path"

        cols_d, rows_d = dist.execute(sql)
        qd = _last_query(coord)
        names_d = {s["name"] for s in qd.tracer.to_dicts()}
        assert "schedule" in names_d and "fastpath/execute" not in names_d
        assert qd.fast_path == "distributed"
        assert cols_f == cols_d and rows_f == rows_d, sql


def test_fast_path_composes_with_prepared_statements(cluster):
    """The full serving path: EXECUTE of a prepared point query on the
    fast path — bind + plan-cache hit + coordinator-local run, nothing
    else (the QPS bench's hot loop, asserted span by span)."""
    coord, _ = cluster
    c = _client(coord, True)
    c.execute("PREPARE fpq FROM "
              "select o_orderkey, o_totalprice from orders "
              "where o_orderkey = ?")
    c.execute("EXECUTE fpq USING 7")  # plans once
    _, rows = c.execute("EXECUTE fpq USING 32")
    q = _last_query(coord)
    names = {s["name"] for s in q.tracer.to_dicts()}
    assert {"prepare/bind", "plan-cache/hit", "fastpath/execute"} <= names
    for absent in ("parse", "analyze/plan", "optimize", "fragment",
                   "schedule", "execute/root-fragment"):
        assert absent not in names, absent
    assert rows == [[32, "304118.14"]]


def test_fast_path_visible_everywhere(cluster):
    """Decision visibility: metrics, query info, EXPLAIN ANALYZE,
    system.runtime.queries.fast_path, CLI summary."""
    from trino_tpu.client.cli import render_summary

    coord, _ = cluster
    c = _client(coord, True)
    f0 = M.FAST_PATH_QUERIES.value("fast-path")
    c.execute("select o_orderkey from orders where o_orderkey = 7")
    assert M.FAST_PATH_QUERIES.value("fast-path") == f0 + 1
    q = _last_query(coord)
    assert q.info()["fastPath"] == "fast-path"
    assert "fast-path" in render_summary(c.stats)
    qid = c.query_id

    _, rows = c.execute(
        f"select fast_path from system.runtime.queries "
        f"where query_id = '{qid}'")
    assert rows == [["fast-path"]]

    _, plan_rows = c.execute(
        "explain analyze select o_orderkey from orders "
        "where o_orderkey = 7")
    text = "\n".join(r[0] for r in plan_rows)
    assert "Fast path: coordinator-local" in text


def test_fast_path_stats_rollup(cluster):
    """The synthetic local task feeds the stage/query rollups: the stats
    block reports real rows/splits for a fast-path query."""
    coord, _ = cluster
    c = _client(coord, True)
    c.execute("select count(*) from orders")
    assert c.stats["totalRows"] > 0  # scan input rows, not zero
    assert c.stats["completedSplits"] >= 1
    q = _last_query(coord)
    tasks = q.task_records()
    assert len(tasks) == 1 and tasks[0]["state"] == "FINISHED"


def test_big_scan_stays_distributed(cluster):
    coord, _ = cluster
    c = _client(coord, True, fast_path_max_scan_rows="10")
    c.execute("select count(*) from orders")
    q = _last_query(coord)
    assert q.fast_path == "distributed"
    names = {s["name"] for s in q.tracer.to_dicts()}
    assert "schedule" in names


def test_fast_path_respects_result_cache(cluster):
    """Caches front the fast path exactly like the distributed path."""
    coord, _ = cluster
    c = _client(coord, True, result_cache_enabled="true")
    sql = "select o_clerk from orders where o_orderkey = 39"
    c.execute(sql)
    assert c.cache_status == "MISS"
    _, rows = c.execute(sql)
    assert c.cache_status == "HIT"
    q = _last_query(coord)
    names = {s["name"] for s in q.tracer.to_dicts()}
    assert "fastpath/execute" not in names  # served from cache, no run


# ------------------------------------------------------ the point-lookup mix
# What the serving deployment sends: a prepared point lookup on ``orders``
# by a key no other request uses (a repeated key would be a result-cache
# hit, which runs no path at all), from concurrent DBAPI clients, with the
# result and device caches on as a serving deployment runs them. With the
# fast path off the same statement goes as plain SQL with its literal.
POINT_SQL = ("select o_orderkey, o_totalprice, o_orderstatus "
             "from orders where o_orderkey = ?")
KNOWN_PRESENT_KEY = 7  # exists at every tpch scale
MIX_CLIENTS, MIX_REQUESTS = 2, 20


@pytest.fixture(scope="module", params=[True, False],
                ids=["serving-on", "serving-off"])
def point_mix(request, cluster):
    import threading

    from trino_tpu.client import dbapi

    coord, _ = cluster
    serving_on = request.param
    props = {"result_cache_enabled": "true", "device_cache_enabled": "true",
             "short_query_fast_path": "true" if serving_on else "false"}

    def lookup(cur, key):
        if serving_on:
            return cur.execute(POINT_SQL, (key,))
        return cur.execute(POINT_SQL.replace("?", str(key)))

    probe = lookup(
        dbapi.connect(coordinator_url=coord.base_url, **props).cursor(),
        KNOWN_PRESENT_KEY)
    before = {path: M.FAST_PATH_QUERIES.value(path)
              for path in ("fast-path", "distributed")}
    failures, paths = [], []

    def client_loop(ci):
        cur = dbapi.connect(coordinator_url=coord.base_url, **props).cursor()
        base = (2_000_000 if serving_on else 1_000_000) + ci * 100_000
        for r in range(MIX_REQUESTS):
            try:
                lookup(cur, base + r)
            except Exception as e:  # noqa: BLE001 — counted, then asserted
                failures.append(f"{base + r}: {e}")
                continue
            paths.append((cur.stats or {}).get("fastPath"))

    threads = [threading.Thread(target=client_loop, args=(ci,))
               for ci in range(MIX_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {
        "serving_on": serving_on, "probe_rows": probe.rowcount,
        "failures": failures, "paths": paths,
        "counted": {path: M.FAST_PATH_QUERIES.value(path) - n
                    for path, n in before.items()},
    }


def test_point_mix_fails_no_statement(point_mix):
    assert point_mix["failures"] == []
    assert len(point_mix["paths"]) == MIX_CLIENTS * MIX_REQUESTS


def test_point_mix_probe_returns_the_known_row(point_mix):
    assert point_mix["probe_rows"] == 1


def test_point_mix_takes_the_fast_path_only_when_it_is_on(point_mix):
    """Every statement of the mix reports the coordinator-local path with
    ``short_query_fast_path`` on and none does with it off, in the
    statement's own stats and in ``trino_tpu_fast_path_queries_total``."""
    n = MIX_CLIENTS * MIX_REQUESTS
    taken, other = (("fast-path", "distributed") if point_mix["serving_on"]
                    else ("distributed", "fast-path"))
    assert point_mix["paths"] == [taken] * n
    assert point_mix["counted"] == {taken: n, other: 0}
