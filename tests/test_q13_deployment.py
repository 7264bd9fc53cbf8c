"""The deployment ``tpch_sf10_q13`` (TPC-H Q13 through the server), at tiny
and SF 0.1 on the CPU and plan-only at SF 10: customer LEFT JOIN orders
under the ON clause's NOT LIKE, expanded against a non-unique build
(``Executor.expand_join``: every match, and a null-extended row for each
customer with none), and the per-customer count finished in the join's
hash fragment, because its group key is the partitioned join's key on the
preserved side (``fragmenter._join_fragment_of``): only the ``c_count``
states are gathered. The ``join/expand`` span and the ``expandedRows`` and
``joinFragmentAggs`` counters say what the join and the placement did."""
import json
import os
import sqlite3

import pytest

from benchmark import spec
from tests import tpch_oracle as oracle
from tests.tpch_sql import QUERIES
from trino_tpu import Session
from trino_tpu.exec.query import plan_sql, run_query
from trino_tpu.sql.planner import fragmenter
from trino_tpu.sql.planner import plan as P

HERE = os.path.dirname(os.path.abspath(__file__))
Q13 = spec.load_template("q13").sql
WORD1 = ["special", "pending", "unusual", "express"]
WORD2 = ["packages", "requests", "accounts", "deposits"]
PAIRS = [(a, b) for a in WORD1 for b in WORD2]
# a broadcast limit under tiny's 15,000 orders partitions the join there,
# as SF 10's size does
PARTITIONED = {"join_max_broadcast_rows": "1024"}


def _q13(word1, word2):
    return Q13.format(word1=word1, word2=word2)


def _session(schema, **props):
    return Session({"catalog": "tpch", "schema": schema, **props})


def _explain(schema, sql, **props):
    rows = run_query(_session(schema, **props),
                     "EXPLAIN (TYPE DISTRIBUTED) " + sql).rows
    return "\n".join(r[0] for r in rows)


def _fragments(schema, sql, **props):
    from trino_tpu.sql.planner.optimizer import stamp_join_estimates

    s = _session(schema, **props)
    root = plan_sql(s, sql)
    stamp_join_estimates(root, s)
    return fragmenter.fragment_plan(root, s)


def _nodes(root, kind):
    return [n for n in P.walk_plan(root) if isinstance(n, kind)]


def _shape(node):
    """The fragment's operators, top down, with their steps and kinds."""
    out = []
    for n in P.walk_plan(node):
        name = type(n).__name__.replace("Node", "")
        if isinstance(n, P.AggregationNode):
            name += f"[{n.step}{'/' + n.distribution if n.distribution else ''}]"
        elif isinstance(n, P.JoinNode):
            name += f"[{n.join_type}/{n.distribution}]"
        elif isinstance(n, fragmenter.RemoteSourceNode):
            name += f"[{n.exchange_type}]"
        out.append(name)
    return out


def _without_the_rule(monkeypatch):
    """The fragmenter as it was before the rule: no aggregation is finished
    in a join's fragment."""
    monkeypatch.setattr(fragmenter, "_join_fragment_of",
                        lambda node, src, fragments: None)


# --------------------------------------------------- the plan, at SF 10
def test_q13_counts_per_customer_in_the_joins_hash_fragment():
    """``[single] Sort <- Aggregation [final] <- gather_states`` over
    ``[hash] Aggregation [partial] <- ... Aggregation [single/partitioned]
    <- Join [left/partitioned]``: no joined row is gathered."""
    frags = _fragments("sf10", _q13("special", "requests"))
    by_kind = {f.partitioning: f for f in frags}
    assert sorted(f.partitioning for f in frags) == [
        "hash", "single", "source", "source"]
    assert _shape(by_kind["single"].root) == [
        "Output", "Sort", "Aggregation[final]", "RemoteSource[gather_states]"]
    assert _shape(by_kind["hash"].root) == [
        "Aggregation[partial]", "Compact", "Project",
        "Aggregation[single/partitioned]", "Project", "Join[left/partitioned]",
        "RemoteSource[partitioned]", "RemoteSource[partitioned]"]
    (join,) = _nodes(by_kind["hash"].root, P.JoinNode)
    assert (join.est_probe_rows, join.est_build_rows) == (1500000, 13500000)
    text = fragmenter.format_fragments(frags)
    assert "Aggregation [single/partitioned] keys=[0]" in text
    assert "RemoteSource[gather] " not in text
    assert [f.id for f in frags] == list(range(len(frags)))


def test_the_parent_gathered_every_joined_row(monkeypatch):
    _without_the_rule(monkeypatch)
    frags = _fragments("sf10", _q13("special", "requests"))
    (single,) = [f for f in frags if f.partitioning == "single"]
    assert _shape(single.root)[-3:] == [
        "Aggregation[single]", "Project", "RemoteSource[gather]"]


@pytest.mark.parametrize("query", [1, 3, 6, 9, 13, 18, 21])
def test_only_q13_and_q18_plan_otherwise_at_sf10(query, monkeypatch):
    """Of the benchmark's queries only Q13's and Q18's aggregations group by
    their partitioned join's key: q1, q3, q6, Q9 and Q21 plan byte for byte
    as the parent's fragmenter does (q3 groups by ``l_orderkey``, not
    ``o_custkey``; Q9 and Q21 aggregate partial/final in their source
    fragments)."""
    now = _explain("sf10", QUERIES[query])
    _without_the_rule(monkeypatch)
    was = _explain("sf10", QUERIES[query])
    assert (now != was) == (query in (13, 18))
    if query in (13, 18):
        assert "/partitioned]" in now.replace("Join [inner/partitioned]", "")


def test_the_argued_q18_plans_move_only_the_aggregation():
    """``q13_argued_plans.json`` holds Q18's SF 10 cases since the rule:
    the pinned text's lines, the top aggregation marked ``partitioned`` and
    moved under the gather, into the join's fragment."""
    with open(os.path.join(HERE, "q13_argued_plans.json"),
              encoding="utf-8") as f:
        argued = json.load(f)
    with open(os.path.join(HERE, "q9_parent_plans.json"),
              encoding="utf-8") as f:
        pinned = json.load(f)
    assert sorted(argued) == sorted(c for c in pinned if "/q18/" in c)
    for case, text in argued.items():
        was = pinned[case]
        strip = lambda t: sorted(ln.strip() for ln in t.splitlines())  # noqa: E731
        assert strip(text.replace("[single/partitioned]", "[single]")) == \
            strip(was)
        hash_part = text.split("Fragment 2 [hash]")[1].split("Fragment 1")[0]
        assert "Aggregation [single/partitioned]" in hash_part


# --------------------------------- the rule's limits, against sqlite
LEFT_BY_NULLABLE = ("select o_custkey, count(*) from customer left join "
                    "orders on c_custkey = o_custkey group by o_custkey")
INNER_BY_BUILD = ("select c_custkey, count(*), sum(o_orderkey) from orders "
                  "join customer on o_custkey = c_custkey group by c_custkey")


def test_a_left_join_grouped_by_its_nullable_key_keeps_its_gather():
    """Every partition holds customers with no order, whose ``o_custkey``
    is NULL: grouped by it in the join's fragment, each task would answer
    its own NULL group."""
    frags = _fragments("tiny", LEFT_BY_NULLABLE, **PARTITIONED)
    (join,) = [j for f in frags for j in _nodes(f.root, P.JoinNode)]
    assert (join.join_type, join.distribution) == ("left", "partitioned")
    assert not [a for f in frags for a in _nodes(f.root, P.AggregationNode)
                if a.distribution == "partitioned"]
    (hash_frag,) = [f for f in frags if f.partitioning == "hash"]
    assert hash_frag.root is join


def test_an_inner_join_grouped_by_its_build_key_is_placed():
    frags = _fragments("tiny", INNER_BY_BUILD, **PARTITIONED)
    (join,) = [j for f in frags for j in _nodes(f.root, P.JoinNode)]
    assert (join.join_type, join.distribution) == ("inner", "partitioned")
    # customer is the build: the group key is the build side's
    by_id = {f.id: f for f in frags}
    build = by_id[join.right.fragment_id].root
    assert [n.table for n in _nodes(build, P.TableScanNode)] == ["customer"]
    (hash_frag,) = [f for f in frags if f.partitioning == "hash"]
    (agg,) = _nodes(hash_frag.root, P.AggregationNode)
    assert agg.distribution == "partitioned" and agg is hash_frag.root


def _sqlite(sql):
    db = sqlite3.connect(":memory:")
    for table, cols in (("customer", ["c_custkey"]),
                        ("orders", ["o_orderkey", "o_custkey"])):
        db.execute(f"create table {table} ({', '.join(cols)})")
        db.executemany(
            f"insert into {table} values ({', '.join('?' * len(cols))})",
            [tuple(r[c] for c in cols)
             for r in oracle.load_table("tiny", table, cols)])
    return db.execute(sql).fetchall()


def _sorted(rows):
    return sorted((tuple(r) for r in rows),
                  key=lambda r: tuple((v is None, v) for v in r))


# ------------------------------------------ through POST /v1/statement
PROPS = {"catalog": "tpch", "schema": "tiny", "result_cache_enabled": "false",
         "device_cache_enabled": "true", "query_max_execution_time": "15m"}


@pytest.fixture(scope="module")
def served():
    """A coordinator and TWO workers: a hash fragment runs as two tasks,
    so a group split across partitions would show as two rows."""
    from trino_tpu.devcache.cache import DEVICE_CACHE
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    DEVICE_CACHE.invalidate_all()
    coord = CoordinatorServer()
    coord.start()
    workers = [WorkerServer(coordinator_url=coord.base_url,
                            node_id=f"q13w{i}") for i in range(2)]
    for w in workers:
        w.start()
    try:
        assert coord.registry.wait_for_workers(2, timeout=15.0)
        yield coord
    finally:
        for w in workers:
            w.stop()
        coord.stop()
        DEVICE_CACHE.invalidate_all()


def _execute(coord, sql, schema="tiny", path="distributed", **props):
    from trino_tpu.client.remote import StatementClient

    client = StatementClient(coord.base_url,
                             dict(PROPS, schema=schema, **props))
    _cols, rows = client.execute(sql)
    assert client.stats["state"] == "FINISHED"
    assert client.stats["fastPath"] == path
    return client, rows


def _profile(coord, query_id):
    from trino_tpu.server import wire

    return wire.json_request(
        "GET", f"{coord.base_url}/v1/query/{query_id}/profile")["kernels"]


@pytest.mark.parametrize("word1, word2", PAIRS)
def test_served_q13_equals_both_oracles_at_tiny(served, word1, word2):
    from benchmark.reference import tpch_q13

    _client, rows = _execute(served, _q13(word1, word2), **PARTITIONED)
    want = tpch_q13.q13("tiny", [{"word1": word1, "word2": word2}])[0]
    assert rows == want and want[0][0] == 0
    assert [list(r) for r in oracle.q13("tiny", word1, word2)] == want


@pytest.mark.parametrize("word1, word2", [("special", "requests"),
                                          ("pending", "deposits")])
def test_served_q13_equals_both_oracles_at_sf0_1(served, word1, word2):
    """At SF 0.1 orders' 150,000 rows are over the broadcast limit: the
    join partitions as it does at SF 10, with nothing forced."""
    from benchmark.reference import tpch_q13

    client, rows = _execute(served, _q13(word1, word2), schema="sf0_1")
    want = tpch_q13.q13("sf0_1", [{"word1": word1, "word2": word2}])[0]
    assert rows == want
    assert [list(r) for r in oracle.q13("sf0_1", word1, word2)] == want
    kernels = _profile(served, client.query_id)
    assert sum(k["joinFragmentAggs"] for k in kernels) == 2   # two tasks


def test_served_rule_limits_equal_sqlite(served):
    for sql in (LEFT_BY_NULLABLE, INNER_BY_BUILD):
        _client, rows = _execute(served, sql, **PARTITIONED)
        assert _sorted(rows) == _sorted(_sqlite(sql))
    # both sides of the LEFT join occur: a NULL group and real keys
    _client, rows = _execute(served, LEFT_BY_NULLABLE, **PARTITIONED)
    assert sum(1 for r in rows if r[0] is None) == 1 and len(rows) > 900


@pytest.mark.parametrize("quantity", [200, 250, 280])
def test_q18_answers_as_its_old_plan_did(served, quantity, monkeypatch):
    """Q18's top aggregation groups by ``c_custkey``, the key of its
    partitioned inner join: it runs in the join's fragment now. Same rows
    as the parent's plan, which gathered the joined rows first, and as the
    reference."""
    from benchmark.reference import tpch

    sql = spec.load_template("q18").sql.format(quantity=quantity)
    client, rows = _execute(served, sql, **PARTITIONED)
    assert sum(k["joinFragmentAggs"]
               for k in _profile(served, client.query_id)) == 2
    _without_the_rule(monkeypatch)
    client, was = _execute(served, sql, **PARTITIONED)
    assert sum(k["joinFragmentAggs"]
               for k in _profile(served, client.query_id)) == 0
    assert rows == was == tpch.q18("tiny", [{"quantity": quantity}])[0]
    assert rows


def test_the_counters_reach_the_profile_and_the_kernels_table(served):
    client, _rows = _execute(served, _q13("special", "requests"),
                             **PARTITIONED)
    # the span reaches the statement's own ledger, as the benchmark reads it
    detail = client.stats["timeline"]["detail"]
    assert [k for k in detail if k.endswith("/expand-join")]
    kernels = _profile(served, client.query_id)
    expanded = [k for k in kernels if k["expandedRows"]]
    assert expanded and {k["operator"] for k in expanded} == {"Join"}
    assert all(k["expandedRows"] & (k["expandedRows"] - 1) == 0
               for k in expanded)            # a power of two: a capacity
    placed = [k for k in kernels if k["joinFragmentAggs"]]
    assert {k["operator"] for k in placed} == {"Aggregation"}
    rows_, aggs = (sum(k[f] for k in kernels)
                   for f in ("expandedRows", "joinFragmentAggs"))
    assert aggs == 2 and rows_ >= 16384
    _client, table = _execute(
        served, "select sum(expanded_rows), sum(join_fragment_aggs) "
        f"from system.runtime.kernels where query_id = '{client.query_id}'",
        path="local-catalog")
    assert [int(v) for v in table[0]] == [rows_, aggs]


# ------------------------------------------------ the span, in one process
def test_the_expand_join_span_splits_op_join_alone():
    """``join/expand`` opens inside its Join's operator span and carries
    what it expanded; in the ledger's second level it takes its instants
    from ``op:Join`` alone."""
    from trino_tpu.exec.executor import Executor
    from trino_tpu.obs import timeline
    from trino_tpu.obs import trace as tracing

    s = _session("tiny")
    root = plan_sql(s, _q13("special", "requests"))
    tracer = tracing.Tracer()
    with tracing.activate(tracer):
        ex = Executor(s)
        ex.execute_checked(root)
    spans = tracer.spans()
    (sp,) = [sp for sp in spans if sp.name == "join/expand"]
    by_id = {x.span_id: x for x in spans}
    assert by_id[sp.parent_id].name == "operator/Join"
    assert sp.attributes == {"join_type": "left", "probe_rows": 1500,
                             "build_rows": sp.attributes["build_rows"],
                             "pairs": 16384}
    assert 14000 < sp.attributes["build_rows"] <= 15000
    assert sum(r["expandedRows"] for r in ex.kernel_stats.values()) == 16384
    # op:Join 0..10 s, join/expand 2..8, a read 3..4
    t0 = 1000.0
    dicts = [{"name": "query", "start": t0, "durationS": 10.0},
             {"name": "device/execute", "start": t0, "durationS": 10.0},
             {"name": "operator/Join", "start": t0, "durationS": 10.0},
             {"name": "join/expand", "start": t0 + 2, "durationS": 6.0},
             {"name": "host/sync", "start": t0 + 3, "durationS": 1.0}]
    detail = timeline.compute_timeline(dicts, t0, t0 + 10).detail
    assert detail == {"device-execute/op:Join": 4.0,
                      "device-execute/expand-join": 5.0,
                      "device-execute/host-sync": 1.0}


# ------------------------------ the reference can tell each wrong answer
@pytest.mark.parametrize("variant", ["inner", "count_star", "where"])
@pytest.mark.parametrize("schema", ["tiny", "sf0_1"])
def test_each_mistake_changes_the_answer(schema, variant):
    """An inner join, ``count(*)`` for ``count(o_orderkey)``, or the NOT
    LIKE in a WHERE: each answers otherwise for both of a cell's kinds of
    binding, so the comparison that decides ``correct`` catches it. A
    float32 control cannot fail: the counts are exact in it."""
    import numpy as np

    from benchmark.reference import tpch_q13

    bindings = [{"word1": "special", "word2": "requests"},
                {"word1": "express", "word2": "accounts"}]  # excludes none
    exact = tpch_q13.q13(schema, bindings)
    wrong = tpch_q13.q13(schema, bindings, variant=variant)
    assert all(a != b for a, b in zip(exact, wrong))
    assert all(rows[0][0] == 0 for rows in exact)
    if variant == "count_star":
        assert all(0 not in [c for c, _d in rows] for rows in wrong)
    else:
        assert all(rows[1:] == w for rows, w in zip(exact, wrong))
    assert tpch_q13.q13(schema, bindings, dtype=np.float32) == exact


# --------------------------------- salting leaves a placed group whole
def test_skew_salting_skips_a_fragment_with_a_placed_aggregation():
    """The re-planner's skew rule spreads a hot probe partition's rows over
    every task: an aggregation finished in the join's fragment would then
    answer the hot key once a task, so the rule leaves its fragment be."""
    from trino_tpu.adaptive.replanner import AdaptivePlanner

    frags = _fragments("tiny", _q13("special", "requests"), **PARTITIONED)
    (hash_frag,) = [f for f in frags if f.partitioning == "hash"]
    planner = AdaptivePlanner(_session("tiny"), stats=None, n_workers=2,
                              id_alloc=iter(()))
    # the stats are never asked: the rule stops at the placed aggregation
    assert planner._maybe_mitigate_skew(
        hash_frag, {f.id: f for f in frags}) is None
