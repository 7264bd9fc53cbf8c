"""The deployment ``tpch_sf10_q9`` (TPC-H Q9 through the server), at tiny on
the CPU and plan-only at SF 1 and SF 10: a join order read from what the
filters leave (``Planner._reorder_implicit_joins`` over filtered sizes,
``stats.dictionary_selectivity``), a large build handed the filter of a small
one (``optimizer.reduce_large_builds``), lineitem joined where it is scanned
(``fragmenter``: a join under a colocated join keeps its probe in place), a
dynamic filter that narrows nothing not made (``Executor._range_narrows``),
the cache saying when it does not keep a scan, and the join-order counters."""
import json
import os

import pytest

from tests import tpch_oracle as oracle
from tests.test_q18_deployment import without_estimates
from tests.tpch_sql import QUERIES
from trino_tpu import Session
from trino_tpu.exec.query import plan_sql, run_query
from trino_tpu.sql.planner import plan as P
from trino_tpu.sql.planner import stats
from trino_tpu.sql.planner.fragmenter import (
    RemoteSourceNode, format_fragments, fragment_plan)
from trino_tpu.sql.planner.optimizer import stamp_join_estimates

HERE = os.path.dirname(os.path.abspath(__file__))
Q9 = QUERIES[9].replace("%green%", "%{color}%")
SCHEMAS = ["tiny", "sf1", "sf10"]


def _nodes(root, kind):
    return [n for n in P.walk_plan(root) if isinstance(n, kind)]


def _tables(root):
    return sorted(n.table for n in _nodes(root, P.TableScanNode))


def _session(schema, **props):
    return Session({"catalog": "tpch", "schema": schema, **props})


def _fragments(schema, color="green"):
    """As EXPLAIN (TYPE DISTRIBUTED) builds them: estimates stamped on the
    joins before the cut."""
    s = _session(schema)
    root = plan_sql(s, Q9.format(color=color))
    stamp_join_estimates(root, s)
    return s, fragment_plan(root, s)


def _probe_chain(frag):
    """The joins on lineitem's probe spine, bottom up, as (join, the tables
    its build scans in the WHOLE plan)."""
    out, node = [], frag.root
    while node.sources:
        if isinstance(node, P.JoinNode):
            out.append(node)
        node = node.sources[0]
    assert isinstance(node, P.TableScanNode) and node.table == "lineitem"
    return out[::-1]


def _build_tables(join, frags):
    by_id = {f.id: f for f in frags}

    def tables(n):
        if isinstance(n, RemoteSourceNode):
            return tables(by_id[n.fragment_id].root)
        if isinstance(n, P.TableScanNode):
            return [n.table]
        return [t for s in n.sources for t in tables(s)]

    return sorted(tables(join.right))


# ---------------------------------------------- (b) the plan, at three scales
@pytest.mark.parametrize("schema", SCHEMAS)
def test_the_order_is_read_from_the_filters(schema):
    """Filtered part is the first build joined to lineitem and is broadcast;
    supplier, nation and partsupp follow; orders comes last, colocated."""
    s, frags = _fragments(schema)
    (frag,) = [f for f in frags if "lineitem" in _tables(f.root)]
    chain = _probe_chain(frag)
    builds = [_build_tables(j, frags) for j in chain]
    whole = s.catalogs["tpch"].table_row_count(schema, "partsupp") \
        <= stats.BROADCAST_BUILD_MAX
    assert builds == [["part"], ["supplier"], ["nation"],
                      ["partsupp"] if whole else ["part", "partsupp"],
                      ["orders"]], format_fragments(frags)
    assert [j.distribution for j in chain] == [
        "broadcast"] * 4 + ["colocated"]
    assert all(j.right_unique and j.join_type == "inner" for j in chain)
    first = chain[0]
    assert isinstance(first.left, P.TableScanNode)
    # the estimates EXPLAIN prints: 185 of 8,649 names hold the colour
    n_part = s.catalogs["tpch"].table_row_count(schema, "part")
    assert first.est_build_rows == n_part * 185 // 8649
    assert first.est_probe_rows == stats.estimate_rows(s, first.left)
    text = format_fragments(frags)
    assert f"est=[probe {first.est_probe_rows}, build {first.est_build_rows}]" in text


@pytest.mark.parametrize("schema", SCHEMAS)
def test_lineitem_and_orders_never_cross_an_exchange(schema):
    """One fragment scans both, joins them colocated on the COMPACTED rows,
    aggregates partially; the final step is above; every other fragment
    scans a dimension."""
    s, frags = _fragments(schema)
    (frag,) = [f for f in frags if "lineitem" in _tables(f.root)]
    assert frag.partitioning == "source"
    assert _tables(frag.root) == ["lineitem", "orders"]
    assert isinstance(frag.root, P.AggregationNode) and frag.root.step == "partial"
    finals = [a for f in frags if f is not frag
              for a in _nodes(f.root, P.AggregationNode)]
    assert [a.step for a in finals] == ["final"]
    for f in frags:
        if f is not frag:
            assert not {"lineitem", "orders"} & set(_tables(f.root))
    colocated = _probe_chain(frag)[-1]
    assert isinstance(colocated.right, P.TableScanNode)
    compacts = _nodes(colocated.left, P.CompactNode)
    big = s.catalogs["tpch"].table_row_count(schema, "lineitem") >= (1 << 17)
    # a Compact sits directly on the first join wherever lineitem is large
    # enough to be worth squeezing (optimizer.COMPACT_MIN_SLOTS), and every
    # later join, the colocated one too, probes what it kept
    assert len(compacts) == (1 if big else 0)
    if big:
        assert compacts[0].source is _probe_chain(frag)[0]
        assert P.compacts_its_match(compacts[0].source)
        probe = _probe_chain(frag)[0].est_probe_rows
        assert abs(compacts[0].estimated_rows - probe * 185 / 8649) < probe / 1000


@pytest.mark.parametrize("schema", ["sf1", "sf10"])
def test_a_large_build_is_handed_the_small_builds_filter(schema):
    """partsupp is too large to broadcast whole; its probe side has already
    been joined with the green parts on the column ps_partkey joins, so it
    is semi-joined with their keys where it is scanned, and the join above
    keeps its match share."""
    s, frags = _fragments(schema)
    (reduced,) = [f for f in frags if "partsupp" in _tables(f.root)]
    (semi,) = _nodes(reduced.root, P.JoinNode)
    assert (semi.join_type, semi.implied, semi.distribution) == (
        "semi", True, "broadcast")
    assert _tables(semi.left) == ["partsupp"]
    assert _build_tables(semi, frags) == ["part"]
    rows = s.catalogs["tpch"].table_row_count(schema, "partsupp")
    assert semi.est_probe_rows == rows
    (frag,) = [f for f in frags if "lineitem" in _tables(f.root)]
    above = _probe_chain(frag)[3]
    assert abs(above.est_build_rows - rows * 185 / 8649) < rows * 0.001
    # and the probe rows are all expected to match: no second Compact
    assert len(_nodes(frag.root, P.CompactNode)) == 1
    assert "implied" in format_fragments(frags)


def test_at_tiny_partsupp_is_broadcast_whole():
    _s, frags = _fragments("tiny")
    assert not [j for f in frags for j in _nodes(f.root, P.JoinNode)
                if j.join_type == "semi"]
    assert sum("part" in _tables(f.root) for f in frags) == 1


NO_DONOR = """select sum(ps_supplycost), count(*) from lineitem
  join partsupp on ps_suppkey = l_suppkey and ps_partkey = l_partkey
  join orders on o_orderkey = l_orderkey {where}"""


@pytest.mark.parametrize("where,kept", [
    ("", True), ("where o_orderdate < date '1993-01-01'", False)])
def test_a_large_build_with_no_donor_is_weighed_against_the_colocation(
        where, kept):
    """An unfiltered 8 M-row partsupp under the orders join has no small
    build to take a filter from. It is broadcast into lineitem's fragment
    only while it is smaller than what repartitioning lineitem would send
    across an exchange in its place, the colocated join's other table:
    against 15 M orders rows it stays, against the 2.4 M a date leaves it
    repartitions as it did on the parent and the orders join is no longer
    colocated."""
    s = _session("sf10")
    root = plan_sql(s, NO_DONOR.format(where=where))
    stamp_join_estimates(root, s)
    frags = fragment_plan(root, s)
    joins = {tuple(_build_tables(j, frags)): j
             for f in frags for j in _nodes(f.root, P.JoinNode)}
    assert not [j for j in joins.values() if j.join_type == "semi"]
    build, other = joins[("partsupp",)], joins[("orders",)]
    assert build.est_build_rows == 8_000_000
    assert (build.est_build_rows < other.est_build_rows) == kept
    assert build.distribution == ("broadcast" if kept else "partitioned")
    assert other.distribution == ("colocated" if kept else "broadcast")
    carried = [f for f in frags if "lineitem" in _tables(f.root)
               and f.output_partition_channels]
    assert bool(carried) == (not kept)


def test_estimates_are_stamped_for_explain_alone():
    """A statement that is only run does not pay for the estimates."""
    s = _session("sf10")
    root = plan_sql(s, Q9.format(color="green"))
    assert all(j.est_probe_rows is None for j in _nodes(root, P.JoinNode))
    rows = run_query(s, "EXPLAIN " + Q9.format(color="green")).rows
    assert sum("est=[probe " in r[0] for r in rows) >= 6


@pytest.mark.parametrize("color", ["hot", "almond"])
def test_another_colour_plans_the_same_shape(color):
    green = format_fragments(_fragments("sf10")[1])
    other = format_fragments(_fragments("sf10", color)[1])
    strip = lambda t: without_estimates(t).replace(color, "green")  # noqa: E731
    assert strip(other) == strip(green)


# ---------------------------- (c) q3's and Q18's plans at SF 10, as before
with open(os.path.join(HERE, "q9_parent_plans.json"), encoding="utf-8") as _f:
    PARENT_PLANS = json.load(_f)


@pytest.mark.parametrize("case", sorted(PARENT_PLANS))
def test_q3_and_q18_plan_at_sf10_as_the_parent_planned_them(case):
    """``q9_parent_plans.json``: EXPLAIN (TYPE DISTRIBUTED) of the
    benchmark's q3 and q18 statements at SF 10, taken from fc569f2 before
    this change: fragments, join kinds and Compact places, byte for byte
    once the printed estimates are taken out."""
    from benchmark import spec

    schema, name, binding = case.split("/", 2)
    sql = spec.load_template(name).sql.format(**json.loads(binding))
    rows = run_query(_session(schema), "EXPLAIN (TYPE DISTRIBUTED) " + sql).rows
    assert without_estimates("\n".join(r[0] for r in rows)) == PARENT_PLANS[case]


def test_the_one_pinned_plan_that_changed_is_q3_at_sf1():
    """``parent_plans.json`` stays the parent's. Two of its cases plan
    otherwise since the broadcast rule reads the build's FILTERED rows, and
    are argued in PERF.md section 6: q3 at SF 1, where customer's segment
    leaves an estimated 30,000 rows, under the 131,072-row limit, so it is
    broadcast into the fragment that joins lineitem and orders (the shape
    tiny always had) where the parent cut a hash fragment for 150,000."""
    from tests.test_q18_deployment import ARGUED_PLANS
    from tests.test_q18_deployment import PARENT_PLANS as PINNED

    assert sorted(c.split("/", 2)[:2] for c in ARGUED_PLANS) == [
        ["sf1", "q3"], ["sf1", "q3"]]
    for case, text in ARGUED_PLANS.items():
        was = PINNED[case]
        assert "Join [inner/partitioned]" in was and "[broadcast]" not in was
        assert "Join [inner/partitioned]" not in text
        assert "Join [inner/broadcast] L[3] = R[0]" in text
        assert "Aggregation [partial]" in text and "[partial]" not in was
        # the colocated lineitem-orders join and its Compact are where they were
        colocated = [ln.strip() for ln in was.splitlines() if "colocated" in ln]
        assert colocated == [ln.strip() for ln in text.splitlines()
                             if "colocated" in ln]
    s = _session("sf1")
    customer = plan_sql(
        s, "select c_custkey from customer where c_mktsegment = 'BUILDING'")
    assert stats.estimate_live_rows(s, customer) == 30000
    assert stats.resolved_broadcast_limit(s.properties) == 131072


# ------------------------------------------- (d) selectivity off a vocabulary
SELECTIVITIES = {
    "p_name like '%green%'": (185, 8649),
    "p_name like 'green%'": (93, 8649),
    "starts_with(p_name, 'green')": (93, 8649),
    "p_name = 'green red'": (1, 8649),
    "p_name in ('green red', 'red green', 'no such name')": (2, 8649),
    "p_mfgr = 'Manufacturer#3'": (1, 5),
    "p_container like '%BOX'": (5, 40),
}


@pytest.mark.parametrize("predicate", sorted(SELECTIVITIES))
@pytest.mark.parametrize("schema", ["tiny", "sf10"])
def test_a_dictionary_predicate_is_evaluated_on_the_vocabulary(schema, predicate):
    s = _session(schema)
    (f,) = _nodes(plan_sql(s, f"select p_partkey from part where {predicate}"),
                  P.FilterNode)
    matching, total = SELECTIVITIES[predicate]
    assert stats.dictionary_selectivity(s, f.predicate, f.source) == (
        matching, total)
    assert stats.predicate_selectivity(s, f.predicate, f.source) == \
        matching / total
    rows = s.catalogs["tpch"].table_row_count(schema, "part")
    assert stats.estimate_live_rows(s, f) == max(1, int(rows * matching / total))


@pytest.mark.parametrize("predicate", [
    "length(p_name) > 10", "upper(p_name) like '%GREEN%'",
    "p_comment like '%green%'", "p_name like p_mfgr"])
def test_what_is_unknown_still_keeps_nine_tenths(predicate):
    s = _session("sf10")
    (f,) = _nodes(plan_sql(s, f"select p_partkey from part where {predicate}"),
                  P.FilterNode)
    assert stats.dictionary_selectivity(s, f.predicate, f.source) is None
    assert stats.predicate_selectivity(s, f.predicate, f.source) == \
        stats.UNKNOWN_FILTER_COEFFICIENT


def test_a_two_column_edge_is_bounded_by_the_builds_rows():
    """lineitem x partsupp on (suppkey, partkey): as many rows as lineitem,
    not lineitem x 8 M / (2 M x 100 K)."""
    s = _session("sf10")
    root = plan_sql(s, "select count(*) from lineitem, partsupp "
                       "where ps_partkey = l_partkey and ps_suppkey = l_suppkey")
    (join,) = _nodes(root, P.JoinNode)
    assert len(join.left_keys) == 2 and join.right_unique
    assert stats.estimate_live_rows(s, join) == stats.estimate_live_rows(
        s, join.left)
    assert not _nodes(root, P.CompactNode)


# ---------------------------------------- (a), (e) through POST /v1/statement
PROPS = {"catalog": "tpch", "schema": "tiny", "result_cache_enabled": "false",
         "device_cache_enabled": "true"}


@pytest.fixture(scope="module")
def served():
    from trino_tpu.devcache.cache import DEVICE_CACHE
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    DEVICE_CACHE.invalidate_all()
    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="q9w0")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        yield coord
    finally:
        worker.stop()
        coord.stop()
        DEVICE_CACHE.invalidate_all()


def _client(coord, **props):
    from trino_tpu.client.remote import StatementClient

    return StatementClient(coord.base_url, {**PROPS, **props})


def _profile(coord, query_id):
    from trino_tpu.server import wire

    return wire.json_request(
        "GET", f"{coord.base_url}/v1/query/{query_id}/profile")["kernels"]


@pytest.mark.parametrize("color", ["green", "hot", "almond"])
def test_served_q9_equals_both_oracles(served, color):
    """``hot`` is a substring of ``hotpink``: LIKE '%hot%' keeps both."""
    from benchmark.reference import tpch_q9

    client = _client(served, device_cache_max_bytes="2147483648",
                     query_max_execution_time="15m")
    _cols, rows = client.execute(Q9.format(color=color))
    assert client.stats["state"] == "FINISHED"
    assert client.stats["fastPath"] == "distributed"
    want = tpch_q9.q9("tiny", [{"color": color}])[0]
    assert rows == want and len(want) > 100
    assert [[n, y, str(v)] for n, y, v in oracle.q9("tiny", color)] == want
    kernels = _profile(served, client.query_id)
    scans = [k for k in kernels if k["operator"] == "TableScan"]
    assert len(scans) == 6
    # what crossed an exchange: the dimensions' live rows and the groups,
    # no lineitem or orders row
    crossed = sum(k["exchangedRows"] for k in kernels)
    assert crossed < 8000 + 2000 + 100 + 25 + 3 * len(want), crossed


def test_one_resident_lineitem_for_every_colour(served):
    from trino_tpu.devcache.cache import DEVICE_CACHE

    client = _client(served)
    client.execute(Q9.format(color="green"))
    first = _profile(served, client.query_id)
    client.execute(Q9.format(color="plum"))
    second = _profile(served, client.query_id)
    scans = [k for k in second if k["operator"] == "TableScan"]
    assert [k["cacheHits"] for k in scans] == [1] * 6
    assert sum(k["stagedBytes"] for k in second) == 0
    assert sum(k["cacheBypasses"] + k["cacheMisses"] for k in second) == 0
    assert sum(k["cacheHits"] for k in first) <= 6
    by_table = {}
    for e in DEVICE_CACHE.snapshot():
        by_table.setdefault(e["table"].split(".")[-1], []).append(e)
    assert len(by_table["lineitem"]) == 1 and len(by_table["orders"]) == 1
    assert set(by_table) == {"lineitem", "orders", "partsupp", "part",
                             "supplier", "nation"}


def test_a_scan_over_the_cap_says_it_bypassed(served):
    from trino_tpu.obs import metrics as M

    def bypassed():
        return M.DEVICE_CACHE_BYPASS.value("over-cap")

    before = bypassed()
    client = _client(served, device_cache_max_bytes="1000")
    _cols, rows = client.execute(
        "select count(*) from lineitem where l_linenumber > 6")
    kernels = _profile(served, client.query_id)
    (scan,) = [k for k in kernels if k["operator"] == "TableScan"]
    assert (scan["cacheBypasses"], scan["cacheHits"], scan["cacheMisses"]) == (
        1, 0, 0)
    assert scan["stagedBytes"] > 1000
    assert bypassed() == before + 1
    _cols, table = client.execute(
        "select sum(cache_bypasses), sum(join_probe_slots), "
        "sum(join_build_slots) from system.runtime.kernels "
        f"where query_id = '{client.query_id}'")
    assert [int(v) for v in table[0]] == [1, 0, 0]
    # with the cache off there is nothing to bypass
    off = _client(served, device_cache_enabled="false")
    off.execute("select count(*) from lineitem where l_linenumber > 6")
    assert sum(k["cacheBypasses"] for k in _profile(served, off.query_id)) == 0


# ------------------------------- (f) what a join's place in the order costs
def test_join_slots_are_the_pages_capacities():
    from trino_tpu.exec.executor import Executor

    s = _session("tiny")
    root = plan_sql(s, "select count(*) from lineitem, supplier, nation "
                       "where l_suppkey = s_suppkey and s_nationkey = n_nationkey")
    ex = Executor(s)
    ex.execute_checked(root)
    joins = _nodes(root, P.JoinNode)
    assert len(joins) == 2
    for j in joins:
        row = ex.kernel_stats[(j.id, "Join")]
        assert row["joinProbeSlots"] == Executor(s).execute(j.left).num_rows
        assert row["joinBuildSlots"] == Executor(s).execute(j.right).num_rows
        assert row["joinProbeSlots"] >= 100 and row["launches"] == 1
    others = [r for (_id, kind), r in ex.kernel_stats.items() if kind != "Join"]
    assert others and not any(r["joinProbeSlots"] or r["joinBuildSlots"]
                              for r in others)


# ------------------------------------ (g) a domain that would narrow nothing
DOMAINS = {
    # build predicate -> what the probe scan of lineitem is handed
    "p_name like '%e%'": None,                        # most parts, whole range
    "p_partkey between 100 and 1500": "range",        # 1,401 keys, 70% of it
    "p_partkey < 400": "set",                         # 399 keys: the set
}


@pytest.mark.parametrize("predicate", sorted(DOMAINS))
def test_a_range_that_narrows_nothing_is_no_domain(predicate):
    from trino_tpu.exec.executor import Executor

    s = _session("tiny")
    root = plan_sql(s, "select count(*) from lineitem, part "
                       f"where l_partkey = p_partkey and {predicate}")
    (join,) = _nodes(root, P.JoinNode)
    assert join.dyn_filter_keys == [0]
    ex = Executor(s)
    page = ex.execute_checked(root)
    assert page.to_pylist() == s.execute(
        "select count(*) from lineitem where l_partkey in "
        f"(select p_partkey from part where {predicate})").rows
    dom = ex.dyn_domains.get((join.id, 0))
    sites = ex.kernel_stats[(join.id, "Join")]["hostSyncSites"]
    reads, _seconds, nbytes = sites["dynamic-filter-domain"]
    if DOMAINS[predicate] is None:
        assert dom is None
    elif DOMAINS[predicate] == "range":
        assert dom.values is None and (dom.low, dom.high) == (100, 1500)
    else:
        assert sorted(dom.values) == list(range(1, 400))
    if DOMAINS[predicate] == "set":
        assert reads >= 2 and nbytes > 399 * 4   # the key column, for its values
    else:
        assert reads == 1 and nbytes <= 24       # three scalars, no more
    (scan,) = [n for n in _nodes(root, P.TableScanNode) if n.table == "lineitem"]
    constraint = ex.scan_constraint(scan)
    assert (constraint is None) == (dom is None)


def test_the_optimize_span_says_what_order_and_why():
    from trino_tpu.obs import trace as tracing

    tracer = tracing.Tracer()
    with tracing.activate(tracer):
        plan_sql(_session("sf10"), Q9.format(color="green"))
    (span,) = [sp for sp in tracer.spans() if sp.name == "optimize"]
    assert span.attributes["join-order"] == (
        "lineitem=60000000 part=42779 supplier=100000 nation=25 "
        "partsupp+part=171116 orders=15000000")
    assert "185/8649" in span.attributes["dictionary-selectivity"]
    assert "p_name" in span.attributes["dictionary-selectivity"]
