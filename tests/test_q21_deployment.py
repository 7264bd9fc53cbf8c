"""The deployment ``tpch_sf10_q21`` (TPC-H Q21 through the server), at tiny
on the CPU and plan-only at SF 1 and SF 10: its EXISTS and NOT EXISTS run
as residual semi and anti joins (``Executor.semi_join_filtered``) against
lineitem builds whose keys are already in order, and neither build is
sorted: a dead tail is masked, dead rows among the live ones stay where
they are and the residual gates each pair by its build row's liveness
(``ops/join.py in_place_build``), and a probe far smaller than its build
finds its key ranges by binary search (``searched_counts``). The counters
``residualPairs``, ``buildSorts`` and ``expansionSorts`` and the
``join/residual`` span say what the joins did."""
import numpy as np
import pytest

import jax.numpy as jnp

from tests import tpch_oracle as oracle
from tests.tpch_sql import QUERIES
from trino_tpu import Session
from trino_tpu.exec.query import plan_sql
from trino_tpu.sql.planner import plan as P

Q21 = QUERIES[21].replace("'SAUDI ARABIA'", "'{nation}'")
NATIONS = ["SAUDI ARABIA", "GERMANY"]


def _session(schema, **props):
    return Session({"catalog": "tpch", "schema": schema, **props})


def _nodes(root, kind):
    return [n for n in P.walk_plan(root) if isinstance(n, kind)]


def _tables(root):
    return sorted(n.table for n in _nodes(root, P.TableScanNode))


def _residual_joins(root):
    return [j for j in _nodes(root, P.JoinNode)
            if j.join_type in ("semi", "anti")]


# ---------------------------------------------- the plan, at three scales
@pytest.mark.parametrize("schema", ["tiny", "sf1", "sf10"])
def test_the_subqueries_join_above_the_dimensions_colocated(schema):
    """One source fragment scans lineitem three times: the anti join sits
    on the semi join, the semi join on the supplier and nation joins, each
    colocated with its lineitem build and each with its residual."""
    from trino_tpu.sql.planner.fragmenter import (
        RemoteSourceNode, format_fragments, fragment_plan)
    from trino_tpu.sql.planner.optimizer import stamp_join_estimates

    s = _session(schema)
    root = plan_sql(s, Q21.format(nation="SAUDI ARABIA"))
    stamp_join_estimates(root, s)
    frags = fragment_plan(root, s)
    (frag,) = [f for f in frags if "lineitem" in _tables(f.root)]
    text = format_fragments(frags)
    assert _tables(frag.root).count("lineitem") == 3, text
    anti, semi = _residual_joins(frag.root)
    assert (anti.join_type, semi.join_type) == ("anti", "semi")
    for j in (anti, semi):
        assert j.distribution == "colocated" and j.filter is not None
        assert _tables(j.right) == ["lineitem"]
    assert text.count("filter=ne(#4:l_suppkey, #1:l_suppkey)") == 2
    # the semi join is under the anti join's probe, the dimensions under
    # the semi join's: both broadcast into the fragment
    assert semi in _nodes(anti.left, P.JoinNode)
    below = [j for j in _nodes(semi.left, P.JoinNode) if j is not semi]
    remote = [n.fragment_id for j in below for n in _nodes(j.right,
                                                          RemoteSourceNode)]
    by_id = {f.id: f for f in frags}
    assert sorted(t for fid in remote for t in _tables(by_id[fid].root)) == [
        "nation", "supplier"]
    assert [j.distribution for j in below].count("broadcast") == 2
    # the anti join's build is the filtered lineitem, the semi join's whole
    assert _nodes(anti.right, P.FilterNode)
    assert not _nodes(semi.right, P.FilterNode)


# ----------------------------------------- the kernels: no sort, same pairs
def _pairs(build, lo, counts):
    """(probe row, build row) of every LIVE pair the ranges hold."""
    rows, live = np.asarray(build.rows), np.asarray(build.live)
    lo, counts = np.asarray(lo), np.asarray(counts)
    return {(i, int(rows[j])) for i in range(len(lo))
            for j in range(lo[i], lo[i] + counts[i]) if live[j]}


@pytest.mark.parametrize("seed", range(6))
def test_an_in_place_build_holds_the_pairs_a_sorted_one_does(seed):
    """Keys in order with dead rows among the live ones, a zeroed dead pad
    after them and, on odd seeds, any value in a dead row: the build taken
    as it lies and searched holds exactly the live pairs of the parent's
    sorted build and merge ranks."""
    from trino_tpu.ops import join as join_ops

    rng = np.random.default_rng(seed)
    n, pad, m = 300, 40, 64
    keys = np.sort(rng.integers(0, 90, n)).astype(np.int32)
    live = rng.random(n) < 0.55
    vals = np.concatenate([keys, np.zeros(pad, np.int32)])
    sel = np.concatenate([live, np.zeros(pad, bool)])
    if seed % 2:
        vals = np.where(sel, vals, rng.integers(-50, 200, n + pad)).astype(
            np.int32)
    probe = [(jnp.asarray(rng.integers(-3, 95, m).astype(np.int32)), None)]
    probe_sel = jnp.asarray(rng.random(m) < 0.8)
    key = (jnp.asarray(vals), None)
    sorted_build = join_ops.build_side([key], jnp.asarray(sel))
    want = _pairs(sorted_build, *join_ops.probe_counts(
        sorted_build, probe, probe_sel))
    in_place = join_ops.in_place_build(key, jnp.asarray(sel))
    assert in_place.in_place
    assert np.all(np.diff(np.asarray(in_place.cols[0]).astype(np.int64)) >= 0)
    searched = join_ops.searched_counts(in_place, probe, probe_sel)
    merged = join_ops.probe_counts(in_place, probe, probe_sel)
    assert all(np.array_equal(a, b) for a, b in zip(searched, merged))
    assert _pairs(in_place, *searched) == want and len(want) > 20


def test_a_row_gather_over_its_limit_goes_by_in_blocks(monkeypatch):
    """Q21's supplier join gathers three payload columns at 2^25 slots,
    which the chip's compiler refused as one [2^25, 3] row gather
    (``ranks.ROW_GATHER_MAX``). In blocks, a tail block padded, the
    columns are the ones the single gather gives."""
    from trino_tpu.ops import ranks

    monkeypatch.setattr(ranks, "ROW_GATHER_MAX", 64)
    monkeypatch.setattr(ranks, "ROW_GATHER_BLOCK", 16)
    rng = np.random.default_rng(21)
    cols = [rng.integers(-1000, 1000, 37).astype(np.int32) for _ in range(3)]
    idx = rng.integers(0, 37, 101).astype(np.int32)
    got = ranks.batched_gather([jnp.asarray(c) for c in cols] +
                               [jnp.asarray(cols[0] > 0)], jnp.asarray(idx))
    for c, g in zip(cols + [cols[0] > 0], got):
        assert np.array_equal(np.asarray(g), c[idx])


def _run_q21(schema, nation, monkeypatch=None, force_sort=False):
    from trino_tpu.exec.executor import Executor

    if force_sort:
        # the parent's path: no key is in order, every range from merge ranks
        monkeypatch.setattr(Executor, "_key_in_order",
                            staticmethod(lambda page, keys: False))
        monkeypatch.setattr(Executor, "SEARCHED_PROBE_SHARE", 0)
    s = _session(schema)
    root = plan_sql(s, Q21.format(nation=nation))
    ex = Executor(s)
    rows = [list(r) for r in ex.execute_checked(root).to_pylist()]
    joins = {j.id: j.join_type for j in _residual_joins(root)}
    counts = {joins[nid]: (r["residualPairs"], r["buildSorts"],
                           r["expansionSorts"])
              for (nid, kind), r in ex.kernel_stats.items() if nid in joins}
    other = sum(r["buildSorts"] + r["residualPairs"] + r["expansionSorts"]
                for (nid, _kind), r in ex.kernel_stats.items()
                if nid not in joins)
    return rows, counts, other


def test_the_residual_joins_answer_as_the_sorted_builds_did(monkeypatch):
    """The parent's path, kept here as the reference and not as a flag:
    both lineitem builds sorted and every range from merge ranks. Same
    rows; it counts two build sorts and two expansion sorts (the merge
    ranks) where this change counts none."""
    from benchmark.reference import tpch_q21

    nation = "GERMANY"
    rows, counts, other = _run_q21("tiny", nation)
    assert rows == tpch_q21.q21("tiny", [{"nation": nation}])[0]
    assert set(counts) == {"semi", "anti"} and other == 0
    assert all(sorts == ranked == 0 and pairs >= 16
               for pairs, sorts, ranked in counts.values())
    was, was_counts, _ = _run_q21("tiny", nation, monkeypatch, force_sort=True)
    assert was == rows
    assert {k: v[1:] for k, v in was_counts.items()} == {
        "semi": (1, 1), "anti": (1, 1)}
    # the in-place anti build keeps its dead rows in the ranges: never
    # fewer pairs than the sorted build's
    assert counts["anti"][0] >= was_counts["anti"][0]
    assert counts["semi"][0] == was_counts["semi"][0]


def test_the_residual_join_span_splits_op_join_alone():
    """``join/residual`` opens inside its Join's operator span and carries
    what it evaluated; in the ledger's second level it takes its instants
    from ``op:Join`` alone, and a read inside it is still ``host-sync``."""
    from trino_tpu.exec.executor import Executor
    from trino_tpu.obs import timeline
    from trino_tpu.obs import trace as tracing

    s = _session("tiny")
    root = plan_sql(s, Q21.format(nation="SAUDI ARABIA"))
    tracer = tracing.Tracer()
    with tracing.activate(tracer):
        Executor(s).execute_checked(root)
    spans = tracer.spans()
    residual = [sp for sp in spans if sp.name == "join/residual"]
    assert sorted(sp.attributes["join_type"] for sp in residual) == [
        "anti", "semi"]
    by_id = {sp.span_id: sp for sp in spans}
    for sp in residual:
        assert by_id[sp.parent_id].name == "operator/Join"
        assert set(sp.attributes) == {"join_type", "probe_rows",
                                      "build_rows", "pairs"}
        assert sp.attributes["pairs"] >= 16
        assert sp.attributes["build_rows"] >= 59837
    # op:Join 0..10 s, join/residual 2..8, a read 3..4
    t0 = 1000.0
    dicts = [{"name": "query", "start": t0, "durationS": 10.0},
             {"name": "device/execute", "start": t0, "durationS": 10.0},
             {"name": "operator/Join", "start": t0, "durationS": 10.0},
             {"name": "join/residual", "start": t0 + 2, "durationS": 6.0},
             {"name": "host/sync", "start": t0 + 3, "durationS": 1.0}]
    detail = timeline.compute_timeline(dicts, t0, t0 + 10).detail
    assert detail == {"device-execute/op:Join": 4.0,
                      "device-execute/residual-join": 5.0,
                      "device-execute/host-sync": 1.0}


# ------------------------------------------ through POST /v1/statement
PROPS = {"catalog": "tpch", "schema": "tiny", "result_cache_enabled": "false",
         "device_cache_enabled": "true", "query_max_execution_time": "15m"}


@pytest.fixture(scope="module")
def served():
    from trino_tpu.devcache.cache import DEVICE_CACHE
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    DEVICE_CACHE.invalidate_all()
    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="q21w0")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        yield coord
    finally:
        worker.stop()
        coord.stop()
        DEVICE_CACHE.invalidate_all()


def _client(coord):
    from trino_tpu.client.remote import StatementClient

    return StatementClient(coord.base_url, PROPS)


def _profile(coord, query_id):
    from trino_tpu.server import wire

    return wire.json_request(
        "GET", f"{coord.base_url}/v1/query/{query_id}/profile")["kernels"]


@pytest.mark.parametrize("nation", NATIONS)
def test_served_q21_equals_both_oracles(served, nation):
    from benchmark.reference import tpch_q21

    client = _client(served)
    _cols, rows = client.execute(Q21.format(nation=nation))
    assert client.stats["state"] == "FINISHED"
    assert client.stats["fastPath"] == "distributed"
    want = tpch_q21.q21("tiny", [{"nation": nation}])[0]
    assert rows == want and len(want) >= 2
    assert [[n, c] for n, c in oracle.q21("tiny", nation_name=nation)] == want


def test_the_counters_reach_the_profile_and_the_kernels_table(served):
    client = _client(served)
    client.execute(Q21.format(nation="SAUDI ARABIA"))
    kernels = _profile(served, client.query_id)
    joins = [k for k in kernels if k["operator"] == "Join"]
    residual = [k for k in joins if k["residualPairs"]]
    assert len(residual) == 2
    assert all(k["residualPairs"] & (k["residualPairs"] - 1) == 0
               for k in residual)           # a power of two: a capacity
    assert sum(k["buildSorts"] for k in residual) == 0
    pairs = sum(k["residualPairs"] for k in kernels)
    sorts = sum(k["buildSorts"] for k in kernels)
    _cols, table = client.execute(
        "select sum(residual_pairs), sum(build_sorts) "
        f"from system.runtime.kernels where query_id = '{client.query_id}'")
    assert [int(v) for v in table[0]] == [pairs, sorts]


# an M:N left join with a residual (``Executor.expand_join``'s tail): its
# pairs' ranges from merge ranks, one combined rank sort, and the probe rows
# with no passing pair back from the expansion's own ranges
LEFT_RESIDUAL = ("select o_orderkey, l_linenumber, l_suppkey from orders "
                 "left join lineitem on o_orderkey = l_orderkey "
                 "and l_linenumber * 300 > o_custkey where o_orderkey < 300")


def test_a_left_join_with_a_residual_equals_sqlite(served):
    import sqlite3

    db = sqlite3.connect(":memory:")
    for table, cols in (("orders", ["o_orderkey", "o_custkey"]),
                        ("lineitem", ["l_orderkey", "l_linenumber",
                                      "l_suppkey"])):
        db.execute(f"create table {table} ({', '.join(cols)})")
        db.executemany(
            f"insert into {table} values ({', '.join('?' * len(cols))})",
            [tuple(r[c] for c in cols)
             for r in oracle.load_table("tiny", table, cols)])
    want = sorted(db.execute(LEFT_RESIDUAL).fetchall(),
                  key=lambda r: tuple((v is None, v) for v in r))
    client = _client(served)
    _cols, rows = client.execute(LEFT_RESIDUAL)
    got = sorted(map(tuple, rows),
                 key=lambda r: tuple((v is None, v) for v in r))
    assert got == want
    # both sides of the residual occur: kept pairs and null-extended rows
    assert any(r[1] is None for r in got) and any(r[1] for r in got)


@pytest.mark.parametrize("sql, sorts", [
    # served at tiny, the semi join's probe is its split's 61,440 slots, as
    # many as its build's, and its ranges come from merge ranks; the anti
    # join's probe of 1,024 slots is searched. Neither expansion nor
    # any-reduce sorts (run in one process, both probes are searched:
    # test_the_residual_joins_answer_as_the_sorted_builds_did)
    (Q21.format(nation="SAUDI ARABIA"), 1),
    (LEFT_RESIDUAL, 1),                       # the left join's merge ranks
], ids=["q21", "left-residual"])
def test_expansion_sorts_reach_the_profile_and_the_kernels_table(
        served, sql, sorts):
    client = _client(served)
    client.execute(sql)
    kernels = _profile(served, client.query_id)
    joins = [k for k in kernels if k["operator"] == "Join"]
    assert joins and sum(k["expansionSorts"] for k in joins) == sorts
    assert sum(k["expansionSorts"] for k in kernels) == sorts
    _cols, table = client.execute(
        "select sum(expansion_sorts) from system.runtime.kernels "
        f"where query_id = '{client.query_id}'")
    assert int(table[0][0]) == sorts


# ------------------------------ the reference can tell a missing residual
@pytest.mark.parametrize("drop", ["exists", "not exists"])
@pytest.mark.parametrize("schema", ["tiny", "sf0_1"])
def test_dropping_either_residual_changes_the_answer(schema, drop):
    """An engine that skipped one subquery's ``l_suppkey <> l1.l_suppkey``
    would answer otherwise for every nation of the cell: the comparison
    that decides ``correct`` catches it. A float32 control cannot fail:
    the counts are exact in it."""
    from benchmark.reference import tpch_q21

    bindings = [{"nation": n} for n in ("RUSSIA", "IRAN") + tuple(NATIONS)]
    exact = tpch_q21.q21(schema, bindings)
    assert all(exact)
    assert all(a != b for a, b in zip(
        exact, tpch_q21.q21(schema, bindings, drop=drop)))
    assert tpch_q21.q21(schema, bindings, dtype=np.float32) == exact
