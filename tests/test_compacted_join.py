"""A join under a Compact squeezes its match, then gathers its payloads
(PR 33): ``Executor.compacted_lookup_join`` returns the page
``compact_to(lookup_join(...))`` returns, slot for slot; the optimizer puts
the CompactNode directly on such a join (``Project(Compact(Join))``); the
Join's kernel row counts it (``compactedJoins``)."""
import decimal

import jax.numpy as jnp
import numpy as np
import pytest

import trino_tpu  # noqa: F401  (x64 before any array is made)
from tpch_sql import QUERIES
from trino_tpu import Session, types as T
from trino_tpu.data.page import Column, Page
from trino_tpu.exec.executor import Executor, QueryError
from trino_tpu.exec.query import plan_sql
from trino_tpu.obs import metrics as M
from trino_tpu.sql.planner import plan as P

N_PROBE, N_BUILD = 3000, 400
BIG = 10 ** 25  # beyond int64: a two-limb decimal(38, 2) payload


def _build_page(tier: str) -> Page:
    """400 unique keys 10, 13, 16, ... with a nullable bigint, a varchar
    and a two-limb decimal payload; every third row dead."""
    keys = np.arange(N_BUILD) * 3 + 10
    key = Column(T.BIGINT, jnp.asarray(keys),
                 vrange=(10, 3 * N_BUILD + 10) if tier == "dense" else None)
    nullable = Column(T.BIGINT, jnp.asarray(keys * 7),
                      nulls=jnp.asarray(np.arange(N_BUILD) % 5 == 0))
    names = Column.from_python(
        T.varchar(), [f"name-{k % 17}" for k in keys])
    wide = Column.from_python(
        T.decimal(38, 2),
        [decimal.Decimal(int(k) * BIG) / 100 for k in keys])
    assert wide.hi is not None and names.dictionary is not None
    sel = jnp.asarray(np.arange(N_BUILD) % 3 != 2)
    return Page([key, nullable, names, wide], sel)


def _probe_page(case: str, tier: str) -> Page:
    rng = np.random.default_rng(33)
    if case == "zero-matches":
        keys = np.full(N_PROBE, 11)  # between two build keys
    else:
        keys = rng.integers(0, 3 * N_BUILD + 40, N_PROBE)
    key = Column(T.BIGINT, jnp.asarray(np.sort(keys)), ascending=True,
                 vrange=(0, 3 * N_BUILD + 40) if tier == "dense" else None)
    price = Column(T.DOUBLE, jnp.asarray(rng.random(N_PROBE)),
                   nulls=jnp.asarray(rng.random(N_PROBE) < 0.1))
    tag = Column.from_python(
        T.varchar(), [f"tag-{i % 5}" for i in range(N_PROBE)])
    sel = None if case == "no-probe-mask" else jnp.asarray(
        rng.random(N_PROBE) < 0.6)
    return Page([key, price, tag], sel)


def _join(**kw) -> P.JoinNode:
    return P.JoinNode(join_type="inner", left_keys=[0], right_keys=[0],
                      right_unique=True, **kw)


def _assert_same_page(got: Page, want: Page):
    assert got.num_rows == want.num_rows
    assert got.live_prefix == want.live_prefix
    assert got.replicated == want.replicated
    np.testing.assert_array_equal(np.asarray(got.sel), np.asarray(want.sel))
    assert len(got.columns) == len(want.columns)
    for g, w in zip(got.columns, want.columns):
        assert g.type == w.type and g.vrange == w.vrange
        assert g.ascending == w.ascending
        assert g.dictionary is w.dictionary
        assert g.values.dtype == w.values.dtype
        for part in ("values", "nulls", "hi"):
            a, b = getattr(g, part), getattr(w, part)
            assert (a is None) == (b is None), part
            if a is not None:  # EVERY slot, the dead ones too
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _selections(tier_label: str) -> float:
    return M.FUSED_JOIN_SELECTIONS.value(tier_label)


@pytest.mark.parametrize("tier", ["dense", "sort-merge"])
@pytest.mark.parametrize("case,capacity", [
    ("probe-mask", 1024), ("no-probe-mask", 2048), ("zero-matches", 64),
    ("capacity-too-small", 128), ("capacity-covers", N_PROBE),
])
def test_the_fused_join_returns_compact_of_lookup_join(tier, case, capacity):
    left, right = _probe_page(case, tier), _build_page(tier)
    node = _join()
    into = P.CompactNode(node, estimated_rows=capacity)
    key = f"cmp:{into.id}"

    plain = Executor(Session(), {key: capacity})
    want = plain.compact_to(plain.lookup_join(node, left, right), capacity, key)
    label = "dense" if tier == "dense" else "fused"
    before = _selections(label)
    fused = Executor(Session(), {key: capacity})
    got = fused.compacted_lookup_join(node, left, right, into)
    assert _selections(label) == before + 1  # the same tier, counted once

    _assert_same_page(got, want)
    assert [c for c, _ in fused.errors] == [c for c, _ in plain.errors]
    live = int(np.asarray(want.sel).sum())
    join_row = fused.kernel_stats.get((node.id, "Join"))
    compact_row = fused.kernel_stats.get((into.id, "Compact"))
    if case == "capacity-covers":
        # nothing to squeeze: the plain path's page, no counter touched
        assert got.num_rows == N_PROBE and compact_row is None
        return
    assert got.num_rows == capacity and got.live_prefix
    assert compact_row["prefixCompactions"] == 1
    assert join_row is None  # charged to the executing row: none here
    if case == "capacity-too-small":
        for ex in (plain, fused):
            with pytest.raises(QueryError) as raised:
                ex.raise_errors()
            assert raised.value.code == f"CAPACITY_EXCEEDED:{key}"
        return
    fused.raise_errors()
    assert live <= capacity and (live == 0) == (case == "zero-matches")
    # against numpy: the live rows are the matched probe rows, in order
    lk = np.asarray(left.columns[0].values)
    bk = np.asarray(right.columns[0].values)[np.asarray(right.sel)]
    hit = np.isin(lk, bk)
    if left.sel is not None:
        hit &= np.asarray(left.sel)
    assert live == int(hit.sum())
    np.testing.assert_array_equal(
        np.asarray(got.columns[0].values)[:live], lk[hit])
    np.testing.assert_array_equal(  # the build key came with its probe key
        np.asarray(got.columns[3].values)[:live], lk[hit])


def test_the_eager_tier_sizes_the_squeeze_from_the_match():
    """No hint: the eager tier reads the match count once
    (``join-emit-count``), as the Compact did, and the Compact above finds
    the hint and the page already squeezed."""
    left, right = _probe_page("probe-mask", "dense"), _build_page("dense")
    node = _join()
    into = P.CompactNode(node, estimated_rows=0)
    ex = Executor(Session())
    got = ex.compacted_lookup_join(node, left, right, into)
    live = int(np.asarray(got.sel).sum())
    cap = ex.capacity_hints[f"cmp:{into.id}"]
    assert cap == got.num_rows and cap // 2 < max(live, 16) <= cap
    assert ex.compact_to(got, cap, f"cmp:{into.id}") is got
    ex.raise_errors()


def test_a_traced_tier_counts_nothing_and_lowers_without_a_sort():
    import jax

    left, right = _probe_page("probe-mask", "dense"), _build_page("dense")
    node = _join()
    into = P.CompactNode(node, estimated_rows=512)
    key = f"cmp:{into.id}"
    seen = []

    def body(lsel, lkey, rkey, rsel, rpay):
        ex = Executor(Session(), {key: 512})
        ex.eager_tier = False
        lp = Page([Column(T.BIGINT, lkey, vrange=left.columns[0].vrange)], lsel)
        rp = Page([Column(T.BIGINT, rkey, vrange=right.columns[0].vrange),
                   Column(T.BIGINT, rpay)], rsel)
        out = ex.compacted_lookup_join(node, lp, rp, into)
        seen.append(ex.kernel_stats)
        return out.columns[0].values, out.columns[2].values, out.sel

    args = (left.sel, left.columns[0].values, right.columns[0].values,
            right.sel, right.columns[1].values)
    text = jax.jit(body).lower(*args).as_text()
    assert "stablehlo.sort" not in text
    probe_key, payload, sel = jax.jit(body)(*args)
    live = np.asarray(sel)
    np.testing.assert_array_equal(
        np.asarray(payload)[live], np.asarray(probe_key)[live] * 7)
    assert all(not stats for stats in seen)  # no kernel row made or charged


# ------------------------------------------------------------- the plan
def _shape(node: P.PlanNode) -> str:
    name = type(node).__name__.replace("Node", "")
    if isinstance(node, P.JoinNode):
        name += f"[{node.join_type}]"
    kids = ", ".join(_shape(s) for s in node.sources)
    return f"{name}({kids})" if kids else name


_SF10 = "tpch.sf10"
_WHERE = ("l_shipdate > date '1995-03-15' "
          "and o_orderdate < date '1995-03-15'")
_OTHER_JOINS = {
    # a residual filter: the join knows more about a slot than the match
    "filter": f"select o_orderpriority, count(*) from {_SF10}.lineitem "
              f"join {_SF10}.orders on l_orderkey = o_orderkey "
              f"and l_extendedprice > o_totalprice where {_WHERE} "
              "group by o_orderpriority",
    "semi": f"select l_shipmode, count(*) from {_SF10}.lineitem "
            "where l_shipdate > date '1995-03-15' and l_orderkey in "
            f"(select o_orderkey from {_SF10}.orders "
            "where o_orderdate < date '1995-03-15') group by l_shipmode",
    # every probe row survives a left join: no estimate plans a Compact
    # on it, so the ratio gate is opened for this one
    "left": f"select o_orderpriority, count(*) from {_SF10}.lineitem "
            f"left join {_SF10}.orders on l_orderkey = o_orderkey "
            "where l_shipdate > date '1995-03-15' group by o_orderpriority",
}


def test_q3_at_sf10_plans_both_compacts_directly_on_their_joins():
    session = Session({"catalog": "tpch", "schema": "sf10"})
    root = plan_sql(session, QUERIES[3])
    shape = _shape(root)
    assert {n.schema for n in P.walk_plan(root)
            if isinstance(n, P.TableScanNode)} == {"sf10"}
    assert shape.count("Project(Compact(Join[inner](") == 2, shape
    assert "Compact(Project(Join" not in shape
    # the third Compact sits on customer's filter, as it did
    assert shape.count("Compact(") == 3, shape
    joins = [n for n in P.walk_plan(root) if isinstance(n, P.JoinNode)]
    assert len(joins) == 2 and all(P.compacts_its_match(j) for j in joins)


@pytest.mark.parametrize("kind", sorted(_OTHER_JOINS))
def test_other_joins_keep_the_compact_above_their_projects(kind, monkeypatch):
    from trino_tpu.sql.planner import optimizer

    if kind == "left":
        monkeypatch.setattr(optimizer, "COMPACT_MIN_RATIO", 0.0)
    root = plan_sql(Session(), _OTHER_JOINS[kind])
    shape = _shape(root)
    join_type = {"filter": "inner"}.get(kind, kind)
    assert f"Aggregation(Compact(Project(Join[{join_type}](" in shape, shape
    assert f"Compact(Join[{join_type}]" not in shape
    join, = [n for n in P.walk_plan(root) if isinstance(n, P.JoinNode)]
    assert not P.compacts_its_match(join)
    # the same statement without what disqualified it takes the new shape
    if kind == "filter":
        plain = plan_sql(Session(), _OTHER_JOINS[kind].replace(
            "and l_extendedprice > o_totalprice ", ""))
        assert "Project(Compact(Join[inner](" in _shape(plain)


# ------------------------------------------- the executor under the plan
def test_q3_runs_its_joins_compacted_and_keeps_their_stats(
        monkeypatch, compacting_plans):
    session = Session()
    root = plan_sql(session, QUERIES[3])
    ex = Executor(session)
    got = ex.execute_checked(root).to_pylist()
    kernels = list(ex.kernel_stats.values())
    counted = [r for r in kernels if r["compactedJoins"]]
    assert counted and {r["operator"] for r in counted} == {"Join"}
    squeezed = [r for r in kernels if r["prefixCompactions"]]
    assert {r["operator"] for r in squeezed} == {"Compact"}
    pairs = [(n.source, n) for n in P.walk_plan(root)
             if isinstance(n, P.CompactNode) and P.compacts_its_match(n.source)]
    assert len(pairs) == 2
    for join, compact in pairs:
        join_row = ex.kernel_stats[(join.id, "Join")]
        compact_row = ex.kernel_stats[(compact.id, "Compact")]
        # the join squeezed the page iff the Compact above counts it (at
        # tiny the second join's match fills its 512-slot probe page: the
        # plain path, as compact_to would return that page as it came)
        assert join_row["compactedJoins"] == compact_row["prefixCompactions"]
        assert join_row["launches"] == 1 and compact_row["launches"] == 1
        # the one read that sizes the squeeze is the join's now
        assert "join-emit-count" in join_row["hostSyncSites"]
        assert "join-emit-count" not in compact_row["hostSyncSites"]
        # the rows it always put out; the Compact adds none and loses none
        js, cs = ex.node_stats[join.id], ex.node_stats[compact.id]
        assert cs.input_rows == js.output_rows == cs.output_rows > 0
        assert cs.output_bytes == js.output_bytes
    # the plan with no CompactNode returns the same rows
    from trino_tpu.exec.query import run_query
    from trino_tpu.sql.planner import optimizer

    monkeypatch.setattr(optimizer, "COMPACT_MIN_SLOTS", 1 << 30)
    again = run_query(Session(), QUERIES[3]).rows
    assert len(got) == 10 and [tuple(r) for r in again] == got


def test_a_compiled_query_grows_the_joins_capacity_and_recovers(
        compacting_plans):
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import run_query
    from trino_tpu.sql.planner import stats

    session = Session()
    root = plan_sql(session, QUERIES[3])
    hints = stats.estimate_capacity_hints(session, root)
    compacts = [n for n in P.walk_plan(root) if isinstance(n, P.CompactNode)
                and P.compacts_its_match(n.source)]
    assert len(compacts) == 2
    for n in compacts:
        hints[f"cmp:{n.id}"] = 16  # far under the match
    cq = CompiledQuery.build(session, root, dict(hints))
    rows = cq.run().to_pylist()
    assert rows == [tuple(r) for r in run_query(Session(), QUERIES[3]).rows]
    for n in compacts:  # doubled until the match fitted
        assert cq.capacity_hints[f"cmp:{n.id}"] > 16


def test_the_spmd_tier_keeps_its_own_lookup_join():
    from trino_tpu.parallel.spmd import SpmdExecutor

    join = _join()
    assert Executor(Session())._join_compacts_match(join)
    assert not SpmdExecutor(Session(), {})._join_compacts_match(join)
    assert not Executor(Session())._join_compacts_match(
        _join(filter=object()))


# ------------------------------------------------- the counter, end to end
def test_served_statements_fold_the_counter_into_profile_and_system_table(
        compacting_plans):
    import json
    import urllib.request

    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="cj-w0")
    worker.start()

    def counted(client, sql):
        _cols, rows = client.execute(sql)
        req = urllib.request.Request(
            f"{coord.base_url}/v1/query/{client.query_id}/profile",
            headers={"X-Trino-User": "test"})
        kernels = json.loads(urllib.request.urlopen(req).read())["kernels"]
        assert kernels and all("compactedJoins" in k for k in kernels)
        assert all(k["operator"] == "Join" for k in kernels
                   if k["compactedJoins"])
        _cols, table = client.execute(
            "select coalesce(sum(compacted_joins), 0) "
            "from system.runtime.kernels "
            f"where query_id = '{client.query_id}'")
        total = sum(k["compactedJoins"] for k in kernels)
        assert table == [[total]]
        return rows, total

    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        client = StatementClient(coord.base_url, {
            "catalog": "tpch", "schema": "tiny",
            "result_cache_enabled": "false"})
        rows, total = counted(client, QUERIES[3])
        assert len(rows) == 10 and total >= 1
        _rows, none = counted(client, QUERIES[6])  # a scan and a filter
        assert none == 0
    finally:
        worker.stop()
        coord.stop()
