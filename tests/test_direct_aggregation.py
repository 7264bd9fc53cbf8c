"""The eager tier's direct-layout aggregation as ONE compiled program.

``Executor._as_one_program`` hands an aggregation body whose grouping takes
the direct layout (no keys, or dictionary / boolean keys of a small
cardinality product) to ``direct_aggregation``: the same body, traced once
per (spec, shape) instead of dispatched primitive by primitive. The
reference throughout is that body itself, run untraced: an executor that is
not the eager tier declines the seam and dispatches what it always did.
"""
import dataclasses
from decimal import Decimal

import jax.numpy as jnp
import numpy as np
import pytest

from tpch_sql import QUERIES
from trino_tpu import Session
from trino_tpu import types as T
from trino_tpu.data.page import Column, Page
from trino_tpu.exec import query as query_module
from trino_tpu.exec.executor import Executor, QueryError, direct_aggregation
from trino_tpu.exec.query import plan_sql, run_query
from trino_tpu.obs.devprofiler import (
    charge_to, install_process_hooks, new_kernel_row)
from trino_tpu.ops import expr_lower as L
from trino_tpu.ops import segments as seg
from trino_tpu.sql.planner import plan as P

BIG = Decimal("12345678901234567890123456789012345678")


@dataclasses.dataclass
class _Source(P.PlanNode):
    """A plan leaf that only knows its types: the source of a hand-made
    aggregation node."""

    types: list = None

    @property
    def output_types(self):
        return self.types


@pytest.fixture(scope="module")
def session():
    s = Session({"catalog": "tpch", "schema": "tiny"})
    s.catalogs["memory"].create_table(
        "t", "wide", [("k", T.BOOLEAN), ("v", T.decimal(38, 0))],
        [(True, BIG), (False, -BIG), (True, Decimal(5)), (False, None),
         (True, BIG)])
    return s


def _aggregation(session, sql):
    """(the statement's single-step aggregation node, its input page)."""
    root = plan_sql(session, sql)
    (agg,) = [n for n in P.walk_plan(root) if isinstance(n, P.AggregationNode)]
    assert agg.step == "single"
    return agg, Executor(session).execute(agg.source)


def _keyed(n_keys, rows=1000, has_sel=True):
    """sum / count / min over a bigint, grouped by a varchar of ``n_keys``
    distinct values: capacity ``n_keys``."""
    rng = np.random.default_rng(n_keys)
    keys = Column.from_python(
        T.VARCHAR, [f"k{i % n_keys:03d}" for i in range(rows)])
    vals = Column(T.BIGINT, jnp.asarray(rng.integers(-10**9, 10**9, rows)),
                  vrange=(-10**9, 10**9))
    sel = jnp.asarray(rng.random(rows) < 0.7) if has_sel else None
    node = P.AggregationNode(
        _Source(types=[T.VARCHAR, T.BIGINT]), [0],
        [P.AggregateCall("sum", 1, T.BIGINT),
         P.AggregateCall("count", None, T.BIGINT),
         P.AggregateCall("min", 1, T.BIGINT)])
    return node, Page([keys, vals], sel)


def _with(page, channel=None, sel="keep", **fields):
    """``page`` with one column's fields and / or its selection replaced."""
    columns = list(page.columns)
    if channel is not None:
        columns[channel] = dataclasses.replace(columns[channel], **fields)
    return Page(columns, page.sel if isinstance(sel, str) else sel,
                page.replicated)


Q_LONG_SUM = ("select l_returnflag, sum(cast(l_extendedprice as decimal(38,2))) "
              "from lineitem group by l_returnflag")
Q_NULLABLE = (
    "select l_linestatus, sum(x), count(x), avg(x), min(x), max(x), count(*) "
    "from (select l_linestatus, case when l_quantity > 10 "
    "then l_extendedprice end as x from lineitem) group by l_linestatus")
Q_MERGEABLE = (
    "select l_linestatus, min(l_shipdate), max(l_extendedprice), "
    "count_if(l_quantity > 30) from lineitem group by l_linestatus")
ALL = ("single", "partial_final", "partial_intermediate_final")


def _case_q1(s):
    return _aggregation(s, QUERIES[1].replace("lineitem", "tpch.tiny.lineitem"))


def _case_q6(s):
    return _aggregation(s, QUERIES[6].replace("lineitem", "tpch.tiny.lineitem"))


def _case_long_sum(s, vrange):
    node, page = _aggregation(s, Q_LONG_SUM)
    channel = node.aggregates[0].arg_channel
    assert page.columns[channel].hi is None
    page = _with(page, channel, vrange=vrange)
    assert Executor._sum_fits_int64(page, channel, page.num_rows) == (
        vrange is not None)
    return node, page


def _case_hi_limb(s):
    return _aggregation(s, "select k, sum(v), count(v) from memory.t.wide group by k")


def _case_avg_decimal(s):
    # a boolean key: capacity 2
    return _aggregation(
        s, "select l_quantity > 25, avg(l_extendedprice), avg(l_discount) "
           "from lineitem group by 1")


def _case_value_carrying(s):
    # min of a varchar keeps the argument's dictionary (single step only:
    # a varchar state column has none to cross the wire with)
    return _aggregation(
        s, "select l_linestatus, min(l_shipmode), max(l_shipinstruct), "
           "bool_and(l_discount > 0.01), bool_or(l_tax > 0.07), "
           "count_if(l_quantity > 30) from lineitem group by l_linestatus")


def _case_no_sel(s):
    node, page = _aggregation(s, Q_NULLABLE)
    page = page.compact()
    assert page.sel is None
    return node, page


def _case_all_dead(s):
    node, page = _aggregation(s, Q_MERGEABLE)
    return node, _with(page, sel=jnp.zeros((page.num_rows,), bool))


def _case_zero_rows(s):
    node, page = _aggregation(s, Q_MERGEABLE)
    return node, page.compact().slice_rows(0, 0)


CASES = {
    "q1": (_case_q1, ALL),  # capacity 6, eight aggregates, sel present
    "q6": (_case_q6, ALL),  # the global aggregate: capacity 1
    "long_sum_bounded": (lambda s: _case_long_sum(s, (0, 10**9)), ALL[:2]),
    "long_sum_unbounded": (lambda s: _case_long_sum(s, None), ALL[:2]),
    "long_sum_hi_limb": (_case_hi_limb, ALL),
    "avg_decimal": (_case_avg_decimal, ALL),
    "value_carrying": (_case_value_carrying, ALL[:1]),
    "mergeable": (lambda s: _aggregation(s, Q_MERGEABLE), ALL),
    "nullable_argument": (lambda s: _aggregation(s, Q_NULLABLE), ALL),
    "no_sel": (_case_no_sel, ALL),
    "all_dead": (_case_all_dead, ALL),
    "zero_rows": (_case_zero_rows, ALL[:1]),
    "capacity_max": (
        lambda s: _keyed(seg.DIRECT_CAPACITY_MAX), ALL[:2]),
}


def _run(ex, node, page, shape, empty):
    """Every page the shape produces, in order (states and results)."""
    if shape == "single":
        return [ex.aggregate_page(node, page)]
    partial, final = (
        P.AggregationNode(node.source, list(node.group_channels),
                          node.aggregates, step=step)
        for step in ("partial", "final"))
    whole = page if page.sel is None else Page(page.columns, None)
    n = page.num_rows
    cuts = [0, n // 3, 2 * n // 3, n]
    states = []
    for lo, hi in zip(cuts, cuts[1:]):
        part = whole.slice_rows(lo, hi)
        if page.sel is not None:
            part = Page(part.columns, page.sel[lo:hi])
        states.append(ex.aggregate_partial(partial, part))
    pages = list(states)

    def shipped(p):
        # the wire carries compacted pages; where nothing is live the
        # worker folds the canonical empty page (server/task.py)
        p = p.compact()
        return p if p.num_rows else empty

    compacted = [shipped(p) for p in states]
    if shape == "partial_intermediate_final":
        running = ex.aggregate_intermediate(
            final, Page.concat_all(compacted[:2]))
        pages.append(running)
        running = ex.aggregate_intermediate(
            final, Page.concat_all([shipped(running), compacted[2]]))
        pages.append(running)
        compacted = [shipped(running)]
    pages.append(ex.aggregate_final(final, Page.concat_all(compacted)))
    return pages


def _assert_same_page(got: Page, want: Page):
    assert len(got.columns) == len(want.columns)
    assert got.replicated == want.replicated
    assert (got.sel is None) == (want.sel is None)
    if got.sel is not None:
        assert got.sel.dtype == want.sel.dtype
        assert np.array_equal(got.sel, want.sel)
    for i, (g, w) in enumerate(zip(got.columns, want.columns)):
        assert g.type == w.type, i
        assert g.dictionary is w.dictionary, i
        assert g.vrange == w.vrange, i
        for attr in ("values", "nulls", "hi"):
            a, b = getattr(g, attr), getattr(w, attr)
            assert (a is None) == (b is None), (i, attr)
            if a is not None:
                assert a.dtype == b.dtype, (i, attr, a.dtype, b.dtype)
                assert np.array_equal(np.asarray(a), np.asarray(b)), (i, attr)


@pytest.mark.parametrize(
    "case,shape",
    [(c, shape) for c, (_, shapes) in CASES.items() for shape in shapes])
def test_one_program_equals_the_untraced_body(session, case, shape):
    node, page = CASES[case][0](session)
    fused, untraced = Executor(session), Executor(session)
    untraced.eager_tier = False  # declines the seam: the body as it stands
    row, ref_row = (new_kernel_row("1", "Aggregation", "eager")
                    for _ in range(2))
    empty = Page.all_dead(P.AggregationNode(
        node.source, list(node.group_channels), node.aggregates,
        step="partial").output_types)
    with charge_to(row):
        got = _run(fused, node, page, shape, empty)
    with charge_to(ref_row):
        want = _run(untraced, node, page, shape, empty)
    assert (row["aggPrograms"], row["aggEager"]) == (len(got), 0)
    assert (ref_row["aggPrograms"], ref_row["aggEager"]) == (0, 0)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        _assert_same_page(g, w)
    assert [c for c, _ in fused.errors] == [c for c, _ in untraced.errors]
    fused.raise_errors()


# ------------------------------------------------ engages, and stays engaged
def _kernel_rows_of(monkeypatch, session, sql):
    made = []

    class Recording(Executor):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(query_module, "Executor", Recording)
    run_query(session, sql)
    (ex,) = made
    return [r for r in ex.kernel_stats.values()
            if r["operator"] == "Aggregation"]


def test_q1_runs_its_aggregation_as_programs(session, monkeypatch):
    rows = _kernel_rows_of(monkeypatch, session, QUERIES[1])
    assert rows
    assert sum(r["aggPrograms"] for r in rows) >= 1
    assert sum(r["aggEager"] for r in rows) == 0


@pytest.mark.parametrize("sql", [
    "select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey",
    # over DIRECT_CAPACITY_MAX, a regrouping aggregate, a dictionary's content
    "select l_comment, count(*) from lineitem group by l_comment",
    "select l_returnflag, count(distinct l_suppkey) from lineitem "
    "group by l_returnflag",
    "select l_returnflag, checksum(l_shipmode) from lineitem "
    "group by l_returnflag",
])
def test_other_layouts_and_aggregates_keep_the_eager_body(
        session, monkeypatch, sql):
    rows = _kernel_rows_of(monkeypatch, session, sql)
    assert sum(r["aggPrograms"] for r in rows) == 0
    assert sum(r["aggEager"] for r in rows) >= 1


def test_traced_tiers_are_not_counted(session):
    from trino_tpu.exec.compiled import CompiledQuery

    row = new_kernel_row("1", "CompiledBody", "compiled")
    with charge_to(row):
        page = CompiledQuery.build(
            session, plan_sql(session, QUERIES[6])).run()
    assert page.num_rows == 1
    assert (row["aggPrograms"], row["aggEager"]) == (0, 0)


def test_same_shape_and_spec_share_one_program(session):
    """A second page of the same shape compiles nothing, and neither does
    one that differs in value range or in dictionary CONTENT alone."""
    install_process_hooks()
    node, page = _keyed(6, rows=512)
    ex = Executor(session)
    ex.aggregate_page(node, page)  # compiles (or not: another test may have)
    programs = direct_aggregation._cache_size()
    renamed = Column.from_python(
        T.VARCHAR, [f"other{i % 6}" for i in range(512)])
    assert renamed.dictionary.values != page.columns[0].dictionary.values
    variants = [
        page,
        _with(page, 1, vrange=(-5, 5)),
        _with(page, 1, vrange=None),
        _with(page, 0, values=renamed.values, dictionary=renamed.dictionary),
    ]
    row = new_kernel_row("1", "Aggregation", "eager")
    with charge_to(row):
        outs = [ex.aggregate_page(node, p) for p in variants]
    assert row["aggPrograms"] == len(variants)
    assert row["compiles"] == 0
    assert direct_aggregation._cache_size() == programs
    # what stayed outside the program is put back on the output
    assert outs[3].columns[0].dictionary is renamed.dictionary
    assert outs[0].columns[0].dictionary is page.columns[0].dictionary
    assert outs[0].columns[0].to_python()[:2] == ["k000", "k001"]
    assert outs[3].columns[0].to_python()[:2] == ["other0", "other1"]
    # another shape, or another spec, is another program
    ex.aggregate_page(node, _keyed(6, rows=256)[1])
    ex.aggregate_page(node, _with(page, sel=None))
    assert direct_aggregation._cache_size() == programs + 2


def test_the_bound_is_part_of_the_spec_not_the_range(session):
    """Two ranges on the same side of the int64 bound share a program; the
    other side is another (the limb sum)."""
    node, page = _case_long_sum(session, (0, 10**9))
    ex = Executor(session)
    channel = node.aggregates[0].arg_channel
    ex.aggregate_page(node, page)
    ex.aggregate_page(node, _with(page, channel, vrange=None))
    programs = direct_aggregation._cache_size()
    ex.aggregate_page(node, _with(page, channel, vrange=(-7, 10**8)))
    ex.aggregate_page(node, _with(page, channel, vrange=(0, 2**62)))
    assert direct_aggregation._cache_size() == programs


# --------------------------------------------------- deferred errors survive
def test_deferred_overflow_raises_through_the_program(session):
    """min has no limb kernel: a long decimal whose high limb carries value
    degrades to the low word and flags DECIMAL_OVERFLOW, from inside the
    program exactly as from the eager body."""
    sql = "select k, min(v) from memory.t.wide group by k"
    node, page = _aggregation(session, sql)
    assert page.columns[node.aggregates[0].arg_channel].hi is not None
    fused, untraced = Executor(session), Executor(session)
    untraced.eager_tier = False
    row = new_kernel_row("1", "Aggregation", "eager")
    with charge_to(row):
        _assert_same_page(fused.aggregate_page(node, page),
                          untraced.aggregate_page(node, page))
    assert row["aggPrograms"] == 1
    assert [c for c, _ in fused.errors] == [L.DECIMAL_OVERFLOW]
    assert ([bool(f) for _, f in fused.errors]
            == [bool(f) for _, f in untraced.errors] == [True])
    for ex in (fused, untraced):
        with pytest.raises(QueryError) as raised:
            ex.raise_errors()
        assert raised.value.code == L.DECIMAL_OVERFLOW
    with pytest.raises(QueryError) as raised:
        Executor(session).execute_checked(plan_sql(session, sql))
    assert raised.value.code == L.DECIMAL_OVERFLOW
    # in range, the same program raises nothing
    ok = Executor(session)
    ok.aggregate_page(node, _with(
        page, node.aggregates[0].arg_channel,
        hi=jnp.zeros_like(page.columns[1].hi),
        values=jnp.abs(page.columns[1].values)))
    ok.raise_errors()


# ------------------------------------------- the counters reach the coordinator
def test_served_q1_folds_the_counters_into_profile_and_system_table():
    import json
    import urllib.request

    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="agg-w0")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        client = StatementClient(coord.base_url, {
            "catalog": "tpch", "schema": "tiny",
            "result_cache_enabled": "false"})
        _cols, rows = client.execute(QUERIES[1])
        assert len(rows) == 4
        req = urllib.request.Request(
            f"{coord.base_url}/v1/query/{client.query_id}/profile",
            headers={"X-Trino-User": "test"})
        kernels = json.loads(urllib.request.urlopen(req).read())["kernels"]
        # the worker's partial body and the final one, each one program
        assert sum(k["aggPrograms"] for k in kernels) >= 2
        assert sum(k["aggEager"] for k in kernels) == 0
        _cols, table = client.execute(
            "select sum(agg_programs), sum(agg_eager) "
            "from system.runtime.kernels "
            f"where query_id = '{client.query_id}'")
        assert table == [[sum(k["aggPrograms"] for k in kernels), 0]]
    finally:
        worker.stop()
        coord.stop()
