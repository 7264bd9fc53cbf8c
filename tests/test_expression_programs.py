"""A Filter's predicate and a Project's expressions as ONE compiled program.

``Executor._as_expression_program`` hands the expressions of a Filter or a
Project to ``expression_program``: the same ``expr_lower.lower`` body,
traced once per key instead of dispatched primitive by primitive. Literals
are arguments, so bindings share a program; what the lowering decides from
a value bound is answered in the key (``ops/expr_key.py``). The reference
throughout is the lowering itself, run untraced: an executor that is not
the eager tier declines the seam and dispatches what it always did.
"""
import dataclasses

import jax
import numpy as np
import pytest

from tpch_sql import QUERIES
from trino_tpu import Session
from trino_tpu import types as T
from trino_tpu.data.page import Column, Page
from trino_tpu.exec.executor import Executor, QueryError, expression_program
from trino_tpu.exec.query import plan_sql
from trino_tpu.obs.devprofiler import charge_to, new_kernel_row
from trino_tpu.ops import expr_lower as L
from trino_tpu.sql import ir
from trino_tpu.sql.planner import plan as P

_COMPILES = []
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _on_event(event, duration, **_kw):
    if event == _BACKEND_COMPILE:
        _COMPILES.append(duration)


jax.monitoring.register_event_duration_secs_listener(_on_event)


@pytest.fixture(scope="module")
def session():
    s = Session({"catalog": "tpch", "schema": "tiny"})
    s.catalogs["memory"].create_table(
        "t", "wide", [("k", T.BIGINT), ("v", T.decimal(38, 0))],
        [(1, 12345678901234567890123456789012345678), (0, 5), (2, None)])
    return s


def _nodes(session, sql):
    """(node, its input page) for every Filter and Project of ``sql``."""
    root = plan_sql(session, sql)
    return [(n, Executor(session).execute(n.source))
            for n in P.walk_plan(root)
            if isinstance(n, (P.FilterNode, P.ProjectNode))]


def _run(executor, node, page):
    """``node``'s own work over ``page``, its source already executed."""
    executor.execute = lambda _node, **_how: page
    return getattr(executor, f"_exec_{type(node).__name__}")(node)


def _both(session, node, page):
    """(program page, eager page, kernel row, the two executors)."""
    fused, eager = Executor(session), Executor(session)
    eager.eager_tier = False  # declines the seam: the lowering as it stands
    row = new_kernel_row("1", type(node).__name__, "eager")
    with charge_to(row):
        got = _run(fused, node, page)
    return got, _run(eager, node, page), row, fused, eager


def _assert_same_page(got: Page, want: Page):
    assert got.replicated == want.replicated
    assert got.live_prefix == want.live_prefix
    assert (got.sel is None) == (want.sel is None)
    if got.sel is not None:
        assert got.sel.dtype == want.sel.dtype
        assert np.array_equal(np.asarray(got.sel), np.asarray(want.sel))
    assert len(got.columns) == len(want.columns)
    for i, (g, w) in enumerate(zip(got.columns, want.columns)):
        assert g.type == w.type, i
        assert g.vrange == w.vrange, (i, g.vrange, w.vrange)
        assert g.ascending == w.ascending, i
        if w.dictionary is None:
            assert g.dictionary is None, i
        else:  # an input's dictionary is put back; a new one is equal
            assert g.dictionary.values == w.dictionary.values, i
        for attr in ("values", "nulls", "hi"):
            a, b = getattr(g, attr), getattr(w, attr)
            assert (a is None) == (b is None), (i, attr)
            if a is not None:
                assert a.dtype == b.dtype, (i, attr, a.dtype, b.dtype)
                assert np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=a.dtype.kind == "f"), (i, attr)


def _computes(node) -> bool:
    return isinstance(node, P.FilterNode) or any(
        not isinstance(e, ir.ColumnRef) for e in node.expressions)


@pytest.mark.parametrize("q", sorted(QUERIES))
def test_every_filter_and_project_of_tpch_equals_the_eager_path(session, q):
    """Selection, values, nulls, hi limbs, dictionaries, value ranges and
    the deferred errors' codes are the eager path's; a node the seam takes
    counts one program, one it declines one eager body."""
    nodes = _nodes(session, QUERIES[q])
    assert nodes
    for node, page in nodes:
        got, want, row, fused, eager = _both(session, node, page)
        _assert_same_page(got, want)
        assert [c for c, _ in fused.errors] == [c for c, _ in eager.errors]
        assert ([bool(f) for _, f in fused.errors]
                == [bool(f) for _, f in eager.errors])
        fused.raise_errors()
        taken = (row["exprPrograms"], row["exprEager"])
        assert taken in ((1, 0), (0, 1)) if _computes(node) else taken == (
            0, 0)


@pytest.mark.parametrize("q", [1, 6])
def test_the_scan_agg_templates_run_as_programs(session, q):
    """q1's and q6's Filters and computing Projects are all programs."""
    counted = 0
    for node, page in _nodes(session, QUERIES[q]):
        *_, row, _f, _e = _both(session, node, page)
        assert row["exprEager"] == 0
        counted += row["exprPrograms"]
    assert counted == 2


# --------------------------------------------------- deferred errors survive
@pytest.mark.parametrize("sql,code", [
    ("select l_quantity / (l_linenumber - l_linenumber) from lineitem",
     L.DIVISION_BY_ZERO),
    ("select cast(v as decimal(10, 0)) from memory.t.wide",
     L.DECIMAL_OVERFLOW),
    # a column-free subtree whose error fires is not folded on the host
    ("select l_quantity from lineitem where l_quantity > 10 / (2 - 2)",
     L.DIVISION_BY_ZERO),
])
def test_a_deferred_error_raises_the_same_query_error(session, sql, code):
    (node, page), = [(n, p) for n, p in _nodes(session, sql)
                     if _computes(n)]
    got, want, row, fused, eager = _both(session, node, page)
    assert row["exprPrograms"] == 1
    _assert_same_page(got, want)
    for ex in (fused, eager):
        with pytest.raises(QueryError) as raised:
            ex.raise_errors()
        assert raised.value.code == code
    with pytest.raises(QueryError) as raised:
        Executor(session).execute_checked(plan_sql(session, sql))
    assert raised.value.code == code


def test_a_folded_subtree_keeps_its_place_among_the_errors(session):
    """``10 / 2`` folds on the host into one argument; its division-by-zero
    check, which did not fire, keeps its place in the error list."""
    node, page = _filter_of(
        session, "select count(*) from lineitem "
                 "where l_quantity > 10 / 2 and l_discount / (l_tax + 1) > 0")
    got, want, row, fused, eager = _both(session, node, page)
    assert row["exprPrograms"] == 1
    _assert_same_page(got, want)
    assert [c for c, _ in fused.errors] == [c for c, _ in eager.errors] == [
        L.DIVISION_BY_ZERO, L.DIVISION_BY_ZERO]
    fused.raise_errors()


# ------------------------------------------------- literals are arguments
Q6 = ("select sum(l_extendedprice * l_discount) from lineitem "
      "where l_shipdate >= date '{d}' "
      "and l_shipdate < date '{d}' + interval '1' year "
      "and l_discount between {disc} - 0.01 and {disc} + 0.01 "
      "and l_quantity < {qty}")


def _filter_of(session, sql):
    (node, page), = [(n, p) for n, p in _nodes(session, sql)
                     if isinstance(n, P.FilterNode)]
    return node, page


def test_q6_bindings_share_one_program(session):
    """The second binding of q6 compiles nothing: its literals are the
    program's arguments, and its answers those of the first."""
    first, second = (
        _filter_of(session, Q6.format(d=d, disc=disc, qty=qty))
        for d, disc, qty in (("1994-01-01", "0.06", 24),
                             ("1995-01-01", "0.07", 25)))
    ex = Executor(session)
    _run(ex, *first)
    programs, compiles = expression_program._cache_size(), len(_COMPILES)
    row = new_kernel_row("1", "Filter", "eager")
    with charge_to(row):
        got = _run(ex, *second)
    assert row["exprPrograms"] == 1
    assert expression_program._cache_size() == programs
    assert len(_COMPILES) == compiles
    eager = Executor(session)
    eager.eager_tier = False
    _assert_same_page(got, _run(eager, *second))


def test_prepared_point_lookups_share_one_program():
    """50 EXECUTEs of the point cell's statement, each another key, through
    the coordinator's fast path: after the first, no program is added and
    nothing compiles, and every answer is the key's row."""
    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="expr-w0")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        client = StatementClient(coord.base_url, {
            "catalog": "tpch", "schema": "tiny",
            "result_cache_enabled": "false", "device_cache_enabled": "false",
            "short_query_fast_path": "true"})
        client.execute(
            "PREPARE point FROM select o_orderkey, o_custkey, o_totalprice, "
            "o_orderdate from orders where o_orderkey = ?")
        keys = [1 + (k * 2_999) % 15_000 for k in range(50)]
        _cols, rows = client.execute(f"EXECUTE point USING {keys[0]}")
        assert [r[0] for r in rows] == [keys[0]]
        programs, compiles = (expression_program._cache_size(),
                              len(_COMPILES))
        for key in keys[1:]:
            _cols, rows = client.execute(f"EXECUTE point USING {key}")
            assert [r[0] for r in rows] == [key]
        assert expression_program._cache_size() == programs
        assert len(_COMPILES) == compiles
    finally:
        worker.stop()
        coord.stop()


def test_a_bound_decides_the_key_not_the_value(session):
    """Two literals whose bounds lead the lowering the same way share a
    program, and each projected column carries its OWN exact range."""
    sqls = [f"select l_extendedprice * ({k} - l_discount) from lineitem"
            for k in (1, 5)]
    (a_node, page), = _nodes(session, sqls[0])
    (b_node, _), = _nodes(session, sqls[1])
    ex = Executor(session)
    got_a = _run(ex, a_node, page)
    programs = expression_program._cache_size()
    got_b = _run(ex, b_node, page)
    assert expression_program._cache_size() == programs
    for node, got in ((a_node, got_a), (b_node, got_b)):
        eager = Executor(session)
        eager.eager_tier = False
        _assert_same_page(got, _run(eager, node, page))
    assert got_a.columns[0].vrange != got_b.columns[0].vrange


def test_a_literal_read_on_the_host_stays_in_the_key(session):
    """``round``'s digits are read on the host: the literal is kept in the
    program's expression, and another digit count is another program."""
    pages = [_nodes(session, f"select round(l_extendedprice, {d}) "
                             "from lineitem")[0] for d in (0, 1, 0)]
    ex = Executor(session)
    compiled = []
    for node, page in pages:
        row = new_kernel_row("1", "Project", "eager")
        before = len(_COMPILES)
        with charge_to(row):
            got = _run(ex, node, page)
        compiled.append(len(_COMPILES) > before)
        assert row["exprPrograms"] == 1
        eager = Executor(session)
        eager.eager_tier = False
        _assert_same_page(got, _run(eager, node, page))
    assert compiled[1:] == [True, False]


# ------------------------------------------------------------- declines
def test_nested_columns_decline(session):
    node = P.FilterNode(None, ir.Call(T.BOOLEAN, "gt", (
        ir.Call(T.BIGINT, "cardinality", (ir.ColumnRef(T.array_of(T.BIGINT), 0),)),
        ir.Constant(T.BIGINT, 1))))
    page = Page([Column.from_python(T.array_of(T.BIGINT), [[1, 2], [3], []])])
    got, want, row, _f, _e = _both(session, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (0, 1)
    _assert_same_page(got, want)
    assert np.asarray(got.sel).tolist() == [True, False, False]


@pytest.mark.parametrize("sql", [
    # int64 calendar math over a column
    "select extract(year from l_shipdate) from lineitem",
    # int128 long division: a two-limb numerator
    "select v / (k + 1) from memory.t.wide",
])
def test_unrolled_arithmetic_declines(session, sql):
    """What unrolls into some hundred primitives a row stays eager: the
    TPU compiler takes tens of seconds over it fused."""
    (node, page), = [(n, p) for n, p in _nodes(session, sql) if _computes(n)]
    got, want, row, _f, _e = _both(session, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (0, 1)
    _assert_same_page(got, want)


def test_calendar_arithmetic_over_literals_folds_on_the_host(session):
    """Over literals alone the calendar is lowered once on the host and the
    rest is a program."""
    node, page = _filter_of(
        session, "select count(*) from lineitem where l_shipdate < "
                 "date '1995-01-31' + interval '1' month")
    got, want, row, _f, _e = _both(session, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (1, 0)
    _assert_same_page(got, want)


def test_a_non_deterministic_call_declines(session):
    (node, page), = _nodes(session, "select l_quantity + random() from lineitem")
    *_, row, _f, _e = _both(session, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (0, 1)


def test_a_traced_tier_declines_and_counts_eager(session):
    node, page = _filter_of(session, QUERIES[6])
    ex = Executor(session)
    ex.eager_tier = False
    row = new_kernel_row("1", "Filter", "eager")
    with charge_to(row):
        _run(ex, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (0, 1)


@pytest.mark.parametrize("sql", [
    "select count(*) from lineitem where l_shipmode = 'MAIL'",
    "select count(*) from lineitem where l_shipmode in ('MAIL', 'SHIP')",
    "select count(*) from part where p_name like '%green%'",
    "select count(*) from lineitem where l_shipmode < l_shipinstruct",
])
def test_a_table_over_a_dictionary_s_words_is_an_argument(session, sql):
    """A varchar compared with a literal or another column, and LIKE, read
    their dictionaries' words: the table is built on the host from the
    page's own dictionary and handed to the program."""
    node, page = _filter_of(session, sql)
    got, want, row, _f, _e = _both(session, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (1, 0)
    _assert_same_page(got, want)


def test_another_vocabulary_of_the_same_size_shares_the_program(session):
    """The table is an argument: a page whose dictionary holds other words
    (the same count) compiles nothing, and answers by ITS words."""
    node, page = _filter_of(
        session, "select count(*) from lineitem where l_shipmode = 'MAIL'")
    channel = node.predicate.args[0].index
    col = page.columns[channel]
    renamed = Column.from_python(T.VARCHAR, [
        w if w == "MAIL" else w.lower() for w in col.to_python()])
    assert len(renamed.dictionary) == len(col.dictionary)
    assert renamed.dictionary.values != col.dictionary.values
    renamed = dataclasses.replace(  # the column's own metadata kept
        col, values=renamed.values.astype(col.values.dtype),
        dictionary=renamed.dictionary)
    other = Page([renamed if i == channel else c
                  for i, c in enumerate(page.columns)], page.sel)
    ex = Executor(session)
    _run(ex, node, page)
    compiles = len(_COMPILES)
    got = _run(ex, node, other)
    assert len(_COMPILES) == compiles
    eager = Executor(session)
    eager.eager_tier = False
    _assert_same_page(got, _run(eager, node, other))


def test_a_new_vocabulary_declines(session, caplog):
    """``substring`` makes a dictionary out of the page's words: eager, as
    foreseen (nothing is logged)."""
    (node, page), = [(n, p) for n, p in _nodes(
        session, "select substring(c_phone, 1, 2) from customer")
        if _computes(n)]
    with caplog.at_level("WARNING", logger="trino_tpu.exec.executor"):
        got, want, row, _f, _e = _both(session, node, page)
    assert (row["exprPrograms"], row["exprEager"]) == (0, 1)
    _assert_same_page(got, want)
    assert not caplog.records


def test_an_unforeseen_trace_failure_is_logged_once_and_runs_eagerly(
        session, caplog, monkeypatch):
    """A program that fails to trace for a reason the seam does not foresee
    leaves the expressions eager, and says so once for them."""
    from trino_tpu.exec import executor

    def fails(*_args):
        raise RuntimeError("not foreseen")

    monkeypatch.setattr(executor, "_LEARNT", {})  # learnt for this test only
    monkeypatch.setattr(executor, "expression_program", fails)
    node, page = _filter_of(session, QUERIES[6])
    with caplog.at_level("WARNING", logger="trino_tpu.exec.executor"):
        for _ in range(2):
            got, want, row, _f, _e = _both(session, node, page)
            assert (row["exprPrograms"], row["exprEager"]) == (0, 1)
            _assert_same_page(got, want)
    (record,) = caplog.records
    assert "runs eagerly" in record.getMessage()
    assert record.exc_info[0] is RuntimeError


def test_zero_rows_and_no_columns(session):
    """A page of no rows, and a predicate that reads no column."""
    node, page = _filter_of(session, QUERIES[6])
    empty = Page([dataclasses.replace(c, values=c.values[:0]) for c in
                  page.columns], None if page.sel is None else page.sel[:0])
    got, want, row, _f, _e = _both(session, node, empty)
    assert row["exprPrograms"] == 1
    _assert_same_page(got, want)
    constant = P.FilterNode(None, ir.Call(T.BOOLEAN, "lt", (
        ir.Constant(T.BIGINT, 1), ir.Constant(T.BIGINT, 2))))
    got, want, row, _f, _e = _both(session, constant, page)
    assert row["exprPrograms"] == 1
    _assert_same_page(got, want)
    assert got.sel.shape == (page.num_rows,)


# ------------------------------------------- the counters reach the coordinator
def test_served_q6_folds_the_counters_into_profile_and_system_table():
    import json
    import urllib.request

    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="expr-w1")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        client = StatementClient(coord.base_url, {
            "catalog": "tpch", "schema": "tiny",
            "result_cache_enabled": "false"})
        _cols, rows = client.execute(QUERIES[6])
        assert len(rows) == 1
        req = urllib.request.Request(
            f"{coord.base_url}/v1/query/{client.query_id}/profile",
            headers={"X-Trino-User": "test"})
        kernels = json.loads(urllib.request.urlopen(req).read())["kernels"]
        programs = sum(k["exprPrograms"] for k in kernels)
        assert programs >= 2  # the Filter and the Project
        assert sum(k["exprEager"] for k in kernels) == 0
        _cols, table = client.execute(
            "select sum(expr_programs), sum(expr_eager) "
            "from system.runtime.kernels "
            f"where query_id = '{client.query_id}'")
        assert table == [[programs, 0]]
    finally:
        worker.stop()
        coord.stop()
