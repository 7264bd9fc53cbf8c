"""The deployment ``tpch_sf10_q18`` (TPC-H Q18 through the server), at tiny
and on the CPU: the two planner rules it forced (an IN subquery's semi-join
sunk under the inner joins it filters, ``optimizer.sink_semi_joins``; a
group-by on the table's partitioning key finished where the table is
scanned, ``fragmenter._colocated_aggregation``), the plans of the
benchmark's other statements left byte for byte as the parent planned them,
served Q18 equal to both oracles, and ``query_max_execution_time``."""
import json
import os
import re
import sqlite3

import numpy as np
import pytest

from trino_tpu import Session
from trino_tpu import types as T
from trino_tpu.exec.query import plan_sql, run_query
from trino_tpu.sql.planner import fragmenter
from trino_tpu.sql.planner import plan as P
from trino_tpu.sql.planner.fragmenter import fragment_plan

HERE = os.path.dirname(os.path.abspath(__file__))
Q18 = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (
        select l_orderkey from lineitem
        group by l_orderkey having sum(l_quantity) > {quantity})
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate limit 100
"""


def _nodes(root, kind):
    return [n for n in P.walk_plan(root) if isinstance(n, kind)]


def _tables(root):
    return sorted(n.table for n in _nodes(root, P.TableScanNode))


# ------------------------------------------------------- (a) the plan's shape
@pytest.mark.parametrize("schema", ["sf1", "sf10"])
def test_q18_filters_orders_before_it_joins_them(schema):
    """Planning only: no fragment hands on the unfiltered lineitem-orders
    join, the subquery's aggregation is whole in a source fragment, and
    nothing but customer and a few rows crosses an exchange."""
    s = Session({"catalog": "tpch", "schema": schema})
    frags = fragment_plan(plan_sql(s, Q18.format(quantity=313)), s)
    semis = [(f, j) for f in frags for j in _nodes(f.root, P.JoinNode)
             if j.join_type == "semi"]
    assert len(semis) == 1
    frag, semi = semis[0]
    # the semi-join sits on orders alone, under the join it filters
    assert _tables(semi.left) == ["orders"]
    assert not _nodes(semi.left, P.JoinNode)
    assert frag.partitioning == "source"
    lo = [j for j in _nodes(frag.root, P.JoinNode) if j.join_type == "inner"]
    assert len(lo) == 1 and lo[0].right is semi
    assert lo[0].distribution == semi.distribution == "colocated"
    # every fragment that joins lineitem to orders joins the FILTERED orders
    for f in frags:
        for j in _nodes(f.root, P.JoinNode):
            if j.join_type == "inner" and "lineitem" in _tables(j.left) \
                    and "orders" in _tables(j.right):
                assert any(x.join_type == "semi"
                           for x in _nodes(j.right, P.JoinNode))
    aggs = [a for a in _nodes(frag.root, P.AggregationNode)]
    assert [(a.step, a.distribution) for a in aggs] == [("single", "colocated")]
    assert _tables(aggs[0]) == ["lineitem"]
    assert not any(a.step in ("partial", "final")
                   for f in frags for a in _nodes(f.root, P.AggregationNode)
                   if _tables(a) == ["lineitem"])
    text = fragmenter.format_fragments(frags)
    assert "Aggregation [single/colocated] keys=[0]" in text
    assert "Join [semi/colocated]" in text
    # both probe-side scans of the joins receive the semi-join's filter
    dyn = {n.table: [c for _, _, c in n.dynamic_filters or ()]
           for n in _nodes(lo[0], P.TableScanNode) if n.dynamic_filters}
    assert "o_orderkey" in dyn["orders"] and dyn["lineitem"] == ["l_orderkey"]


with open(os.path.join(HERE, "parent_plans.json"), encoding="utf-8") as _f:
    PARENT_PLANS = json.load(_f)


# the cases a later PR planned otherwise and argued (PERF.md section 6, PR 36:
# q3 at SF 1, customer's filtered rows against the broadcast limit): that
# PR's text, held by tests/test_q9_deployment.py; the parent's file stays
with open(os.path.join(HERE, "q9_argued_plans.json"), encoding="utf-8") as _f:
    ARGUED_PLANS = json.load(_f)


def without_estimates(text):
    """EXPLAIN prints each join's estimated probe and build rows since
    PR 36; the plans pinned before it are compared without them."""
    return re.sub(r" est=\[probe \d+, build \d+\]", "", text)


@pytest.mark.parametrize("case", sorted(PARENT_PLANS))
def test_the_other_cells_statements_plan_as_the_parent_planned_them(case):
    """q1, q3, q6 and the point lookup at tiny, SF 1 and SF 10: the
    distributed plan's text is the parent's (``parent_plans.json``: EXPLAIN
    (TYPE DISTRIBUTED) of the benchmark's templates, taken from 8d84bdd
    before this change), byte for byte once the estimates EXPLAIN has
    printed on a join since PR 36 are taken out; a case in ``ARGUED_PLANS``
    reads the text argued for it instead."""
    from benchmark import spec

    schema, name, binding = case.split("/", 2)
    binding = json.loads(binding)
    t = spec.load_template(name)
    sql = (t.sql.replace("?", str(binding["key"])) if t.mode == "prepared"
           else t.sql.format(**binding))
    s = Session({"catalog": "tpch", "schema": schema})
    rows = run_query(s, "EXPLAIN (TYPE DISTRIBUTED) " + sql).rows
    assert without_estimates("\n".join(r[0] for r in rows)) == \
        ARGUED_PLANS.get(case, PARENT_PLANS[case])


# ---------------------------------- (b) the semi-join rule, against sqlite
A = [(i, (i * 7) % 11 if i % 9 else None, i % 5) for i in range(1, 41)]
A.append((None, 3, 1))                       # a NULL key on the probe side
B = [(i, i * 10, (i * 3) % 4) for i in range(0, 11)]
C = [(i, f"c{i}") for i in range(0, 4)]
S = [(i,) for i in (2, 3, 5, 8, 13, 21, 34, 40)]
S_NULL = S + [(None,)]                       # and one in the subquery


@pytest.fixture(scope="module")
def small():
    s = Session()
    mem = s.catalogs["memory"]
    db = sqlite3.connect(":memory:")
    for name, cols, rows in (
            ("a", ["id", "bk", "g"], A), ("b", ["id", "v", "ck"], B),
            ("c", ["id", "name"], C), ("s", ["x"], S), ("sn", ["x"], S_NULL)):
        types = [T.VARCHAR if c == "name" else T.BIGINT for c in cols]
        mem.create_table("t", name, list(zip(cols, types)), rows)
        db.execute(f"create table {name} ({', '.join(cols)})")
        db.executemany(
            f"insert into {name} values ({', '.join('?' * len(cols))})", rows)
    return s, db


def _same_as_sqlite(small, sql):
    s, db = small
    got = s.execute(sql.replace(" t.", " memory.t.")).rows
    want = db.execute(sql.replace(" t.", " ")).fetchall()
    key = lambda r: tuple((v is None, v) for v in r)  # noqa: E731
    assert sorted(map(tuple, got), key=key) == sorted(want, key=key)
    return plan_sql(s, sql.replace(" t.", " memory.t."))


SUNK = {
    "two-way, key on a": (
        "select a.id, b.v from t.a, t.b where a.bk = b.id "
        "and a.id in (select x from t.s)", "a"),
    "two-way, key on b": (
        "select a.id, b.v from t.a, t.b where a.bk = b.id "
        "and b.id in (select x from t.s)", "b"),
    "three-way, key on the middle table": (
        "select a.id, b.v, c.name from t.a, t.b, t.c where a.bk = b.id "
        "and b.ck = c.id and b.id in (select x from t.s)", "b"),
    "three-way, key on the last table": (
        "select a.id, c.name from t.a, t.b, t.c where a.bk = b.id "
        "and b.ck = c.id and c.id in (select x from t.s)", "c"),
    "NULL keys on both sides": (
        "select a.id, b.v from t.a, t.b where a.bk = b.id "
        "and a.id in (select x from t.sn)", "a"),
    "explicit inner join, key on the right input": (
        "select a.id, b.v from t.a join t.b on a.bk = b.id "
        "where b.id in (select x from t.sn)", "b"),
    "under an aggregation": (
        "select a.g, count(*), sum(b.v) from t.a, t.b where a.bk = b.id "
        "and a.id in (select x from t.s) group by a.g", "a"),
    "the subquery aggregates": (
        "select a.id, b.v from t.a, t.b where a.bk = b.id and a.g in "
        "(select g from t.a group by g having sum(id) > 150)", "a"),
}


@pytest.mark.parametrize("case", sorted(SUNK))
def test_an_in_subquery_is_planned_under_the_joins_it_filters(small, case):
    sql, table = SUNK[case]
    root = _same_as_sqlite(small, sql)
    (semi,) = [j for j in _nodes(root, P.JoinNode) if j.join_type == "semi"]
    assert not _nodes(semi.left, P.JoinNode), "the semi-join is over a join"
    assert _tables(semi.left) == [table]
    # and the joins it was on top of are still there, above it
    assert any(semi in P.walk_plan(j) for j in _nodes(root, P.JoinNode)
               if j.join_type == "inner")


STAYS = {
    "NOT IN keeps its place": (
        "select a.id, b.v from t.a, t.b where a.bk = b.id "
        "and a.g not in (select x from t.s)", "anti"),
    "key from the nullable side of an outer join": (
        "select a.id, b.v from t.a left join t.b on a.bk = b.id "
        "where b.id in (select x from t.s)", "semi"),
    "key computed from both sides": (
        "select a.id, b.v from t.a, t.b where a.bk = b.id "
        "and a.id + b.id in (select x from t.s)", None),
    "no join to sink under": (
        "select a.id from t.a where a.id in (select x from t.sn)", "semi"),
}


@pytest.mark.parametrize("case", sorted(STAYS))
def test_what_does_not_commute_is_left_where_it_was(small, case):
    sql, kind = STAYS[case]
    if kind is None:
        s, _db = small
        from trino_tpu.sql.planner.planner import PlanningError

        with pytest.raises(PlanningError):   # as before: not supported
            plan_sql(s, sql.replace(" t.", " memory.t."))
        return
    root = _same_as_sqlite(small, sql)
    (j,) = [j for j in _nodes(root, P.JoinNode) if j.join_type == kind]
    others = [x for x in _nodes(root, P.JoinNode) if x is not j]
    # every other join of the statement is still UNDER it
    assert all(x in P.walk_plan(j.left) or x in P.walk_plan(j.right)
               for x in others)
    if "join" in sql:
        assert any(x.join_type in ("inner", "left")
                   for x in _nodes(j.left, P.JoinNode))


# ---------------------------------------- (c) the aggregation rule's plans
TAKEN = {
    "keys equal to the partitioning column":
        "select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey",
    "keys a superset of it":
        "select l_orderkey, l_returnflag, count(*), max(l_tax) from lineitem "
        "group by l_returnflag, l_orderkey",
    "through a filter and a computed projection":
        "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) "
        "from lineitem where l_quantity > 10 group by l_orderkey",
    "a static constraint on the key does not disable it":
        "select l_orderkey, count(*) from lineitem where l_orderkey < 1000 "
        "group by l_orderkey",
    "orders, with a DISTINCT aggregate":
        "select o_orderkey, count(distinct o_custkey) from orders "
        "group by o_orderkey",
    "with its HAVING above it":
        "select l_orderkey from lineitem group by l_orderkey "
        "having sum(l_quantity) > 250",
}
NOT_TAKEN = {
    "keys that are not the partitioning column":
        "select l_partkey, sum(l_quantity) from lineitem group by l_partkey",
    "a table the connector does not partition":
        "select c_custkey, count(*) from customer group by c_custkey",
    "a computed key":
        "select l_orderkey + 1, count(*) from lineitem group by l_orderkey + 1",
    "a repartitioning join beneath":
        "select o_orderkey, count(*) from orders, customer "
        "where o_custkey = c_custkey group by o_orderkey",
    "a source behind an exchange":
        "select k, count(*) from (select l_orderkey as k from lineitem "
        "union all select o_orderkey as k from orders) group by k",
    "no keys":
        "select sum(l_quantity) from lineitem",
}


def _tiny(**props):
    return Session({"catalog": "tpch", "schema": "tiny", **props})


@pytest.mark.parametrize("case", sorted(TAKEN))
def test_a_group_by_on_the_partitioning_key_is_finished_at_the_scan(case):
    s = _tiny(join_max_broadcast_rows=1000)
    frags = fragment_plan(plan_sql(s, TAKEN[case]), s)
    aggs = [(f, a) for f in frags for a in _nodes(f.root, P.AggregationNode)]
    assert len(aggs) == 1, fragmenter.format_fragments(frags)
    frag, agg = aggs[0]
    assert (agg.step, agg.distribution) == ("single", "colocated")
    assert frag.partitioning == "source" and _nodes(frag.root, P.TableScanNode)
    assert frag.output_partition_channels is None
    # the HAVING filter stays with it, inside the source fragment
    if "having" in TAKEN[case]:
        assert any(agg in P.walk_plan(f_) for f_ in
                   _nodes(frag.root, P.FilterNode))


@pytest.mark.parametrize("case", sorted(NOT_TAKEN))
def test_any_other_group_by_is_cut_as_before(case, monkeypatch):
    s = _tiny(join_max_broadcast_rows=1000)
    root = plan_sql(s, NOT_TAKEN[case])
    frags = fragment_plan(root, s)
    aggs = [a for f in frags for a in _nodes(f.root, P.AggregationNode)]
    assert aggs and all(a.distribution is None for a in aggs)
    # and the plan is what the fragmenter cut without the rule
    monkeypatch.setattr(fragmenter, "_colocated_aggregation",
                        lambda *a, **k: False)
    assert fragmenter.format_fragments(fragment_plan(root, s)) == \
        fragmenter.format_fragments(frags)


# ------------------- the run layout: a presorted group-by with no gather
def _runs_by_numpy(run_start, x):
    import numpy as np

    out = np.zeros(len(x), dtype=np.int64)
    acc = 0
    for i, (s, v) in enumerate(zip(run_start, x)):
        acc = int(v) if s else acc + int(v)
        out[i] = ((acc + 2**63) % 2**64) - 2**63
    return out


@pytest.mark.parametrize("n", [1, 7, 1000, 5000])
@pytest.mark.parametrize("kind", ["small", "negative", "beyond 32 bits",
                                  "wrapping"])
def test_run_sums_and_counts_are_the_runs_own(n, kind):
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.ops import segments as seg

    rng = np.random.default_rng(n * 31 + len(kind))
    hi = {"small": 50, "negative": 50, "beyond 32 bits": 2**45,
          "wrapping": 2**62}[kind]
    lo = 0 if kind == "small" else -hi
    x = rng.integers(lo, hi, size=n, dtype=np.int64)
    run_start = rng.random(n) < 0.3
    run_start[0] = True
    m = rng.random(n) < 0.7
    got = np.asarray(seg._run_sums(jnp.asarray(run_start), jnp.asarray(x)))
    assert (got == _runs_by_numpy(run_start, x)).all()
    counts = np.asarray(seg._run_counts(jnp.asarray(run_start), jnp.asarray(m)))
    assert (counts == _runs_by_numpy(run_start, m.astype(np.int64))).all()
    rows = np.asarray(seg._run_counts(jnp.asarray(run_start), None))
    assert (rows == _runs_by_numpy(run_start, np.ones(n, np.int64))).all()


RUN_QUERIES = [
    "select l_orderkey, sum(l_quantity), count(*), avg(l_extendedprice), "
    "count(l_comment) from lineitem group by l_orderkey",
    "select l_orderkey, sum(l_extendedprice * (1 - l_discount)), "
    "sum(l_linenumber - 4) from lineitem where l_shipdate > date '1995-06-17' "
    "group by l_orderkey",
    "select o_orderkey, sum(o_totalprice), avg(o_shippriority) from orders "
    "group by o_orderkey having sum(o_totalprice) > 300000",
    "select k, sum(v), count(v), avg(v) from memory.t.runs group by k",
]


@pytest.mark.parametrize("sql", RUN_QUERIES)
def test_the_run_layout_answers_as_the_sorted_layout_does(sql, monkeypatch):
    """A presorted key groups by scans alone (``seg.run_layout``); switched
    off, the same statement lists its groups and gathers (the sorted
    layout): the same rows."""
    from trino_tpu.exec.executor import Executor
    from trino_tpu.ops import segments as seg

    s = _tiny()
    # NULLs, negatives, values past 32 bits, an ascending null-free key
    s.catalogs["memory"].create_table(
        "t", "runs", [("k", T.BIGINT), ("v", T.BIGINT)],
        [(i // 3, None if i % 5 == 0 else (i - 40) * 2**33)
         for i in range(90)])
    made = []
    real = seg.run_layout
    monkeypatch.setattr(seg, "run_layout",
                        lambda rs: made.append(1) or real(rs))
    scans_alone = sorted(s.execute(sql).rows)
    monkeypatch.setattr(Executor, "_scans_alone",
                        staticmethod(lambda node, page: False))
    before = len(made)
    listed = sorted(s.execute(sql).rows)
    assert scans_alone == listed and len(listed) > 20
    assert len(made) == before          # the second run built none
    # the generator declares its key sorted; a filtered page's dead rows
    # are no tail, so it groups by the general path either way
    if "memory" not in sql and "where" not in sql:
        assert before >= 1


def test_min_max_and_distinct_keep_the_sorted_layout():
    from trino_tpu.exec.executor import Executor

    s = _tiny()
    for sql, want in (
            ("select l_orderkey, min(l_quantity) from lineitem "
             "group by l_orderkey", False),
            ("select l_orderkey, count(distinct l_suppkey) from lineitem "
             "group by l_orderkey", False),
            ("select l_orderkey, sum(l_quantity) from lineitem "
             "group by l_orderkey", True)):
        agg = _nodes(plan_sql(s, sql), P.AggregationNode)[0]
        page = Executor(s).execute(agg.source)
        assert Executor._scans_alone(agg, page) is want, sql


# ------------- a large vocabulary crosses an exchange once, not once a chunk
def _names_page(n, nulls=False):
    from trino_tpu.data.page import Page

    names = [None if nulls and i % 7 == 3 else f"Customer#{i:09d}"
             for i in range(n)]
    return Page.from_pydict({"k": T.BIGINT, "name": T.VARCHAR},
                            {"k": list(range(n)), "name": names})


@pytest.mark.parametrize("nulls", [False, True])
@pytest.mark.parametrize("chunk", [1000, 4097, 6000])
def test_a_chunk_ships_the_vocabulary_it_references(chunk, nulls):
    """The chunks of one output page share its dictionary; each is
    serialized with the entries its rows reference, decodes to the same
    strings, and the consumer's concatenation is the page again."""
    from trino_tpu.data import serde
    from trino_tpu.data.page import Page

    n = 20000
    page = _names_page(n, nulls)
    want = page.to_pylist()
    whole = serde.serialize_page(page)
    chunks = [page.slice_rows(lo, min(n, lo + chunk))
              for lo in range(0, n, chunk)]
    frames = [serde.serialize_page(c) for c in chunks]
    back = [serde.deserialize_page(f) for f in frames]
    assert [r for p in back for r in p.to_pylist()] == want
    pruned = chunk < n - serde.VOCAB_PRUNE_MIN
    for c, p in zip(chunks, back):
        vocab = len(p.columns[1].dictionary)
        assert (vocab <= len(c.columns[0])) == pruned
    # what crosses the wire is about the page once, not once a chunk
    if pruned:
        assert sum(map(len, frames)) < 1.5 * len(whole)
    merged = Page.concat_all(back)
    assert merged.to_pylist() == want
    d = merged.columns[1].dictionary
    assert d.values == sorted(d.values) and len(set(d.values)) == len(d)


def test_a_pruned_vocabulary_of_non_ascii_names_round_trips():
    """``_referenced_vocabulary`` stays in front of the version 4 block: a
    chunk of a page with a larger vocabulary than ``VOCAB_PRUNE_MIN`` ships
    the entries it references, NULL codes as they are."""
    from trino_tpu.data import serde
    from trino_tpu.data.dictionary import Dictionary
    from trino_tpu.data.page import Column, Page

    n = serde.VOCAB_PRUNE_MIN + 2000
    vocab = sorted(f"Kundé#{i:06d}" if i % 3 else f"Customer#{i:06d}"
                   for i in range(n))
    codes = np.arange(n, dtype=np.int32)
    codes[::17] = -1
    page = Page([Column(T.VARCHAR, codes, codes < 0, Dictionary(vocab))])
    want = [(None,) if c < 0 else (vocab[c],) for c in codes.tolist()]
    chunk = page.slice_rows(100, 1100)
    back = serde.deserialize_page(
        serde.serialize_page(chunk, serde.CODEC_NONE))
    assert back.to_pylist() == want[100:1100]
    kept = back.columns[0].dictionary.values
    assert len(kept) == len({r for r in want[100:1100] if r[0] is not None})
    assert kept == sorted(kept)
    whole = serde.deserialize_page(serde.serialize_page(page))
    assert whole.to_pylist() == want and len(
        whole.columns[0].dictionary) == n        # n rows: nothing to prune


def test_customers_vocabulary_at_sf10_is_written_and_read_in_seconds():
    """1.5 M names, the vocabulary of Q18's customer page at SF 10, as one
    block: one join and one encode out, one decode and slices in. A string
    at a time it took 0.75 s to write and 0.5 s to read on this kind of
    host; the limit leaves room for a loaded one and still fails a
    quadratic writer."""
    import time

    from trino_tpu.data import serde
    from trino_tpu.data.dictionary import Dictionary
    from trino_tpu.data.page import Column, Page

    n = 1_500_000
    vocab = [f"Customer#{i:09d}" for i in range(n)]
    page = Page([Column(T.VARCHAR, np.arange(n, dtype=np.int32), None,
                        Dictionary(vocab))])
    t0 = time.perf_counter()
    frame = serde.serialize_page(page, serde.CODEC_NONE)
    t1 = time.perf_counter()
    back = serde.deserialize_page(frame)
    t2 = time.perf_counter()
    assert back.columns[0].dictionary.values == vocab
    assert np.array_equal(np.asarray(back.columns[0].values), np.arange(n))
    assert len(frame) < n * (4 + 4 + 18) + 200
    assert t1 - t0 < 5.0 and t2 - t1 < 5.0, (t1 - t0, t2 - t1)


def test_vocabularies_that_interleave_still_merge_by_value():
    from trino_tpu.data.page import Page, _consecutive_vocabularies

    a = Page.from_pydict({"s": T.VARCHAR}, {"s": ["b", "d", None, "b"]})
    b = Page.from_pydict({"s": T.VARCHAR}, {"s": ["a", "c", "d"]})
    c = Page.from_pydict({"s": T.VARCHAR}, {"s": ["x", "y"]})
    dicts = [p.columns[0].dictionary for p in (a, b, c)]
    assert _consecutive_vocabularies(dicts) is None
    assert _consecutive_vocabularies([dicts[1], dicts[2]]) == [0, 3]
    assert Page.concat_all([a, b, c]).to_pylist() == [
        ("b",), ("d",), (None,), ("b",), ("a",), ("c",), ("d",), ("x",), ("y",)]
    assert Page.concat_all([b, c]).to_pylist() == [
        ("a",), ("c",), ("d",), ("x",), ("y",)]
    # an all-NULL page: an empty vocabulary, any code under its null mask
    nothing = Page.from_pydict({"s": T.VARCHAR}, {"s": [None, None]})
    assert Page.concat_all([b, nothing, c]).to_pylist() == [
        ("a",), ("c",), ("d",), (None,), (None,), ("x",), ("y",)]
    assert Page.concat_all([nothing, c]).to_pylist() == [
        (None,), (None,), ("x",), ("y",)]


def test_a_dictionary_builds_its_lookup_when_first_asked():
    from trino_tpu.data.dictionary import Dictionary

    d = Dictionary(["a", "b", "c"])
    assert d._lookup is None and d.decode_one(1) == "b"
    assert d._lookup is None
    assert d.code_of("c") == 2 and d.code_of("zz") == -1
    assert d._lookup == {"a": 0, "b": 1, "c": 2}
    assert list(d.encode(["b", None])) == [1, -1]
