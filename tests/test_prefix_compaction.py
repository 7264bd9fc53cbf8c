"""Compaction without a sort (PR 31): ``ops/ranks.true_positions`` lists a
mask's set positions from prefix counts, ``Executor.compact_to`` gathers
the kept rows at them, and the kernel row counts it
(``prefixCompactions``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trino_tpu  # noqa: F401  (x64 before any array is made)
from tpch_sql import QUERIES
from trino_tpu import Session, types as T
from trino_tpu.data.page import Column, Page
from trino_tpu.exec.executor import Executor, QueryError
from trino_tpu.obs.devprofiler import charge_to, new_kernel_row
from trino_tpu.ops import ranks


def _mask(n: int, kind: str) -> np.ndarray:
    rng = np.random.default_rng(n)
    if kind == "empty":
        return np.zeros(n, bool)
    if kind == "all":
        return np.ones(n, bool)
    if kind == "last-block":  # set bits only in the last 1024-row block
        m = np.zeros(n, bool)
        m[max(0, n - 7):] = True
        return m
    if kind == "first-only":
        m = np.zeros(n, bool)
        m[0] = True
        return m
    return rng.random(n) < {"sparse": 0.027, "half": 0.5}[kind]


def _reference(mask: np.ndarray, size: int, fill: int) -> np.ndarray:
    pos = np.flatnonzero(mask)[:size]
    return np.concatenate(
        [pos, np.full(size - pos.shape[0], fill)]).astype(np.int32)


# n: one row, around a packed word (32), around a scan block (1024), and
# 2^k +- 1 past two scan levels; size: under the count, over it, >= n
_LENGTHS = (1, 2, 31, 32, 33, 1023, 1024, 1025, 4095, 4096, 4097,
            (1 << 15) - 1, 1 << 15, (1 << 15) + 1, (1 << 20) + 1)


@pytest.mark.parametrize("kind", ["empty", "all", "sparse", "half",
                                  "last-block", "first-only"])
@pytest.mark.parametrize("n", _LENGTHS)
def test_true_positions_equals_flatnonzero(n, kind):
    mask = _mask(n, kind)
    count = int(mask.sum())
    fill = n - 1
    for size in sorted({1, max(1, count // 2), max(1, count), count + 3,
                        n, n + 5}):
        got = ranks.true_positions(jnp.asarray(mask), size, fill)
        assert got.dtype == jnp.int32 and got.shape == (size,)
        np.testing.assert_array_equal(
            np.asarray(got), _reference(mask, size, fill),
            err_msg=f"n={n} kind={kind} size={size}")


def test_true_positions_of_no_rows():
    got = ranks.true_positions(jnp.zeros((0,), bool), 4, 0)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(4, np.int32))


def test_true_positions_traces_inside_a_program_without_a_sort():
    mask = _mask(5000, "half")

    def body(m):
        return ranks.true_positions(m, 100, 0) * 2

    np.testing.assert_array_equal(
        np.asarray(jax.jit(body)(jnp.asarray(mask))),
        2 * _reference(mask, 100, 0))
    text = jax.jit(body).lower(jnp.asarray(mask)).as_text()
    assert "stablehlo.sort" not in text and "_sort_pass" not in text
    # the sort it replaced does show there: the check can fail
    old = jax.jit(lambda m: ranks.argsort32(~m)[:100]).lower(
        jnp.asarray(mask)).as_text()
    assert "stablehlo.sort" in old


def _page(n: int, mask, *, nested: bool = False) -> Page:
    keys = jnp.arange(n, dtype=jnp.int64) * 3 + 7
    cols = [
        Column(T.BIGINT, keys, vrange=(7, 3 * n + 7), ascending=True),
        Column(T.DOUBLE, jnp.arange(n, dtype=jnp.float64) / 4,
               nulls=jnp.asarray(np.arange(n) % 5 == 0)),
        Column(T.INTEGER, jnp.arange(n, dtype=jnp.int32)[::-1]),
    ]
    if nested:
        child = Column(T.BIGINT, jnp.arange(n, dtype=jnp.int64))
        cols.append(Column(T.array_of(T.BIGINT),
                           jnp.ones((n,), jnp.int32), children=[child]))
    return Page(cols, None if mask is None else jnp.asarray(mask))


@pytest.mark.parametrize("n,kind,capacity", [
    (1025, "sparse", 64), (4097, "half", 4096), (5000, "last-block", 8),
    (5000, "empty", 16), (70_000, "sparse", 4096),
    (3000, "all", 2999), (4097, "half", 1024),  # over the capacity
])
def test_compact_to_keeps_the_live_rows_in_order(n, kind, capacity):
    mask = _mask(n, kind)
    page = _page(n, mask)
    ex = Executor(Session())
    row = new_kernel_row("7", "Compact", "eager")
    with charge_to(row):
        out = ex.compact_to(page, capacity, "cmp:7")
    count = int(mask.sum())
    if count > capacity:
        with pytest.raises(QueryError) as raised:
            ex.raise_errors()
        assert raised.value.code == "CAPACITY_EXCEEDED:cmp:7"
        return
    ex.raise_errors()
    assert out.num_rows == capacity and out.live_prefix
    sel = np.asarray(out.sel)
    np.testing.assert_array_equal(sel, np.arange(capacity) < count)
    for got, src in zip(out.columns, page.columns):
        np.testing.assert_array_equal(
            np.asarray(got.values)[sel], np.asarray(src.values)[mask])
        if src.nulls is not None:
            np.testing.assert_array_equal(
                np.asarray(got.nulls)[sel], np.asarray(src.nulls)[mask])
        assert got.type == src.type
        assert got.vrange == src.vrange and got.ascending == src.ascending
    assert row["prefixCompactions"] == 1


@pytest.mark.parametrize("case", ["no-mask", "capacity-covers", "nested"])
def test_compact_to_returns_the_page_it_cannot_help(case):
    n = 2048
    mask = None if case == "no-mask" else _mask(n, "half")
    page = _page(n, mask, nested=case == "nested")
    ex = Executor(Session())
    row = new_kernel_row("7", "Compact", "eager")
    with charge_to(row):
        out = ex.compact_to(page, n if case == "capacity-covers" else 64,
                            "cmp:7")
    assert out is page
    assert row["prefixCompactions"] == 0 and not ex.errors


def test_compact_to_lowers_without_a_sort():
    n, capacity = 5000, 512
    mask = _mask(n, "sparse")

    def body(sel, a, b):
        page = Page([Column(T.BIGINT, a), Column(T.BIGINT, b)], sel)
        out = Executor(Session()).compact_to(page, capacity, "cmp:1")
        return out.columns[0].values, out.columns[1].values, out.sel

    a = jnp.arange(n, dtype=jnp.int64)
    text = jax.jit(body).lower(jnp.asarray(mask), a, a + 1).as_text()
    assert "stablehlo.sort" not in text and "_sort_pass" not in text
    va, vb, sel = jax.jit(body)(jnp.asarray(mask), a, a + 1)
    want = np.flatnonzero(mask)
    np.testing.assert_array_equal(np.asarray(va)[np.asarray(sel)], want)
    np.testing.assert_array_equal(np.asarray(vb)[np.asarray(sel)], want + 1)


# ------------------------------------------------- the counter, end to end
Q3 = QUERIES[3]


def test_q3_counts_its_compactions_on_the_compact_rows(
        monkeypatch, compacting_plans):
    from trino_tpu.exec import query as query_module
    from trino_tpu.exec.query import run_query

    made = []

    class Recording(Executor):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(query_module, "Executor", Recording)
    got = run_query(Session(), Q3).rows
    rows = [r for ex in made for r in ex.kernel_stats.values()]
    counted = [r for r in rows if r["prefixCompactions"]]
    assert counted and {r["operator"] for r in counted} == {"Compact"}
    # the plan without a CompactNode returns the same rows
    from trino_tpu.sql.planner import optimizer

    monkeypatch.setattr(optimizer, "COMPACT_MIN_SLOTS", 1 << 30)
    assert len(got) == 10 and got == run_query(Session(), Q3).rows


def test_traced_tiers_count_no_compaction():
    ex = Executor(Session())
    ex.eager_tier = False
    row = new_kernel_row("7", "Compact", "eager")
    with charge_to(row):
        out = ex.compact_to(_page(2048, _mask(2048, "sparse")), 256, "cmp:7")
    assert out.num_rows == 256 and row["prefixCompactions"] == 0


def test_served_q3_folds_the_counter_into_profile_and_system_table(
        compacting_plans):
    import json
    import urllib.request

    from trino_tpu.client.remote import StatementClient
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    worker = WorkerServer(coordinator_url=coord.base_url, node_id="cmp-w0")
    worker.start()
    try:
        assert coord.registry.wait_for_workers(1, timeout=15.0)
        client = StatementClient(coord.base_url, {
            "catalog": "tpch", "schema": "tiny",
            "result_cache_enabled": "false"})
        _cols, rows = client.execute(Q3)
        assert len(rows) == 10
        req = urllib.request.Request(
            f"{coord.base_url}/v1/query/{client.query_id}/profile",
            headers={"X-Trino-User": "test"})
        kernels = json.loads(urllib.request.urlopen(req).read())["kernels"]
        assert all("prefixCompactions" in k for k in kernels)
        total = sum(k["prefixCompactions"] for k in kernels)
        assert total >= 1
        _cols, table = client.execute(
            "select sum(prefix_compactions) from system.runtime.kernels "
            f"where query_id = '{client.query_id}'")
        assert table == [[total]]
    finally:
        worker.stop()
        coord.stop()
