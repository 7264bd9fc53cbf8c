"""Randomized join correctness: the fused sort-merge tier against host
ground truth (exec/host_eval.py), across inner/left/semi joins, NULL
keys, duplicate keys, empty builds, and the all-hot single-key skew
shape, on both the dense and fused cost-gate paths.

Shapes are FIXED across randomized trials (only content varies) so each
kernel compiles once and the suite stays tier-1-fast.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu import Session
from trino_tpu import types as T
from trino_tpu.data.page import Column, Page
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.host_eval import HostEvaluator, Unsupported
from trino_tpu.exec.query import plan_sql
from trino_tpu.ops import fused_join as FJ
from trino_tpu.ops import join as J
from trino_tpu.sql.planner import plan as P

N_BUILD, N_PROBE = 64, 96


# --------------------------------------------------------------- kernel unit
def _ref_lookup(bk, blive, pk, pvalid):
    """Numpy reference for the unique-key lookup: per probe row, the
    matching LIVE build row index or -1."""
    out = np.full(len(pk), -1, np.int64)
    table = {}
    for i, (k, lv) in enumerate(zip(bk, blive)):
        if lv:
            table[int(k)] = i
    for j, (k, v) in enumerate(zip(pk, pvalid)):
        if v and int(k) in table:
            out[j] = table[int(k)]
    return out


def _trial(rng, all_hot=False, empty_build=False, sparse=False):
    span = (1 << 40) if sparse else (N_BUILD * 2)
    bk = rng.choice(span, size=N_BUILD, replace=False).astype(np.int64)
    if all_hot:
        pk = np.full(N_PROBE, bk[0], np.int64)  # every probe hits one key
    else:
        pk = np.concatenate([
            rng.choice(bk, size=N_PROBE // 2),
            rng.integers(0, span, size=N_PROBE - N_PROBE // 2),
        ]).astype(np.int64)
    bnull = rng.random(N_BUILD) < 0.15
    pnull = rng.random(N_PROBE) < 0.15
    bsel = (np.zeros(N_BUILD, bool) if empty_build
            else rng.random(N_BUILD) < 0.8)
    return bk, pk, bnull, pnull, bsel


@pytest.mark.parametrize("shape", ["plain", "all_hot", "empty_build", "sparse"])
def test_fused_probe_unique_matches_reference(shape):
    rng = np.random.default_rng(42)
    for _ in range(4):
        bk, pk, bnull, pnull, bsel = _trial(
            rng, all_hot=shape == "all_hot",
            empty_build=shape == "empty_build", sparse=shape == "sparse")
        bkeys = [(jnp.asarray(bk), jnp.asarray(~bnull))]
        pkeys = [(jnp.asarray(pk), jnp.asarray(~pnull))]
        rows, matched = FJ.fused_probe_unique(bkeys, jnp.asarray(bsel), pkeys)
        rows, matched = np.asarray(rows), np.asarray(matched)
        ref = _ref_lookup(bk, bsel & ~bnull, pk, ~pnull)
        assert np.array_equal(matched, ref >= 0)
        assert np.array_equal(rows[matched], ref[matched])


def test_fused_membership_duplicates_and_nulls():
    rng = np.random.default_rng(7)
    for _ in range(4):
        bk = rng.integers(0, 16, N_BUILD).astype(np.int64)  # heavy dups
        pk = rng.integers(0, 24, N_PROBE).astype(np.int64)
        bnull = rng.random(N_BUILD) < 0.2
        bsel = rng.random(N_BUILD) < 0.7
        hit = FJ.fused_membership(
            [(jnp.asarray(bk), jnp.asarray(~bnull))], jnp.asarray(bsel),
            [(jnp.asarray(pk), None)])
        ref = np.isin(pk, bk[bsel & ~bnull])
        assert np.array_equal(np.asarray(hit), ref)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_merge_sorted_build_matches_reference(use_pallas):
    """The sorted-build merge tier (warm build-cache shape), XLA rank path
    and the Pallas tiled-merge kernel (interpret mode on CPU)."""
    rng = np.random.default_rng(9)
    for _ in range(3):
        span = N_BUILD * 4  # sentinel-safe: far below int32 max
        bk = rng.choice(span, size=N_BUILD, replace=False).astype(np.int64)
        pk = np.concatenate([
            rng.choice(bk, size=N_PROBE // 2),
            rng.integers(0, span, size=N_PROBE - N_PROBE // 2),
        ]).astype(np.int64)
        bsel = rng.random(N_BUILD) < 0.8
        dt = jnp.int32 if use_pallas else jnp.int64
        bkeys = [(jnp.asarray(bk).astype(dt), None)]
        pkeys = [(jnp.asarray(pk).astype(dt), None)]
        build = J.build_side(bkeys, jnp.asarray(bsel))
        rows, matched = FJ.merge_sorted_build(
            build, pkeys, use_pallas=use_pallas, pallas_block_build=256,
            pallas_interpret=True)
        ref = _ref_lookup(bk, bsel, pk, np.ones(N_PROBE, bool))
        assert np.array_equal(np.asarray(matched), ref >= 0)
        assert np.array_equal(np.asarray(rows)[ref >= 0], ref[ref >= 0])


# ---------------------------------------------------------- engine vs host
def _null_sortable(row):
    return tuple((x is None, 0 if x is None else x) for x in row)


def _page_rows(page: Page):
    """Live rows of an engine Page as comparable tuples (None = NULL)."""
    n = page.num_rows
    sel = (np.ones(n, bool) if page.sel is None
           else np.asarray(page.sel).astype(bool))
    cols = []
    for c in page.columns:
        vals = np.asarray(c.values)
        nulls = (np.zeros(n, bool) if c.nulls is None
                 else np.asarray(c.nulls).astype(bool))
        cols.append((vals, nulls))
    return sorted(
        (tuple(None if nl[i] else int(v[i]) for v, nl in cols)
         for i in range(n) if sel[i]),
        key=_null_sortable,
    )


def _hpage_rows(hpage):
    n = hpage.num_rows
    out = []
    for i in range(n):
        row = []
        for c in hpage.cols:
            null = c.nulls is not None and bool(c.nulls[i])
            row.append(None if null else int(np.asarray(c.values)[i]))
        out.append(tuple(row))
    return sorted(out, key=_null_sortable)


def _make_tables(session, rng, sparse=False, empty_build=False,
                 all_hot=False):
    mem = session.catalogs["memory"]
    span = (1 << 40) if sparse else N_BUILD
    bk = rng.choice(span, size=N_BUILD, replace=False)
    build_rows = [
        (None if rng.random() < 0.1 else int(k), int(rng.integers(0, 1000)))
        for k in bk
    ]
    if empty_build:
        build_rows = [(int(span + 10), 0)]  # one never-matching row
    probe_keys = (np.full(N_PROBE, bk[0]) if all_hot else np.concatenate([
        rng.choice(bk, size=N_PROBE // 2),
        rng.integers(0, span, size=N_PROBE - N_PROBE // 2),
    ]))
    probe_rows = [
        (None if rng.random() < 0.1 else int(k), int(rng.integers(0, 1000)))
        for k in probe_keys
    ]
    mem.create_table("t", "build", [("k", T.BIGINT), ("v", T.BIGINT)],
                     build_rows)
    mem.create_table("t", "probe", [("k", T.BIGINT), ("w", T.BIGINT)],
                     probe_rows)


_JOIN_SQL = {
    # M:N inner (expansion kernel; build dups from the generator)
    "inner": """select p.w, b.v from memory.t.probe p
                join memory.t.build b on p.k = b.k""",
    # N:1 lookup (group-by proves build uniqueness -> right_unique)
    "lookup": """select p.w, b.vv from memory.t.probe p join
                 (select k, max(v) vv from memory.t.build group by k) b
                 on p.k = b.k""",
    "left": """select p.w, b.vv from memory.t.probe p left join
               (select k, max(v) vv from memory.t.build group by k) b
               on p.k = b.k""",
    "semi": """select p.w from memory.t.probe p
               where p.k in (select k from memory.t.build)""",
}


@pytest.mark.parametrize("join", ["inner", "lookup", "left", "semi"])
@pytest.mark.parametrize("shape", ["dense", "sparse", "all_hot", "empty"])
def test_engine_join_matches_host_ground_truth(join, shape):
    """The whole dispatch (cost gate included: dense span on the 'dense'
    shape, fused tier on 'sparse') against HostEvaluator ground truth."""
    rng = np.random.default_rng(hash((join, shape)) % (1 << 31))
    session = Session()
    _make_tables(session, rng, sparse=shape == "sparse",
                 empty_build=shape == "empty", all_hot=shape == "all_hot")
    root = plan_sql(session, _JOIN_SQL[join])
    ex = Executor(session)
    page = ex.execute_checked(root)
    try:
        # OutputNode only renames; the evaluator covers its source
        host = HostEvaluator(session, {}).eval(root.source)
    except Unsupported as e:
        pytest.skip(f"host ground truth unavailable: {e}")
    assert _page_rows(page) == _hpage_rows(host)


def test_fused_off_matches_fused_on():
    """The legacy pipeline and the fused tier agree at the SQL level."""
    rng = np.random.default_rng(123)
    on = Session()
    _make_tables(on, rng, sparse=True)
    off = Session(properties={"fused_join_enabled": False})
    off.catalogs["memory"] = on.catalogs["memory"]  # same data
    sql = _JOIN_SQL["lookup"]
    p_on = Executor(on).execute_checked(plan_sql(on, sql))
    p_off = Executor(off).execute_checked(plan_sql(off, sql))
    assert _page_rows(p_on) == _page_rows(p_off)


@pytest.mark.parametrize("join", ["lookup", "semi"])
@pytest.mark.parametrize("keys,tier", [("dense", "dense"),
                                       ("sparse", "fused")])
def test_cost_gate_selects_the_tier_by_key_range(join, keys, tier):
    """``trino_tpu_fused_join_selections_total`` moves once, under
    ``dense`` for a build whose keys fill their range (the direct-address
    table) and under ``fused`` for keys spread over 2^40 (one combined
    sort): a drift in the selection is a silent loss of speed."""
    from trino_tpu.obs import metrics as M

    rng = np.random.default_rng(3)
    n_probe, n_build = 1 << 12, 1 << 10
    if keys == "dense":
        span = n_build - 1
        bkeys = np.arange(n_build, dtype=np.int64)
    else:
        span = 1 << 40  # far beyond the dense table's span limit
        bkeys = rng.choice(span, size=n_build, replace=False).astype(np.int64)
    pkeys = np.concatenate([
        rng.choice(bkeys, size=n_probe // 2),
        rng.integers(0, span, size=n_probe - n_probe // 2),
    ]).astype(np.int64)
    build = Page([Column(T.BIGINT, jnp.asarray(bkeys), vrange=(0, span))])
    probe = Page([Column(T.BIGINT, jnp.asarray(pkeys), vrange=(0, span))])
    tiers = ("dense", "fused", "legacy", "merge-sorted", "merge-pallas")
    before = {t: M.FUSED_JOIN_SELECTIONS.value(t) for t in tiers}
    ex = Executor(Session())
    if join == "lookup":
        node = P.JoinNode(join_type="inner", left=None, right=None,
                          left_keys=[0], right_keys=[0], right_unique=True)
        out = ex.lookup_join(node, probe, build)
    else:
        node = P.JoinNode(join_type="semi", left=None, right=None,
                          left_keys=[0], right_keys=[0])
        out = ex.semi_join(node, probe, build)
    moved = {t: M.FUSED_JOIN_SELECTIONS.value(t) - before[t] for t in tiers}
    assert moved == {t: int(t == tier) for t in tiers}
    assert np.array_equal(np.asarray(out.sel), np.isin(pkeys, bkeys))


# ----------------------------------------------------- sorted-build cache
def test_device_build_cache_warm_join_skips_build_sort():
    """Second identical semi join against a bare versioned scan serves the
    SORTED build artifact from the device cache (build-hits metric moves);
    DML moves the data_version and the stale artifact is never served."""
    from trino_tpu.obs import metrics as M

    session = Session(properties={"device_cache_enabled": True})
    mem = session.catalogs["memory"]
    mem.create_table("t", "probe", [("k", T.BIGINT), ("w", T.BIGINT)],
                     [(i * 7 % 50, i) for i in range(60)])
    mem.create_table("t", "dim", [("k", T.BIGINT)],
                     [(i * 7 % 50 + (1 << 40) * (i % 2),) for i in range(20)])
    sql = ("select p.w from memory.t.probe p "
           "where p.k in (select k from memory.t.dim)")

    def run():
        root = plan_sql(session, sql)
        return _page_rows(Executor(session).execute_checked(root))

    h0 = M.DEVICE_CACHE_BUILD_HITS.value()
    first = run()
    assert M.DEVICE_CACHE_BUILD_HITS.value() == h0  # cold: a miss, admitted
    second = run()
    assert M.DEVICE_CACHE_BUILD_HITS.value() == h0 + 1  # warm: sort skipped
    assert first == second
    # DML invalidates: the new key must be visible (no stale artifact)
    session.execute("insert into memory.t.dim values (1)")
    third = run()
    assert M.DEVICE_CACHE_BUILD_HITS.value() == h0 + 1  # version moved: miss
    extra = [(w,) for (k, w) in
             [(i * 7 % 50, i) for i in range(60)] if k == 1]
    assert sorted(third) == sorted(second + extra)


def test_build_cache_disabled_without_property():
    """Without device_cache_enabled the build path never consults the
    pool (bypass, no loader run — the fully-fused path stays cheaper)."""
    from trino_tpu.obs import metrics as M

    session = Session()
    mem = session.catalogs["memory"]
    mem.create_table("t", "probe", [("k", T.BIGINT)], [(i,) for i in range(20)])
    mem.create_table("t", "dim", [("k", T.BIGINT)],
                     [(i + (1 << 40),) for i in range(10)])
    sql = ("select p.k from memory.t.probe p "
           "where p.k in (select k from memory.t.dim)")
    h0 = M.DEVICE_CACHE_BUILD_HITS.value()
    for _ in range(2):
        Executor(session).execute_checked(plan_sql(session, sql))
    assert M.DEVICE_CACHE_BUILD_HITS.value() == h0


# ------------------------------------------------------- reseed tile hints
def test_reseed_merge_tile_hint():
    """The Pallas merge-window hint prices from the staged key histograms:
    skewed (high-multiplicity) builds get wider windows, clamped to the
    kernel's VMEM budget."""
    from trino_tpu.adaptive import reseed as R

    def side(hashes, live=None):
        h = np.asarray(hashes, np.uint64)
        lv = np.ones(len(h), bool) if live is None else np.asarray(live)
        return R._SideKeys(hash=h, live=lv, sel=lv, n_rows=len(h))

    probe = side(np.arange(4096))
    uniform = side(np.arange(1024))
    assert R._merge_tile_hint(probe, uniform) == R._JTILE_MIN
    hot = side(np.zeros(1024))  # one key, multiplicity 1024
    assert R._merge_tile_hint(probe, hot) == R._JTILE_MAX
    empty = side(np.arange(8), live=np.zeros(8, bool))
    assert R._merge_tile_hint(probe, empty) == R._JTILE_MIN


def test_pallas_merge_null_slot_sentinel_edge():
    """A NULL probe slot whose RAW physical value equals INT32_MAX (the
    kernel pad sentinel) must neither match nor drag its block's covering
    window past the padded build buffer (the vrange proof only bounds
    LIVE values; the caller masks null slots in-range and the kernel
    clamps its window count)."""
    bk = np.arange(0, 1000, 2, dtype=np.int64)
    pk = np.array([4, 8, 2**31 - 1, 10], np.int64)
    pvalid = np.array([True, True, False, True])
    build = J.build_side([(jnp.asarray(bk).astype(jnp.int32), None)], None)
    rows, matched = FJ.merge_sorted_build(
        build, [(jnp.asarray(pk).astype(jnp.int32), jnp.asarray(pvalid))],
        use_pallas=True, pallas_block_build=256, pallas_interpret=True)
    assert list(np.asarray(matched)) == [True, True, False, True]
    assert list(np.asarray(rows)[np.asarray(matched)]) == [2, 4, 5]


def test_fused_join_pallas_off_tpu_is_a_typed_error(monkeypatch):
    """``fused_join_pallas`` means the COMPILED kernel: a join that
    reaches it on a backend that cannot compile it (the CPU here) fails
    typed — it neither interprets nor quietly takes the XLA merge."""
    from trino_tpu.exec.executor import QueryError

    ex = Executor(Session(properties={"fused_join_pallas": True}))
    monkeypatch.setattr(ex, "_merge_sentinel_safe", lambda *a: True)
    with pytest.raises(QueryError) as err:
        ex._merge_sorted_tier(None, None, None, None, [], [])
    assert err.value.code == "PALLAS_MERGE_BACKEND"
