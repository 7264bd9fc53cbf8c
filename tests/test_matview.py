"""Materialized views: lifecycle, transparent substitution, freshness.

Coverage map (ISSUE 15):

- parser round-trips for the three statements;
- CREATE-time validation (non-deterministic / unversioned / live-table
  definitions rejected, duplicate names, IF NOT EXISTS, OR REPLACE);
- the staleness matrix: INSERT/UPDATE/DELETE/DROP on any base table
  suppresses substitution (correct fallback rows), REFRESH resumes it;
- exact-subtree + select-item-prefix matching, name-based expansion,
  and the copy-on-write contract against the plan cache;
- per-user access control re-fired at substitution and REFRESH time;
- coordinator surfaces: queryStats.mvHits/mvNames, EXPLAIN ANALYZE
  headers + [mv: ...] scan annotations, result-cache coupling
  (REFRESH/base-DML both invalidate), device-cache warm-on-refresh,
  system.metadata.materialized_views;
- cross-process registry replication over the PR 12 executor plane;
- the q3 shape (three base tables, one view): the staleness matrix with
  each mutation on a different base table, and the warm storage hit.
"""
import pytest

import tests.conftest  # noqa: F401 — cpu mesh config

from trino_tpu.client.session import Session
from trino_tpu.sql.parser import ast
from trino_tpu.sql.parser.parser import ParseError, parse_statement


# ----------------------------------------------------------------- parser
def test_parse_create_refresh_drop():
    s = parse_statement(
        "create materialized view m.d.v1 as select 1 as x")
    assert isinstance(s, ast.CreateMaterializedView)
    assert s.name == ("m", "d", "v1") and not s.not_exists
    assert isinstance(s.query, ast.Query)
    s = parse_statement(
        "create or replace materialized view v1 as select 1 x")
    assert s.or_replace
    s = parse_statement(
        "create materialized view if not exists v1 as select 1 x")
    assert s.not_exists
    s = parse_statement("refresh materialized view memory.default.v1")
    assert isinstance(s, ast.RefreshMaterializedView)
    assert s.name == ("memory", "default", "v1")
    s = parse_statement("drop materialized view if exists v1")
    assert isinstance(s, ast.DropMaterializedView) and s.if_exists
    with pytest.raises(ParseError):
        parse_statement("create materialized view v1 (a bigint)")
    # soft keywords stay usable as identifiers
    assert isinstance(
        parse_statement("select materialized from t"), ast.Query)


# ---------------------------------------------------------- embedded base
def _mem_session(**props):
    s = Session({"catalog": "memory", "schema": "default", **props})
    s.execute("create table t (k bigint, v bigint)")
    s.execute("insert into t values (1, 10), (2, 20), (1, 30)")
    return s


MV_SQL = "create materialized view mv1 as select k, sum(v) as total from t group by k"
QUERY = "select k, sum(v) as total from t group by k"


def _hits(session) -> int:
    return sum(mv.hits for mv in session.matviews.snapshot())


def test_create_refresh_substitute_drop_roundtrip():
    s = _mem_session()
    s.execute(MV_SQL)
    mv = s.matviews.snapshot()[0]
    assert mv.qualified == "memory.default.mv1"
    assert mv.storage_qualified == "memory.default.mv1$storage"
    assert mv.base_versions is not None  # refresh-on-create ran
    h0 = _hits(s)
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0 + 1
    assert "[mv: memory.default.mv1]" in s.explain(QUERY)
    # name-based querying: the view expands, then substitutes
    assert sorted(s.execute("select * from mv1").rows) == [(1, 40), (2, 20)]
    s.execute("drop materialized view mv1")
    assert s.matviews.empty()
    assert s.catalogs["memory"].get_table("default", "mv1$storage") is None
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]


def test_create_validation():
    s = _mem_session()
    with pytest.raises(ValueError, match="not materializable"):
        s.execute("create materialized view bad as "
                  "select k, random() as r from t")
    with pytest.raises(ValueError, match="not materializable"):
        s.execute("create materialized view bad as "
                  "select query_id from system.runtime.queries")
    with pytest.raises(ValueError, match="uniquely named"):
        s.execute("create materialized view bad as select k, k from t")
    s.execute(MV_SQL)
    with pytest.raises(ValueError, match="already exists"):
        s.execute(MV_SQL)
    # IF NOT EXISTS: no-op; OR REPLACE: new definition takes over
    s.execute("create materialized view if not exists mv1 as "
              "select k from t group by k")
    assert len(s.matviews.snapshot()[0].column_names) == 2
    s.execute("create or replace materialized view mv1 as "
              "select v, count(*) as n from t group by v")
    assert s.matviews.snapshot()[0].column_names == ("v", "n")
    assert sorted(s.execute("select * from mv1").rows) == [
        (10, 1), (20, 1), (30, 1)]


def test_refresh_on_create_off():
    s = _mem_session(materialized_view_refresh_on_create=False)
    s.execute(MV_SQL)
    mv = s.matviews.snapshot()[0]
    assert mv.base_versions is None and mv.last_refresh is None
    h0 = _hits(s)
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0  # never-refreshed views cannot substitute
    s.execute("refresh materialized view mv1")
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0 + 1


def test_refresh_missing_view_errors():
    s = _mem_session()
    with pytest.raises(ValueError, match="not found"):
        s.execute("refresh materialized view nope")
    with pytest.raises(ValueError, match="not found"):
        s.execute("drop materialized view nope")
    s.execute("drop materialized view if exists nope")  # no-op


# ------------------------------------------------------- staleness matrix
def test_staleness_matrix():
    """INSERT/UPDATE/DELETE/DROP on the base table suppresses
    substitution with bit-identical fallback rows; REFRESH resumes."""
    s = _mem_session()
    s.execute(MV_SQL)

    def run(expect_substituted, expected_rows):
        h0 = _hits(s)
        rows = sorted(s.execute(QUERY).rows)
        assert rows == expected_rows
        assert (_hits(s) > h0) == expect_substituted

    run(True, [(1, 40), (2, 20)])
    mutations = [
        ("insert into t values (3, 5)", [(1, 40), (2, 20), (3, 5)]),
        ("update t set v = v + 1 where k = 3", [(1, 40), (2, 20), (3, 6)]),
        ("delete from t where k = 3", [(1, 40), (2, 20)]),
    ]
    for stmt, expected in mutations:
        s.execute(stmt)
        run(False, expected)
        s.execute("refresh materialized view mv1")
        run(True, expected)
    # DROP + recreate: the version counter survives the drop
    s.execute("drop table t")
    s.execute("create table t (k bigint, v bigint)")
    s.execute("insert into t values (7, 7)")
    run(False, [(7, 7)])
    s.execute("refresh materialized view mv1")
    run(True, [(7, 7)])


def test_out_of_band_storage_mutation_suppresses():
    """An edit (or drop) of the storage table itself moves its version
    off the recorded one: substitution must fall back."""
    s = _mem_session()
    s.execute(MV_SQL)
    s.catalogs["memory"].insert_rows("default", "mv1$storage", [(9, 9)])
    h0 = _hits(s)
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0
    s.catalogs["memory"].drop_table("default", "mv1$storage")
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0
    s.execute("refresh materialized view mv1")  # recreates storage
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0 + 1


def test_substitution_property_off():
    s = _mem_session(materialized_view_substitution=False)
    s.execute(MV_SQL)
    h0 = _hits(s)
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0
    # by-name still works (expansion is not substitution)
    assert sorted(s.execute("select * from mv1").rows) == [(1, 40), (2, 20)]


def test_transaction_never_substitutes():
    s = _mem_session()
    s.execute(MV_SQL)
    h0 = _hits(s)
    s.execute("start transaction")
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    s.execute("commit")
    assert _hits(s) == h0


# ------------------------------------------------------ matching variants
def test_prefix_and_filter_on_top_matching():
    s = _mem_session()
    s.execute(MV_SQL)
    mv = s.matviews.snapshot()[0]
    assert mv.prefix_canonicals, "prefix match keys not precomputed"
    h0 = _hits(s)
    # select-item prefix: only the first MV column
    assert sorted(s.execute("select k from t group by k").rows) == [
        (1,), (2,)]
    assert _hits(s) == h0 + 1
    plan = s.explain("select k from t group by k")  # EXPLAIN hits too
    assert "mv1$storage" in plan and "['k']" in plan
    # order/limit ON TOP of the matched subtree substitutes underneath
    h1 = _hits(s)
    assert s.execute(QUERY + " order by total desc limit 1").rows == [
        (1, 40)]
    assert _hits(s) == h1 + 1


def test_plan_cache_stays_substitution_free():
    """The coordinator applies substitution on a copy: a cached plan
    must serve BOTH a fresh (substituted) and a stale (fallback) run.
    Embedded proof: the same optimized plan object is reused via the
    session's plan path, and fallback after DML returns base rows."""
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.matview.substitute import substitute_plan
    from trino_tpu.sql.planner import plan as P

    s = _mem_session()
    s.execute(MV_SQL)
    root = plan_sql(s, QUERY)
    sub1, notes1 = substitute_plan(s, root)
    assert notes1[0]["result"] == "substituted"
    # the input tree was not mutated: no storage scan inside it
    assert all(not (isinstance(n, P.TableScanNode)
                    and n.mv_name is not None)
               for n in P.walk_plan(root))
    s.execute("insert into t values (9, 9)")
    sub2, notes2 = substitute_plan(s, root)
    assert sub2 is root and notes2[0]["result"] == "stale"


def test_mv_over_view_name_and_nested_definition():
    """A second MV defined OVER the first one's name: the definition
    expands the inner view, so the outer canonical matches queries that
    spell the whole computation out."""
    s = _mem_session()
    s.execute(MV_SQL)
    s.execute("create materialized view mv2 as "
              "select total, count(*) as n from mv1 group by total")
    assert sorted(s.execute(
        "select total, count(*) as n from mv1 group by total").rows) == [
        (20, 1), (40, 1)]


def test_mv_cycle_guard():
    """Mutually recursive registry entries (constructible only through
    the replication surface) fail loudly at expansion, never recurse."""
    from trino_tpu.matview.registry import MaterializedView

    s = _mem_session()

    def reg(name, sql):
        s.matviews.put(MaterializedView(
            catalog="memory", schema="default", name=name,
            definition_sql=sql, definition=parse_statement(sql),
            owner="t", default_catalog="memory",
            default_schema="default"))

    reg("cyca", "select * from cycb")
    reg("cycb", "select * from cyca")
    with pytest.raises(Exception, match="cycle"):
        s.execute("select * from cyca")


# --------------------------------------------------------- access control
def test_access_control_refires():
    from trino_tpu.server.security import (
        AccessDeniedError, Identity, RuleBasedAccessControl, TableRule)

    rules_all = RuleBasedAccessControl([
        TableRule(["alice"], privileges=("SELECT", "INSERT")),
        TableRule(["bob"], "memory", "default", "mv1$storage",
                  ("SELECT",)),
    ])
    alice = Session({"catalog": "memory", "schema": "default"},
                    identity=Identity("alice"), access_control=rules_all)
    alice.execute("create table t (k bigint, v bigint)")
    alice.execute("insert into t values (1, 10), (2, 20)")
    alice.execute(MV_SQL)
    h0 = _hits(alice)
    assert sorted(alice.execute(QUERY).rows) == [(1, 10), (2, 20)]
    assert _hits(alice) == h0 + 1
    # bob can reach the storage table but NOT the base table: his query
    # fails at plan time (the base scan is denied), and a REFRESH as bob
    # is denied too — the view launders nothing
    bob = Session({"catalog": "memory", "schema": "default"},
                  identity=Identity("bob"), access_control=rules_all,
                  catalogs=alice.catalogs, matviews=alice.matviews)
    with pytest.raises(AccessDeniedError):
        bob.execute(QUERY)
    with pytest.raises(AccessDeniedError):
        bob.execute("refresh materialized view mv1")


def test_substitution_access_check_unit():
    """The substitution-time re-check itself (plan-time AC is the outer
    guard): a registry entry whose base tables the principal cannot
    select reports access-denied and falls back."""
    from trino_tpu.matview.substitute import _access_denied_reason
    from trino_tpu.server.security import (
        Identity, RuleBasedAccessControl, TableRule)

    s = _mem_session()
    s.execute(MV_SQL)
    mv = s.matviews.snapshot()[0]
    s.access_control = RuleBasedAccessControl(
        [TableRule(["nobody"], privileges=("SELECT",))])
    s.identity = Identity("intruder")
    assert "access denied" in _access_denied_reason(s, mv)


# -------------------------------------------------- coordinator end-to-end
@pytest.fixture(scope="module")
def cluster():
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer

    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"mvw{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _client(coord, **props):
    from trino_tpu.client.remote import StatementClient

    return StatementClient(coord.base_url, {
        "catalog": "memory", "schema": "default", **props})


def test_coordinator_lifecycle_and_stats(cluster):
    coord, _ = cluster
    c = _client(coord)
    c.execute("create table ct (k bigint, v bigint)")
    c.execute("insert into ct values (1, 10), (2, 20)")
    c.execute("create materialized view cmv as "
              "select k, sum(v) as total from ct group by k")
    cols, rows = c.execute(
        "select k, total from cmv order by k")
    assert [tuple(r) for r in rows] == [(1, 10), (2, 20)]
    assert c.stats.get("mvHits") == 1
    assert c.stats.get("mvNames") == ["memory.default.cmv"]
    # the registry is server-wide: a SECOND client substitutes too
    c2 = _client(coord)
    cols, rows = c2.execute(
        "select k, sum(v) as total from ct group by k order by k")
    assert c2.stats.get("mvHits") == 1
    # system.metadata.materialized_views with LIVE freshness
    cols, rows = c.execute(
        "select catalog, schema_name, name, fresh, stale_reason, "
        "storage_table, hit_count from system.metadata.materialized_views")
    (row,) = [r for r in rows if r[2] == "cmv"]
    assert row[:4] == ["memory", "default", "cmv", True]
    assert row[5] == "memory.default.cmv$storage" and row[6] >= 2
    c.execute("insert into ct values (3, 3)")
    cols, rows = c.execute(
        "select fresh, stale_reason from system.metadata.materialized_views"
        " where name = 'cmv'")
    assert rows[0][0] is False and "moved" in rows[0][1]
    # stale => fallback with correct rows + mvHits 0
    cols, rows = c.execute(
        "select k, sum(v) as total from ct group by k order by k")
    assert [tuple(r) for r in rows] == [(1, 10), (2, 20), (3, 3)]
    assert c.stats.get("mvHits") == 0
    cols, rows = c.execute("refresh materialized view cmv")
    assert rows == [[3]]
    cols, rows = c.execute(
        "select k, sum(v) as total from ct group by k order by k")
    assert c.stats.get("mvHits") == 1
    c.execute("drop materialized view cmv")


def test_explain_analyze_annotations(cluster):
    coord, _ = cluster
    c = _client(coord)
    c.execute("create table et (k bigint, v bigint)")
    c.execute("insert into et values (1, 1)")
    c.execute("create materialized view emv as "
              "select k, sum(v) as s from et group by k")
    cols, rows = c.execute(
        "explain analyze select k, sum(v) as s from et group by k")
    text = "\n".join(r[0] for r in rows)
    assert "Materialized view memory.default.emv: substituted" in text
    assert "[mv: memory.default.emv]" in text
    c.execute("insert into et values (2, 2)")
    cols, rows = c.execute(
        "explain analyze select k, sum(v) as s from et group by k")
    text = "\n".join(r[0] for r in rows)
    assert "fallback (stale" in text and "[mv:" not in text
    c.execute("drop materialized view emv")


def test_result_cache_coupling(cluster):
    """Result-cache keys of substituted plans embed the storage version
    AND the base versions: REFRESH and base DML both flip HIT -> MISS."""
    coord, _ = cluster
    c = _client(coord, result_cache_enabled="true")
    c.execute("create table rt (k bigint, v bigint)")
    c.execute("insert into rt values (1, 5)")
    c.execute("create materialized view rmv as "
              "select k, sum(v) as total from rt group by k")
    sql = "select k, sum(v) as total from rt group by k order by k"
    cols, rows = c.execute(sql)
    assert c.cache_status == "MISS" and c.stats.get("mvHits") == 1
    cols, rows = c.execute(sql)
    assert c.cache_status == "HIT"
    # REFRESH moves the storage version -> the cached result dies
    c.execute("refresh materialized view rmv")
    cols, rows = c.execute(sql)
    assert c.cache_status == "MISS" and c.stats.get("mvHits") == 1
    assert c.execute(sql) and c.cache_status == "HIT"
    # base DML moves the base version -> stale fallback, fresh key
    c.execute("insert into rt values (2, 6)")
    cols, rows = c.execute(sql)
    assert c.cache_status == "MISS" and c.stats.get("mvHits") == 0
    assert [tuple(r) for r in rows] == [(1, 5), (2, 6)]
    c.execute("drop materialized view rmv")


def test_device_cache_warm_on_refresh(cluster):
    """REFRESH pre-stages the storage table: the first substituted query
    is a device-cache HIT with zero fresh staged rows."""
    from trino_tpu.devcache import DEVICE_CACHE

    coord, _ = cluster
    c = _client(coord, device_cache_enabled="true")
    c.execute("create table wt (k bigint, v bigint)")
    c.execute("insert into wt values (1, 2), (3, 4)")
    c.execute("create materialized view wmv as "
              "select k, sum(v) as total from wt group by k")
    entries = {e["table"]: e for e in DEVICE_CACHE.snapshot()}
    assert "wmv$storage" in entries, "refresh did not pre-stage storage"
    staged_hits = entries["wmv$storage"]["hits"]
    cols, rows = c.execute(
        "select k, sum(v) as total from wt group by k order by k")
    assert c.stats.get("mvHits") == 1
    assert c.stats.get("deviceCacheHits", 0) >= 1
    entries = {e["table"]: e for e in DEVICE_CACHE.snapshot()}
    assert entries["wmv$storage"]["hits"] == staged_hits + 1
    c.execute("drop materialized view wmv")


def test_prepared_execute_substitutes(cluster):
    coord, _ = cluster
    c = _client(coord)
    c.execute("create table pt (k bigint, v bigint)")
    c.execute("insert into pt values (1, 2), (1, 3), (2, 4)")
    c.execute("create materialized view pmv as "
              "select k, sum(v) as total from pt group by k")
    c.execute("PREPARE pq FROM select k, sum(v) as total from pt "
              "group by k order by k")
    cols, rows = c.execute("EXECUTE pq")
    assert [tuple(r) for r in rows] == [(1, 5), (2, 4)]
    assert c.stats.get("mvHits") == 1
    c.execute("drop materialized view pmv")
    c.execute("DEALLOCATE PREPARE pq")


def test_or_replace_if_not_exists_rejected():
    """The clause combination is ambiguous (which wins when the view
    exists?) — rejected loudly, like the reference engine."""
    s = _mem_session()
    with pytest.raises(ValueError, match="cannot combine"):
        s.execute("create or replace materialized view if not exists "
                  "mv1 as select k from t group by k")
    assert s.matviews.empty()


def test_unreadable_storage_falls_back():
    """A storage connector that RAISES on the freshness probe is treated
    as stale: the query falls back to the base plan instead of failing
    (same contract the base-table probes already honor)."""
    s = _mem_session()
    s.execute(MV_SQL)
    conn = s.catalogs["memory"]
    orig = conn.get_table

    def flaky(schema, table):
        if table.endswith("$storage"):
            raise RuntimeError("storage connector exploded")
        return orig(schema, table)

    conn.get_table = flaky
    try:
        h0 = _hits(s)
        assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
        assert _hits(s) == h0  # suppressed, not failed
    finally:
        conn.get_table = orig
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0 + 1  # probe healthy again -> substitution back


def test_prepared_mv_ddl_roundtrip(cluster):
    """MV DDL through PREPARE/EXECUTE takes the same path as the
    unprepared spelling: the view registers with its definition SQL
    (replication-capable), substitutes, refreshes, and drops."""
    coord, _ = cluster
    c = _client(coord)
    c.execute("create table pdt (k bigint, v bigint)")
    c.execute("insert into pdt values (1, 2), (1, 3), (2, 4)")
    c.execute("PREPARE pcm FROM create materialized view pmv2 as "
              "select k, sum(v) as total from pdt group by k")
    c.execute("EXECUTE pcm")
    mv = coord.matviews.get("memory", "default", "pmv2")
    assert mv is not None and mv.base_versions is not None
    assert mv.definition_sql  # replication ships definitions as SQL
    cols, rows = c.execute(
        "select k, sum(v) as total from pdt group by k order by k")
    assert [tuple(r) for r in rows] == [(1, 5), (2, 4)]
    assert c.stats.get("mvHits") == 1
    c.execute("insert into pdt values (3, 9)")
    c.execute("PREPARE prm FROM refresh materialized view pmv2")
    cols, rows = c.execute("EXECUTE prm")
    assert rows == [[3]]
    cols, rows = c.execute(
        "select k, sum(v) as total from pdt group by k order by k")
    assert c.stats.get("mvHits") == 1
    c.execute("PREPARE pdm FROM drop materialized view pmv2")
    c.execute("EXECUTE pdm")
    assert coord.matviews.get("memory", "default", "pmv2") is None
    for name in ("pcm", "prm", "pdm"):
        c.execute(f"DEALLOCATE PREPARE {name}")


def test_create_or_replace_failure_preserves_old_view():
    """A failed initial refresh must not destroy the replaced view: the
    old entry stays registered (and substitutable) and the statement
    errors loudly."""
    from trino_tpu.matview import lifecycle as L

    s = _mem_session()
    s.execute(MV_SQL)
    stmt = parse_statement(
        "create or replace materialized view mv1 as "
        "select v, count(*) as n from t group by v")

    def boom(_root):
        raise RuntimeError("refresh exploded")

    with pytest.raises(RuntimeError, match="refresh exploded"):
        L.create_materialized_view(s, stmt, execute_fn=boom)
    mv = s.matviews.get("memory", "default", "mv1")
    assert mv is not None and mv.column_names == ("k", "total")
    h0 = _hits(s)
    assert sorted(s.execute(QUERY).rows) == [(1, 40), (2, 20)]
    assert _hits(s) == h0 + 1  # old view still fresh and substituting


def test_fallback_storage_name_qualifies_catalog():
    """Views over unwritable catalogs store as <name>$<catalog>$storage
    in the fallback catalog, so same-named views of two catalogs never
    collide; same-catalog storage keeps the short name."""
    s = Session({"catalog": "tpch", "schema": "tiny"})
    s.execute("create materialized view nv as "
              "select n_regionkey, count(*) as n from nation "
              "group by n_regionkey")
    mv = s.matviews.snapshot()[0]
    assert mv.storage_catalog == "memory"
    assert mv.storage_table == "nv$tpch$storage"
    assert sorted(s.execute(
        "select n_regionkey, count(*) as n from nation "
        "group by n_regionkey").rows) == [(0, 5), (1, 5), (2, 5),
                                          (3, 5), (4, 5)]


def test_definition_sql_fallback_roundtrip():
    """Statements the prefix-stripping regex cannot take apart keep the
    FULL text, and from_payload unwraps the CREATE's query — replication
    never silently skips a legal statement."""
    from trino_tpu.matview import lifecycle as L
    from trino_tpu.matview.registry import (
        MaterializedView, from_payload, to_payload)

    sql = "-- nightly rollup\ncreate materialized view m as select 1 as x"
    text = L.definition_sql_of(sql)
    assert text == sql.strip()  # full statement kept
    mv = MaterializedView(
        catalog="memory", schema="default", name="m",
        definition_sql=text, definition=parse_statement(sql).query,
        owner="t")
    rt = from_payload(to_payload(mv))
    assert isinstance(rt.definition, ast.Query)
    assert L.definition_sql_of(
        "create materialized view m as select 1 as x") == "select 1 as x"


def test_sync_procedure_requires_internal_signature():
    """The replication procedure is NOT a user surface: an unsigned (or
    wrongly signed) CALL is denied, so clients cannot inject registry
    entries that would launder access control."""
    import base64
    import json

    from trino_tpu.server import wire
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.security import AccessDeniedError
    from trino_tpu.server.system_tables import CoordinatorSystemTables

    coord = CoordinatorServer.__new__(CoordinatorServer)  # no sockets
    from trino_tpu.matview.registry import MaterializedViewRegistry

    coord.matviews = MaterializedViewRegistry()
    provider = CoordinatorSystemTables(coord)
    proc = provider.procedure("runtime", "sync_materialized_view")
    blob = base64.b64encode(json.dumps(
        {"op": "drop", "catalog": "m", "schema": "d",
         "name": "x"}).encode()).decode()
    with pytest.raises(AccessDeniedError):
        proc(None, blob, None)
    with pytest.raises(AccessDeniedError):
        proc(None, blob, "deadbeef")
    assert "dropped" in proc(None, blob, wire.sign(blob.encode()))


# ------------------------------------------------- executor-process plane
@pytest.fixture(scope="module")
def proc_coord(tmp_path_factory):
    import os

    from trino_tpu.server.coordinator import CoordinatorServer

    fs_root = str(tmp_path_factory.mktemp("mvlake"))
    old = os.environ.get("TRINO_TPU_FS_ROOT")
    os.environ["TRINO_TPU_FS_ROOT"] = fs_root
    coord = CoordinatorServer(executor_plane="process",
                              executor_processes=1)
    coord.start()
    yield coord
    coord.stop()
    if old is None:
        os.environ.pop("TRINO_TPU_FS_ROOT", None)
    else:
        os.environ["TRINO_TPU_FS_ROOT"] = old


def _wait(q, timeout=180.0):
    q.state.wait_for_terminal(timeout)
    assert q.state.get() == "FINISHED", q.failure
    return q


def test_process_plane_registry_replication(proc_coord):
    """CREATE/REFRESH/DROP on the dispatch process replicate the registry
    to executor processes (sync_materialized_view payloads): a sticky-
    routed SELECT substitutes IN THE CHILD against shared filesystem
    storage, and a DROP stops it — rows stay correct throughout."""
    coord = proc_coord
    props = {"catalog": "tpch", "schema": "tiny",
             "short_query_fast_path": "true",
             "materialized_view_storage_catalog": "filesystem"}
    sql = ("select c_custkey, c_name from customer "
           "where c_mktsegment = 'BUILDING'")
    # boot + baseline: the broadcast only reaches booted children
    q = _wait(coord.submit(sql, props))
    assert q.plane.startswith("executor-process:")
    base_rows = [tuple(r) for r in q.rows]
    assert base_rows and q.mv_substitutions == []
    _wait(coord.submit(
        "create materialized view tpch.tiny.bld as " + sql, props))
    assert coord.matviews.get("tpch", "tiny", "bld") is not None
    q = _wait(coord.submit(sql, props))
    assert q.plane.startswith("executor-process:"), q.plane
    assert q.mv_substitutions == ["tpch.tiny.bld"]
    assert [tuple(r) for r in q.rows] == base_rows
    # DROP replicates: the child falls back to the base plan
    _wait(coord.submit("drop materialized view tpch.tiny.bld", props))
    q = _wait(coord.submit(sql, props))
    assert q.plane.startswith("executor-process:")
    assert q.mv_substitutions == []
    assert [tuple(r) for r in q.rows] == base_rows


# ------------------------- the q3 shape: three base tables, one view
Q3_AGG = """
select l_orderkey, o_orderdate, o_shippriority,
       sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
"""
Q3_TOP = Q3_AGG + " order by revenue desc, o_orderdate, l_orderkey limit 10"
# each mutation moves the version of a DIFFERENT base table of the join
Q3_MUTATIONS = {
    "insert": ["insert into orders select * from orders limit 1"],
    "update": ["update lineitem set l_quantity = l_quantity + 1 "
               "where l_orderkey = 1"],
    "delete": ["delete from customer where c_custkey = 1"],
    "drop": ["drop table customer",
             "create table customer as select * from tpch.tiny.customer"],
}


@pytest.fixture(scope="module")
def q3_matrix():
    """The staleness matrix over mutable copies of tpch.tiny: after each
    mutation, and again after the REFRESH that follows it, whether the
    query was substituted and whether its rows equal the base tables' own
    (substitution forced off). Records; the tests assert."""
    s = Session({"catalog": "memory", "schema": "default",
                 "device_cache_enabled": True})
    for t in ("customer", "orders", "lineitem"):
        s.execute(f"create table {t} as select * from tpch.tiny.{t}")
    s.execute("create materialized view q3m as " + Q3_AGG)

    def observe():
        before = _hits(s)
        rows = s.execute(Q3_TOP).rows
        substituted = _hits(s) > before
        s.properties["materialized_view_substitution"] = False
        try:
            truth = s.execute(Q3_TOP).rows
        finally:
            s.properties["materialized_view_substitution"] = True
        return {"substituted": substituted, "identical": rows == truth,
                "rows": len(rows)}

    steps = {"fresh": observe()}
    for name, statements in Q3_MUTATIONS.items():
        for sql in statements:
            s.execute(sql)
        steps[f"{name}-stale"] = observe()
        s.execute("refresh materialized view q3m")
        steps[f"{name}-refreshed"] = observe()
    s.execute("drop materialized view q3m")
    return steps


def test_q3_matrix_never_substitutes_a_stale_view(q3_matrix):
    """``incorrect_freshness_substitutions == 0``: no step whose view was
    stale was answered from it, and the fresh view was."""
    stale = [k for k in q3_matrix if k.endswith("-stale")]
    assert len(stale) == len(Q3_MUTATIONS)
    assert [k for k in stale if q3_matrix[k]["substituted"]] == []
    assert q3_matrix["fresh"]["substituted"]
    assert q3_matrix["fresh"]["rows"] == 10


@pytest.mark.parametrize("mutation", list(Q3_MUTATIONS))
def test_q3_stale_view_falls_back_and_refresh_resumes(q3_matrix, mutation):
    stale = q3_matrix[f"{mutation}-stale"]
    refreshed = q3_matrix[f"{mutation}-refreshed"]
    assert stale["identical"] and not stale["substituted"]
    assert refreshed["identical"] and refreshed["substituted"]


def test_q3_fresh_view_is_a_warm_storage_hit():
    """On the immutable tpch catalog the view stays fresh: the first
    substituted q3 returns the base query's rows and is served from the
    storage table the REFRESH staged into HBM (its device-cache entry
    counts a hit)."""
    from trino_tpu.devcache import DEVICE_CACHE

    s = Session({"catalog": "tpch", "schema": "tiny",
                 "device_cache_enabled": True})
    base_rows = s.execute(Q3_TOP).rows
    s.execute("create materialized view q3rev as " + Q3_AGG)
    try:
        storage = s.matviews.snapshot()[0].storage_table
        hits = _hits(s)
        assert s.execute(Q3_TOP).rows == base_rows
        assert _hits(s) > hits, "fresh MV did not substitute"
        (entry,) = [e for e in DEVICE_CACHE.snapshot()
                    if e["table"] == storage]
        assert entry["hits"] >= 1
    finally:
        s.execute("drop materialized view q3rev")
