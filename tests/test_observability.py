"""EXPLAIN ANALYZE / operator stats / typed session properties.

Reference behaviors matched: PlanPrinter stats injection (§5.1),
SystemSessionProperties typed registry (§5.6), SET/RESET/SHOW SESSION.
"""
import pytest

from trino_tpu.client.session import Session


@pytest.fixture()
def session():
    return Session({"catalog": "tpch", "schema": "tiny"})


def test_explain_analyze_reports_stats(session):
    out = session.execute("""
        explain analyze
        select o_orderpriority, count(*) from orders
        where o_orderdate >= date '1995-01-01'
        group by o_orderpriority order by o_orderpriority
    """)
    text = "\n".join(r[0] for r in out.rows)
    assert "Query wall time:" in text
    assert "wall=" in text and "rows=" in text
    assert "scanned=" in text  # scan stats on the TableScan line
    assert "Aggregation" in text and "TableScan" in text


def test_explain_analyze_shows_spill_and_budget(session):
    session.set_property("query_max_device_memory", 100_000)
    out = session.execute("""
        explain analyze
        select c_custkey, count(o_orderkey) from customer, orders
        where c_custkey = o_custkey group by c_custkey
    """)
    text = "\n".join(r[0] for r in out.rows)
    assert "Device memory budget:" in text
    assert "spilled:" in text and "passes" in text


def test_explain_shows_constraint_and_dynamic_filters(session):
    out = session.execute("""
        explain (type logical)
        select count(*) from lineitem, orders
        where l_orderkey = o_orderkey and o_orderkey < 100
    """)
    text = "\n".join(r[0] for r in out.rows)
    assert "constraint=" in text
    assert "dynamic_filters=['l_orderkey']" in text


def test_set_show_reset_session(session):
    session.execute("set session dynamic_filtering_enabled = false")
    assert session.properties["dynamic_filtering_enabled"] is False
    rows = session.execute("show session").rows
    by_name = {r[0]: r for r in rows}
    assert by_name["dynamic_filtering_enabled"][1] == "False"
    assert "spill" in by_name["spill_enabled"][4]  # description populated
    session.execute("reset session dynamic_filtering_enabled")
    assert session.properties["dynamic_filtering_enabled"] is True


def test_unknown_property_rejected(session):
    with pytest.raises(ValueError, match="does not exist"):
        session.execute("set session no_such_knob = 1")
    with pytest.raises(ValueError, match="does not exist"):
        Session({"bogus_prop": 1})


def test_property_type_validation(session):
    with pytest.raises(ValueError, match="expected integer"):
        session.set_property("query_max_device_memory", "not-a-number")
    with pytest.raises(ValueError, match="positive"):
        session.set_property("join_max_broadcast_rows", 0)
    # string coercion (client protocol headers arrive as strings)
    session.set_property("query_max_device_memory", "1048576")
    assert session.properties["query_max_device_memory"] == 1048576


def test_dynamic_filtering_property_respected(session):
    from trino_tpu.exec.executor import Executor

    session.set_property("dynamic_filtering_enabled", False)
    ex = Executor(session)
    assert ex.enable_dynamic_filtering is False
    session.set_property("dynamic_filtering_enabled", True)
    assert Executor(session).enable_dynamic_filtering is True


def test_spill_works_with_dynamic_filtering_off(session):
    """Spill is a memory-tier decision, not a dynamic-filtering one: the
    budget must still partition when DF is disabled."""
    from trino_tpu.exec.executor import Executor
    from trino_tpu.exec.query import plan_sql

    session.set_property("dynamic_filtering_enabled", False)
    session.set_property("query_max_device_memory", 150_000)
    ex = Executor(session)
    root = plan_sql(session, "select l_orderkey, count(*) from lineitem group by l_orderkey")
    ex.execute_checked(root)
    assert any(s.kind == "aggregation" for s in ex.memory.spills)


def test_explain_analyze_live_row_counts(session):
    out = session.execute(
        "explain analyze select * from orders where o_orderkey = 7")
    text = "\n".join(r[0] for r in out.rows)
    # the filter's output is 1 live row, not the 15000 padded slots
    filter_line = next(l for l in text.split("\n") if "- Filter" in l)
    assert "rows=1]" in filter_line


def test_spill_disabled_runs_unpartitioned(session):
    session.set_property("query_max_device_memory", 50_000)
    session.set_property("spill_enabled", False)
    from trino_tpu.exec.executor import Executor
    from trino_tpu.exec.query import plan_sql

    ex = Executor(session)
    root = plan_sql(session, "select l_orderkey, count(*) from lineitem group by l_orderkey")
    ex.execute_checked(root)
    assert not ex.memory.spills
