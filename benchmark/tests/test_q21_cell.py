"""``tpch_sf10_q21.q21``: TPC-H Q21 at SF 10 through the server, four tables
resident, its EXISTS and NOT EXISTS run as residual semi and anti joins.

The cell is data over code the benchmark had, and one new reference file: a
configuration, a template with one ``choice`` parameter, a traffic file,
three metric files, two for the ``kernel_counter`` reader and one for
``timeline_detail`` (``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import contextlib
import io
import json
import types

import pytest

from benchmark import spec

CELL = "tpch_sf10_q21.q21"
Q9 = "tpch_sf10_q9.q9"
COUNTERS = {
    # name -> kernel-row field
    "residual_pairs_per_stmt.analytic": "residualPairs",
    "join_build_sorts_per_stmt.analytic": "buildSorts",
}
SPAN_METRIC = "residual_join_ms.analytic"
NEW_METRICS = sorted(COUNTERS) + [SPAN_METRIC]
TINY_ROWS = {"lineitem": 59837, "orders": 15000, "supplier": 100,
             "nation": 25}
SEED = 3600000011   # over 2**31: a run's seed may exceed 32 bits


def test_the_cell_loads_and_states_its_deployment():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tpch_sf10_q21", "q21", 1)
    assert list(cell.templates) == ["q21"]
    t = cell.templates["q21"]
    assert (t.path, t.mode, t.reference, t.reference_module) == (
        "distributed", "literal", "q21", "tpch_q21")
    assert cell.session_properties() == {
        "catalog": "tpch", "schema": "sf10",
        "result_cache_enabled": "false", "device_cache_enabled": "true",
        "query_max_execution_time": "15m"}
    q9 = spec.load_cell(Q9)
    for key in ("catalog", "schema", "scale_factor", "layout", "chips"):
        assert cell.config[key] == q9.config[key], key
    # Q9's guarantees, the decimal one replaced by exact counts
    assert [g for g in cell.config["guarantees"] if "exact" not in g] == [
        g for g in q9.config["guarantees"] if "exact" not in g]
    assert any("count(*)" in g for g in cell.config["guarantees"])
    assert cell.config["row_counts"] == {
        "lineitem": 59994670, "orders": 15000000, "supplier": 100000,
        "nation": 25}
    assert t.scan_rows(cell.row_counts) == 75094695
    # the columns a statement must read once, at their logical widths
    assert t.scan_bytes(cell.row_counts, spec.type_bytes()) == 1621872380
    assert cell.config["reduced"] == ["layout", "queries", "scale"]
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    for key in ("clause_numbers", "nation_domain", "streams",
                "resident_set", "query_max_execution_time"):
        assert key in cell.config["assumed"], key
    assert len(cell.config["source"]) <= 200
    # lineitem's four narrowed columns and their mask, 62,914,560 slots,
    # fit under the shipped cap
    from trino_tpu.client.properties import SYSTEM_SESSION_PROPERTIES

    shipped = SYSTEM_SESSION_PROPERTIES["device_cache_max_bytes"].default
    assert 62914560 * (4 * 4 + 1) < shipped


def test_it_is_listed_where_it_must_be_and_reports_what_the_analytic_cells_do():
    bench = spec.load_benchmark_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch_sf10_q21", "q21", 1)
    assert len(entry["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == "tpch_sf10_q21"]
    assert config["file"] == "benchmark/configs/tpch_sf10_q21.json"
    assert config["source"] == spec.load_cell(CELL).config["source"]
    assert config["reduced"] == ["layout", "queries", "scale"]
    for m in bench["end_to_end"]:
        if m["name"] in ("geomean_ms", "rows_per_s"):
            assert CELL in m["workloads"]
    cell, q9 = spec.load_cell(CELL), spec.load_cell(Q9)
    names = lambda metrics: [m["name"] for m in metrics]  # noqa: E731
    assert names(cell.end_to_end) == ["geomean_ms", "rows_per_s", "setup_s"]
    assert names(cell.per_layer) == names(q9.per_layer)
    assert set(NEW_METRICS) <= set(names(cell.per_layer))
    assert "hbm_roofline_share.analytic" in names(cell.per_layer)


def test_the_window_sends_two_fixed_nations_whatever_the_seed():
    from trino_tpu.connector.tpch.generator import NATIONS

    cell = spec.load_cell(CELL)
    (param,) = cell.templates["q21"].params
    assert param["kind"] == "choice"
    assert param["values"] == [name for name, _region in NATIONS]
    plans = [spec.build_plan(cell, seed, 51.0) for seed in (1, SEED)]
    nations = [sorted(s.binding["nation"] for s in p.distinct) for p in plans]
    assert nations[0] == nations[1] and len(set(nations[0])) == 2
    for plan in plans:
        assert plan.kind == "closed" and len(plan.streams) == 1
        for s in plan.distinct:
            assert f"n_name = '{s.binding['nation']}'" in s.sql
            assert "{" not in s.sql and "not exists" in s.sql


# --------------------------------------- the three metric files, on their readers
def _run(profiles=None, records=()):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=list(records)),
        profiles=profiles or {})


def _row(operator, **fields):
    return dict({"operator": operator, "launches": 1, "residualPairs": 0,
                 "buildSorts": 0}, **fields)


# a Q21 as this change runs it (one statement) and one whose builds sort
IN_PLACE = [_row("TableScan"), _row("Join", buildSorts=0),
            _row("Join", residualPairs=4194304),
            _row("Join", residualPairs=4194304), _row("Aggregation")]
SORTED = [_row("Join", buildSorts=1), _row("Join", residualPairs=4194304),
          _row("Join", residualPairs=4194304, buildSorts=1)]
WANT = {"residual_pairs_per_stmt.analytic": (8388608.0, 8388608.0, 8388608.0),
        "join_build_sorts_per_stmt.analytic": (0.0, 2.0, 1.0)}


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_each_counter_file_drives_kernel_counter(name):
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(name)
    assert (body["reader"], body["field"]) == ("kernel_counter", COUNTERS[name])
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert entry["better"] == "lower" and entry["moves"] == "geomean_ms"
    assert entry["source"] == "program_counter" and "workloads" not in entry
    a, b, both = WANT[name]
    assert kernel_counter.read(body, _run({"a": IN_PLACE})) == a
    assert kernel_counter.read(body, _run({"b": SORTED})) == b
    assert kernel_counter.read(body, _run({"a": IN_PLACE, "b": SORTED})) == both


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_a_program_without_the_field_leaves_the_metric_out(name):
    """The parent's kernel rows lack both fields: nothing to read, no
    raise, and the line leaves the metric out."""
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(name)
    bare = {"a": [{"operator": "TableScan", "launches": 1},
                  {"operator": "Join", "launches": 1}]}
    assert kernel_counter.read(body, _run(bare)) is None
    assert kernel_counter.read(body, _run()) is None


def _record(detail):
    return types.SimpleNamespace(
        error=None, stats={"timeline": {"detail": detail}})


def test_the_span_file_drives_timeline_detail():
    from benchmark.readers import timeline_detail

    body = spec.load_layer_metric(SPAN_METRIC)
    assert (body["reader"], body["patterns"]) == (
        "timeline_detail", ["*/residual-join"])
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[SPAN_METRIC]
    assert (entry["unit"], entry["source"]) == ("ms", "program_span")
    assert "workloads" not in entry
    records = [_record({"device-execute/residual-join": 0.25,
                        "device-execute/op:Join": 0.5,
                        "device-execute/host-sync": 1.0}),
               _record({"exchange-wait/residual-join": 0.125})]
    assert timeline_detail.read(body, _run(records=records)) == 187.5
    # a program whose ledger has no second level: nothing to read
    bare = types.SimpleNamespace(error=None, stats={"timeline": {}})
    assert timeline_detail.read(body, _run(records=[bare])) is None


def test_the_layer_is_one_the_benchmark_already_names():
    bench = spec.load_benchmark_json()
    known = {m["layer"] for m in bench["per_layer"]
             if m["name"] not in NEW_METRICS}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["layer"] in known
            assert m["layer"].startswith("worker task and executor")


# --------------------------------------------------- the rehearsal, CPU, tiny
def _tiny_cell(workload, root=spec.ROOT, _load=spec.load_cell):
    cell = _load(workload, root)
    cell.config = dict(cell.config, schema="tiny", row_counts=TINY_ROWS)
    return cell


@pytest.mark.parametrize("trace", [0, 1])
def test_a_cpu_rehearsal_at_tiny_is_correct(monkeypatch, trace):
    from benchmark import run

    monkeypatch.setattr(spec, "load_cell", _tiny_cell)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(io.StringIO()):
        rc = run.run(["--workload", CELL, "--seed", str(SEED), "--seconds",
                      "3", "--trace", str(trace)],
                     require_chip=False, out=out, err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"], err.getvalue()[-2000:]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert all(v["value"] == 0 for v in result["compared"].values())
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    if not trace:
        assert set(metrics) == {"geomean_ms", "rows_per_s", "setup_s"}
        return
    assert metrics["staged_bytes_per_stmt.analytic"] == 0.0
    assert metrics["compiles_in_window.analytic"] == 0.0
    # two residual joins a statement, each a power of two of pairs (the two
    # nations' capacities differ, so the mean need not be whole); no build
    # of Q21's sorted
    assert metrics["residual_pairs_per_stmt.analytic"] >= 2 * 16
    assert metrics["join_build_sorts_per_stmt.analytic"] == 0.0
    assert metrics[SPAN_METRIC] >= 0.0
