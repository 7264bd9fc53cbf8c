"""``tpch_sf10_q9.q9``: TPC-H Q9 at SF 10 through the server, six tables
resident, the join order read from the filters.

The cell is data over code the benchmark had, and one new reference file: a
configuration, a template with one ``choice`` parameter, a traffic file, two
metric files for the ``kernel_counter`` reader
(``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import contextlib
import io
import json
import types

import pytest

from benchmark import spec

CELL = "tpch_sf10_q9.q9"
Q18 = "tpch_sf10_q18.q18"
NEW_METRICS = {
    # name -> kernel-row field
    "join_probe_slots_per_stmt.analytic": "joinProbeSlots",
    "join_build_slots_per_stmt.analytic": "joinBuildSlots",
}
TINY_ROWS = {"lineitem": 59837, "orders": 15000, "partsupp": 8000,
             "part": 2000, "supplier": 100, "nation": 25}
SEED = 3600000007   # over 2**31: the driver's seeds are large


def test_the_cell_loads_and_states_its_deployment():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tpch_sf10_q9", "q9", 1)
    assert list(cell.templates) == ["q9"]
    t = cell.templates["q9"]
    assert (t.path, t.reference, t.reference_module) == (
        "distributed", "q9", "tpch_q9")
    assert cell.session_properties() == {
        "catalog": "tpch", "schema": "sf10",
        "result_cache_enabled": "false", "device_cache_enabled": "true",
        "device_cache_max_bytes": "2147483648",
        "query_max_execution_time": "15m"}
    q18 = spec.load_cell(Q18)
    for key in ("catalog", "schema", "scale_factor", "layout", "chips",
                "guarantees"):
        assert cell.config[key] == q18.config[key], key
    assert cell.config["row_counts"] == {
        "lineitem": 59994670, "orders": 15000000, "partsupp": 8000000,
        "part": 2000000, "supplier": 100000, "nation": 25}
    assert t.scan_rows(cell.row_counts) == 85094695
    # the columns a statement must read once: 3.28 GB, 4 ms of the chip's
    # HBM bandwidth, so the roofline share stays far under 100%
    assert t.scan_bytes(cell.row_counts, spec.type_bytes()) == 3277344460
    assert cell.config["reduced"] == ["layout", "queries", "scale"]
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    for key in ("clause_numbers", "color_domain", "p_name", "streams",
                "resident_set", "query_max_execution_time"):
        assert key in cell.config["assumed"], key
    assert len(cell.config["source"]) <= 200
    # lineitem's six columns are over the shipped cap and under this one
    from trino_tpu.client.properties import SYSTEM_SESSION_PROPERTIES

    shipped = SYSTEM_SESSION_PROPERTIES["device_cache_max_bytes"].default
    assert shipped < 62914560 * 25 < int(
        cell.session_properties()["device_cache_max_bytes"])


def test_it_is_listed_where_it_must_be_and_reports_what_the_analytic_cells_do():
    bench = spec.load_benchmark_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch_sf10_q9", "q9", 1)
    assert len(entry["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == "tpch_sf10_q9"]
    assert config["file"] == "benchmark/configs/tpch_sf10_q9.json"
    assert config["source"] == spec.load_cell(CELL).config["source"]
    assert config["reduced"] == ["layout", "queries", "scale"]
    for m in bench["end_to_end"]:
        if m["name"] in ("geomean_ms", "rows_per_s"):
            assert CELL in m["workloads"]
    cell, q18 = spec.load_cell(CELL), spec.load_cell(Q18)
    names = lambda metrics: [m["name"] for m in metrics]  # noqa: E731
    assert names(cell.end_to_end) == ["geomean_ms", "rows_per_s", "setup_s"]
    assert names(cell.per_layer) == names(q18.per_layer)
    assert set(NEW_METRICS) <= set(names(cell.per_layer))
    assert "hbm_roofline_share.analytic" in names(cell.per_layer)


def test_the_window_sends_two_fixed_colours_whatever_the_seed():
    from trino_tpu.connector.tpch.generator import PART_COLORS

    cell = spec.load_cell(CELL)
    (param,) = cell.templates["q9"].params
    assert param["kind"] == "choice" and param["values"] == list(PART_COLORS)
    plans = [spec.build_plan(cell, seed, 51.0) for seed in (1, SEED)]
    colours = [sorted(s.binding["color"] for s in p.distinct) for p in plans]
    assert colours[0] == colours[1] and len(set(colours[0])) == 2
    for plan in plans:
        assert plan.kind == "closed" and len(plan.streams) == 1
        for s in plan.distinct:
            assert f"p_name like '%{s.binding['color']}%'" in s.sql
            assert "{" not in s.sql


# --------------------------------------- the two metric files, on their reader
def _run(profiles=None):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=[]), profiles=profiles or {})


def _row(operator, **fields):
    return dict({"operator": operator, "launches": 1, "joinProbeSlots": 0,
                 "joinBuildSlots": 0}, **fields)


# a q9 as this PR plans it (one statement) and as the parent planned it
ORDERED = [_row("TableScan"), _row("Join", joinProbeSlots=62914560, joinBuildSlots=65536),
           _row("Join", joinProbeSlots=2097152, joinBuildSlots=131072),
           _row("Join", joinProbeSlots=2097152, joinBuildSlots=25),
           _row("Aggregation")]
UNORDERED = [_row("Join", joinProbeSlots=62914560, joinBuildSlots=8388608),
             _row("Join", joinProbeSlots=62914560, joinBuildSlots=131072),
             _row("Join", joinProbeSlots=62914560, joinBuildSlots=15728640)]
WANT = {"join_probe_slots_per_stmt.analytic":
        (67108864.0, 188743680.0, 127926272.0),
        "join_build_slots_per_stmt.analytic":
        (196633.0, 24248320.0, 12222476.5)}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_file_drives_kernel_counter(name):
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(name)
    assert (body["reader"], body["field"]) == (
        "kernel_counter", NEW_METRICS[name])
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert entry["better"] == "lower" and entry["moves"] == "geomean_ms"
    assert entry["source"] == "program_counter" and "workloads" not in entry
    assert entry["layer"].startswith("SQL front end")
    ordered, unordered, both = WANT[name]
    assert kernel_counter.read(body, _run({"a": ORDERED})) == ordered
    assert kernel_counter.read(body, _run({"b": UNORDERED})) == unordered
    assert kernel_counter.read(
        body, _run({"a": ORDERED, "b": UNORDERED})) == both


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_program_without_the_field_leaves_the_metric_out(name):
    """The parent's kernel rows lack both fields: nothing to read, no
    raise, and the line leaves the metric out."""
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(name)
    bare = {"a": [{"operator": "TableScan", "launches": 1},
                  {"operator": "Join", "launches": 1}]}
    assert kernel_counter.read(body, _run(bare)) is None
    assert kernel_counter.read(body, _run()) is None


def test_the_layer_is_one_the_benchmark_already_names():
    bench = spec.load_benchmark_json()
    known = {m["layer"] for m in bench["per_layer"]
             if m["name"] not in NEW_METRICS}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["layer"] in known


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
    # tiny: six scans (partsupp fits under the broadcast limit whole), all
    # served from the cache; five joins, the first over lineitem's page
    assert metrics["device_cache_hits_per_stmt.analytic"] == 6.0
    assert metrics["staged_bytes_per_stmt.analytic"] == 0.0
    assert metrics["compiles_in_window.analytic"] == 0.0
    assert metrics["colocated_aggs_per_stmt.analytic"] == 0.0
    # the dimensions' live rows and the groups: no lineitem or orders row
    assert metrics["exchanged_rows_per_stmt.analytic"] < 8000 + 2000 + 700
    assert 59837 <= metrics["join_probe_slots_per_stmt.analytic"] <= 5 * 65536
    assert metrics["join_build_slots_per_stmt.analytic"] >= 15000 + 8000 + 125
