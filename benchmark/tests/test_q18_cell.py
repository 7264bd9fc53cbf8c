"""``tpch_sf10_q18.q18``: TPC-H Q18 at SF 10 through the server, tables
resident, every statement bounded by ``query_max_execution_time``.

The cell is data over code the benchmark had: a configuration file, a
traffic file, three metric files for the ``kernel_counter`` reader, the
template, substitution domain and reference that were already in the tree
(``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import contextlib
import io
import json
import types

import pytest

from benchmark import spec

CELL = "tpch_sf10_q18.q18"
RESIDENT = "tpch_sf10_resident.q3"
NEW_METRICS = {
    # name -> (kernel-row field, better, layer's first words)
    "colocated_aggs_per_stmt.analytic": ("colocatedAggs", "higher", "SQL front end"),
    "exchanged_rows_per_stmt.analytic": ("exchangedRows", "lower", "exchange"),
    "agg_eager_per_stmt.analytic": ("aggEager", "lower", "worker task and executor"),
}
TINY_ROWS = {"lineitem": 59837, "orders": 15000, "customer": 1500}
SEED = 3400000007   # over 2**31: the driver's seeds are large


def test_the_cell_loads_and_states_its_deployment():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tpch_sf10_q18", "q18", 1)
    assert list(cell.templates) == ["q18"]
    assert cell.templates["q18"].path == "distributed"
    props = cell.session_properties()
    assert props == {"catalog": "tpch", "schema": "sf10",
                     "result_cache_enabled": "false",
                     "device_cache_enabled": "true",
                     "query_max_execution_time": "15m"}
    resident = spec.load_cell(RESIDENT)
    for key in ("catalog", "schema", "scale_factor", "row_counts", "layout",
                "chips"):
        assert cell.config[key] == resident.config[key], key
    # the resident deployment's guarantees, and the time limit's
    assert cell.config["guarantees"][:5] == resident.config["guarantees"]
    assert "15 minutes" in cell.config["guarantees"][5]
    assert "keeps serving" in cell.config["guarantees"][5]
    assert cell.config["reduced"] == ["layout", "queries", "scale"]
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    assert "query_max_execution_time" in cell.config["assumed"]
    assert len(cell.config["source"]) <= 200
    from trino_tpu.client.properties import parse_duration

    assert parse_duration(props["query_max_execution_time"]) == 900.0


def test_it_is_listed_where_it_must_be_and_reports_what_the_analytic_cells_do():
    bench = spec.load_benchmark_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch_sf10_q18", "q18", 1)
    assert len(entry["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == "tpch_sf10_q18"]
    assert config["file"] == "benchmark/configs/tpch_sf10_q18.json"
    assert config["source"] == spec.load_cell(CELL).config["source"]
    assert config["reduced"] == ["layout", "queries", "scale"]
    for m in bench["end_to_end"]:
        if m["name"] in ("geomean_ms", "rows_per_s"):
            assert CELL in m["workloads"]
    cell, resident = spec.load_cell(CELL), spec.load_cell(RESIDENT)
    names = lambda metrics: [m["name"] for m in metrics]  # noqa: E731
    assert names(cell.end_to_end) == ["geomean_ms", "rows_per_s", "setup_s"]
    assert names(cell.per_layer) == names(resident.per_layer)
    assert set(NEW_METRICS) <= set(names(cell.per_layer))
    assert "hbm_roofline_share.analytic" in names(cell.per_layer)


def test_the_window_sends_two_fixed_bindings_whatever_the_seed():
    cell = spec.load_cell(CELL)
    plans = [spec.build_plan(cell, seed, 51.0) for seed in (1, SEED)]
    for plan in plans:
        assert plan.kind == "closed" and len(plan.streams) == 1
        assert sorted(s.binding["quantity"] for s in plan.distinct) == [314, 315]
        assert all("sum(l_quantity) > 31" in s.sql for s in plan.distinct)
    assert cell.templates["q18"].scan_rows(cell.row_counts) == 76494670


# ------------------------------------- the three metric files, on their reader
def _run(profiles=None):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=[]), profiles=profiles or {})


def _row(operator, **fields):
    return dict({"operator": operator, "launches": 1, "colocatedAggs": 0,
                 "exchangedRows": 0, "aggEager": 0}, **fields)


# a q18 as this PR plans it (one statement) and a q3 (another)
Q18_ROWS = [_row("TableScan"), _row("Aggregation", colocatedAggs=1, aggEager=1),
            _row("Project", exchangedRows=4200),
            _row("TableScan", exchangedRows=1500000),
            _row("Join", exchangedRows=4200), _row("Aggregation", aggEager=1)]
Q3_ROWS = [_row("Project", exchangedRows=1600000),
           _row("TableScan", exchangedRows=300000),
           _row("Join", exchangedRows=1600000),
           _row("Aggregation", aggEager=1)]
WANT = {"colocated_aggs_per_stmt.analytic": (1.0, 0.0, 0.5),
        "exchanged_rows_per_stmt.analytic": (1508400.0, 3500000.0, 2504200.0),
        "agg_eager_per_stmt.analytic": (2.0, 1.0, 1.5)}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_file_drives_kernel_counter(name):
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(name)
    field, better, layer = NEW_METRICS[name]
    assert (body["reader"], body["field"]) == ("kernel_counter", field)
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert entry["better"] == better and entry["moves"] == "geomean_ms"
    assert entry["source"] == "program_counter" and "workloads" not in entry
    assert entry["layer"].startswith(layer)
    q18, q3, both = WANT[name]
    assert kernel_counter.read(body, _run({"a": Q18_ROWS})) == pytest.approx(q18)
    assert kernel_counter.read(body, _run({"b": Q3_ROWS})) == pytest.approx(q3)
    assert kernel_counter.read(
        body, _run({"a": Q18_ROWS, "b": Q3_ROWS})) == pytest.approx(both)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_program_without_the_field_leaves_the_metric_out(name):
    """The parent's kernel rows lack ``colocatedAggs`` and ``exchangedRows``
    (it has ``aggEager`` since PR 28): nothing to read, no raise, and the
    line leaves the metric out."""
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(name)
    bare = {"a": [{"operator": "TableScan", "launches": 1},
                  {"operator": "Aggregation", "launches": 1}]}
    assert kernel_counter.read(body, _run(bare)) is None
    assert kernel_counter.read(body, _run()) is None


def test_the_layers_are_ones_the_benchmark_already_names():
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
    assert metrics["colocated_aggs_per_stmt.analytic"] == 1.0
    assert metrics["device_cache_hits_per_stmt.analytic"] == 4.0
    assert metrics["staged_bytes_per_stmt.analytic"] == 0.0
    assert metrics["compiles_in_window.analytic"] == 0.0
    # tiny's customer table, whole, and nothing else: no order of tiny
    # reaches QUANTITY 314, so the filtered join emits no row
    assert metrics["exchanged_rows_per_stmt.analytic"] == 1500.0
    assert metrics["agg_eager_per_stmt.analytic"] >= 2.0


def test_the_parent_refuses_every_statement_of_the_cell():
    """What the driver's run of this cell on the parent commit meets: a
    program that does not know ``query_max_execution_time`` refuses the
    session before it plans anything."""
    from trino_tpu.client import properties

    props = spec.load_cell(CELL).session_properties()
    known = dict(properties.SYSTEM_SESSION_PROPERTIES)
    del known["query_max_execution_time"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(properties, "SYSTEM_SESSION_PROPERTIES", known)
        with pytest.raises(ValueError, match="does not exist"):
            properties.defaulted({k: v for k, v in props.items()})
    assert properties.defaulted(props)["query_max_execution_time"] == "15m"
