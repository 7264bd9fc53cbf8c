"""``tpch_sf10_resident.q3``: ``tpch_sf10.q3`` with its tables kept in HBM.

The pair differs in one session property and in nothing the harness sends:
the same traffic file, template, bindings and seeds, so the same statements
in the same order and the same reference. Its three per-layer metrics are
data files over readers the benchmark has
(``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import json
import types

import pytest

from benchmark import spec
from benchmark.loadgen import Record

CELL = "tpch_sf10_resident.q3"
TWIN = "tpch_sf10.q3"
NEW_METRICS = ("device_cache_hits_per_stmt.analytic",
               "staged_bytes_per_stmt.analytic", "cache_lookup_ms.analytic")
SEED = 3000000007


def test_the_cell_loads_with_the_cache_on_and_nothing_else_changed():
    cell, twin = spec.load_cell(CELL), spec.load_cell(TWIN)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tpch_sf10_resident", "q3", 1)
    props, twin_props = cell.session_properties(), twin.session_properties()
    assert props["device_cache_enabled"] == "true"
    assert props["result_cache_enabled"] == "false"   # every statement executes
    assert {k for k in props if props[k] != twin_props.get(k)} == {
        "device_cache_enabled"}
    assert set(props) == set(twin_props)
    for key in ("catalog", "schema", "scale_factor", "row_counts", "reduced",
                "layout", "chips"):
        assert cell.config[key] == twin.config[key], key
    assert cell.config["guarantees"][:4] == twin.config["guarantees"]
    assert "data_version" in cell.config["guarantees"][4]
    assert len(cell.config["source"]) <= 200


def test_it_reports_what_its_twin_reports_and_is_listed_where_it_must_be():
    cell, twin = spec.load_cell(CELL), spec.load_cell(TWIN)

    def names(metrics):
        return [m["name"] for m in metrics]

    assert names(cell.end_to_end) == names(twin.end_to_end) == [
        "geomean_ms", "rows_per_s", "setup_s"]
    assert names(cell.per_layer) == names(twin.per_layer)
    assert names(cell.per_layer)[-3:] == list(NEW_METRICS)
    bench = spec.load_benchmark_json()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["file"] == (
        "benchmark/configs/tpch_sf10_resident.json")
    for m in bench["end_to_end"]:
        if m["name"] in ("geomean_ms", "rows_per_s"):
            assert m["workloads"][-1] == CELL
    for m in bench["per_layer"][-3:]:
        assert "workloads" not in m and m["moves"] == "geomean_ms"


def test_it_shares_its_twin_s_plan_statement_for_statement():
    plan = spec.build_plan(spec.load_cell(CELL), SEED, 51.0)
    twin = spec.build_plan(spec.load_cell(TWIN), SEED, 51.0)
    assert plan.kind == twin.kind == "closed"
    assert plan.distinct == twin.distinct and len(plan.distinct) == 2
    assert plan.streams == twin.streams and len(plan.streams) == 1
    assert plan.turn == twin.turn


# ------------------------------------- the three metric files, each on its reader
def _record(detail):
    rec = Record("q3", "{}", "sql", 0, "0.0", 0.0, 0.0)
    rec.stats = {"timeline": {"phases": {"device-staging": 1.0},
                              "detail": detail}}
    return rec


def _run(records=(), profiles=None):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=list(records)),
        profiles=profiles or {})


def _scan(hits, misses, staged):
    return {"operator": "TableScan", "launches": 1, "cacheHits": hits,
            "cacheMisses": misses, "stagedBytes": staged}


# a statement served from HBM (4 scans, 4 hits) and one that staged its tables
RESIDENT = {"a": [_scan(1, 0, 0)] * 4 + [{"operator": "Join", "launches": 9,
                                          "cacheHits": 0, "cacheMisses": 0,
                                          "stagedBytes": 0}]}
STAGING = {"a": [_scan(0, 0, 1069547520), _scan(0, 0, 267386880),
                 _scan(0, 0, 7077888), _scan(0, 0, 7077888)]}
WANT = {
    "device_cache_hits_per_stmt.analytic": (4.0, 0.0),
    "staged_bytes_per_stmt.analytic": (0.0, 1351090176.0),
    # the hit's lookups; a cache-off statement opens no lookup span
    "cache_lookup_ms.analytic": (0.3, 0.0),
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_file_drives_its_reader(name):
    body = spec.load_layer_metric(name)
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    reader = __import__(f"benchmark.readers.{body['reader']}",
                        fromlist=["read"])
    hit = _record({"device-staging/cache-lookup": 0.0003,
                   "device-staging/op:TableScan": 0.0007})
    miss = _record({"device-staging/transfer": 4.1,
                    "device-staging/op:TableScan": 0.17})
    resident, staging = WANT[name]
    assert reader.read(body, _run([hit], RESIDENT)) == pytest.approx(resident)
    assert reader.read(body, _run([miss], STAGING)) == pytest.approx(staging)


@pytest.mark.parametrize("name", NEW_METRICS[:2])
def test_a_program_without_the_fields_leaves_the_counters_out(name):
    """The parent's kernel rows lack the three fields: nothing to read, no
    raise, and the line leaves the metric out."""
    body = spec.load_layer_metric(name)
    from benchmark.readers import kernel_counter

    parent = {"a": [{"operator": "TableScan", "launches": 1}]}
    assert kernel_counter.read(body, _run(profiles=parent)) is None
    assert kernel_counter.read(body, _run()) is None


def test_the_layer_is_one_the_benchmark_already_names():
    bench = spec.load_benchmark_json()
    known = {m["layer"] for m in bench["per_layer"][:-3]}
    for m in bench["per_layer"][-3:]:
        assert m["layer"] in known
    assert json.dumps(bench).count(CELL) == 3
