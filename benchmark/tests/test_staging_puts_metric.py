"""``staging_puts_per_stmt.analytic``: host -> device puts a statement's
fresh stagings issued (kernel rows' ``stagingPuts``, PR 37), a data file
over the ``kernel_counter`` reader. Where the metric sits in ``per_layer``
is not pinned (``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import types

import pytest

from benchmark import spec

NAME = "staging_puts_per_stmt.analytic"


def _run(profiles):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=[]), profiles=profiles)


def _scan(puts, staged):
    return {"operator": "TableScan", "launches": 1, "stagedBytes": staged,
            "stagingPuts": puts}


JOIN = {"operator": "Join", "launches": 9, "stagedBytes": 0,
        "stagingPuts": 0}
# tpch_sf10.q3 with the cache off: lineitem's four columns and its mask,
# orders' four and its mask, customer's two and its mask, every statement
STAGING = {"a": [_scan(5, 1069547520), _scan(5, 267386880),
                 _scan(3, 14155776), JOIN],
           "b": [_scan(5, 1069547520), _scan(5, 267386880),
                 _scan(3, 14155776), JOIN]}
# the resident cells: every scan a hit, nothing put
RESIDENT = {"a": [_scan(0, 0)] * 4 + [JOIN]}
# the parent's rows have no such field: nothing to read
PARENT = {"a": [{"operator": "TableScan", "launches": 1,
                 "stagedBytes": 1069547520}]}


def test_the_metric_file_matches_its_entry():
    body = spec.load_layer_metric(NAME)
    entry = {m["name"]: m for m in spec.load_benchmark_json()["per_layer"]}[
        NAME]
    assert (body["reader"], body["field"]) == ("kernel_counter",
                                               "stagingPuts")
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert entry["better"] == "lower" and "workloads" not in entry
    staged = {m["name"]: m for m in spec.load_benchmark_json()["per_layer"]}[
        "staged_bytes_per_stmt.analytic"]
    assert entry["layer"] == staged["layer"]


@pytest.mark.parametrize("profiles,want", [
    (STAGING, 13.0), (RESIDENT, 0.0), (PARENT, None), ({}, None)],
    ids=["staging", "resident", "parent", "no-profile"])
def test_it_drives_the_kernel_counter_reader(profiles, want):
    from benchmark.readers import kernel_counter

    got = kernel_counter.read(spec.load_layer_metric(NAME), _run(profiles))
    assert got == (None if want is None else pytest.approx(want))


def test_every_cell_that_reports_geomean_reports_it():
    bench = spec.load_benchmark_json()
    for cell in bench["workloads"]:
        loaded = spec.load_cell(cell["name"])
        reports = NAME in [m["name"] for m in loaded.per_layer]
        assert reports == ("geomean_ms" in [
            m["name"] for m in loaded.end_to_end]), cell["name"]
