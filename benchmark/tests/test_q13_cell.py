"""``tpch_sf10_q13.q13``: TPC-H Q13 at SF 10 through the server, two tables
resident, its LEFT OUTER join expanded against a non-unique orders build
and its per-customer count finished in the join's hash fragment.

The cell is data over code the benchmark had, and one new reference file: a
configuration, a template with two ``choice`` parameters, a traffic file,
three metric files, two for the ``kernel_counter`` reader and one for
``timeline_detail`` (``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import contextlib
import io
import json
import types

import pytest

from benchmark import spec

CELL = "tpch_sf10_q13.q13"
Q21 = "tpch_sf10_q21.q21"
COUNTERS = {
    # name -> (kernel-row field, better)
    "expanded_rows_per_stmt.analytic": ("expandedRows", "lower"),
    "join_fragment_aggs_per_stmt.analytic": ("joinFragmentAggs", "higher"),
}
SPAN_METRIC = "expand_join_ms.analytic"
NEW_METRICS = sorted(COUNTERS) + [SPAN_METRIC]
TINY_ROWS = {"customer": 1500, "orders": 15000}
SEED = 3600000013   # over 2**31: a run's seed may exceed 32 bits
WORD1 = ["special", "pending", "unusual", "express"]
WORD2 = ["packages", "requests", "accounts", "deposits"]


def test_the_cell_loads_and_states_its_deployment():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "tpch_sf10_q13", "q13", 1)
    assert list(cell.templates) == ["q13"]
    t = cell.templates["q13"]
    assert (t.path, t.mode, t.reference, t.reference_module) == (
        "distributed", "literal", "q13", "tpch_q13")
    assert cell.session_properties() == {
        "catalog": "tpch", "schema": "sf10",
        "result_cache_enabled": "false", "device_cache_enabled": "true",
        "query_max_execution_time": "15m"}
    q21 = spec.load_cell(Q21)
    for key in ("catalog", "schema", "scale_factor", "layout", "chips",
                "reduced"):
        assert cell.config[key] == q21.config[key], key
    # Q21's guarantees, its exact counts stated for Q13's two counts
    assert [g for g in cell.config["guarantees"] if "exact" not in g] == [
        g for g in q21.config["guarantees"] if "exact" not in g]
    assert any("count(o_orderkey)" in g for g in cell.config["guarantees"])
    assert cell.config["row_counts"] == {"customer": 1500000,
                                         "orders": 15000000}
    assert t.scan_rows(cell.row_counts) == 16500000
    # the columns a statement must read once, at their logical widths
    assert t.scan_bytes(cell.row_counts, spec.type_bytes()) == (
        1500000 * 8 + 15000000 * (8 + 8 + 4))
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    for key in ("clause_numbers", "word_domains", "comment_pool", "streams",
                "resident_set", "query_max_execution_time"):
        assert key in cell.config["assumed"], key
    assert len(cell.config["source"]) <= 200


def test_it_is_listed_where_it_must_be_and_reports_what_the_analytic_cells_do():
    bench = spec.load_benchmark_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert bench["workloads"][-1] is entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch_sf10_q13", "q13", 1)
    assert len(entry["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == "tpch_sf10_q13"]
    assert config["file"] == "benchmark/configs/tpch_sf10_q13.json"
    assert config["source"] == spec.load_cell(CELL).config["source"]
    assert config["reduced"] == ["layout", "queries", "scale"]
    for m in bench["end_to_end"]:
        if m["name"] in ("geomean_ms", "rows_per_s"):
            assert m["workloads"][-1] == CELL
    cell, q21 = spec.load_cell(CELL), spec.load_cell(Q21)
    names = lambda metrics: [m["name"] for m in metrics]  # noqa: E731
    assert names(cell.end_to_end) == ["geomean_ms", "rows_per_s", "setup_s"]
    assert names(cell.per_layer) == names(q21.per_layer)
    assert names(bench["per_layer"])[-3:] == [
        SPAN_METRIC, "expanded_rows_per_stmt.analytic",
        "join_fragment_aggs_per_stmt.analytic"]


def test_the_window_sends_two_fixed_word_pairs_whatever_the_seed():
    cell = spec.load_cell(CELL)
    params = cell.templates["q13"].params
    assert [(p["name"], p["kind"], p["values"]) for p in params] == [
        ("word1", "choice", WORD1), ("word2", "choice", WORD2)]
    plans = [spec.build_plan(cell, seed, 51.0) for seed in (1, SEED)]
    pairs = [sorted((s.binding["word1"], s.binding["word2"])
                    for s in p.distinct) for p in plans]
    assert pairs[0] == pairs[1] and len(set(pairs[0])) == 2
    for plan in plans:
        assert plan.kind == "closed" and len(plan.streams) == 1
        for s in plan.distinct:
            b = s.binding
            assert f"not like '%{b['word1']}%{b['word2']}%'" in s.sql
            assert "{" not in s.sql and "left outer join" in s.sql


# --------------------------------------- the three metric files, on their readers
def _run(profiles=None, records=()):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=list(records)),
        profiles=profiles or {})


def _row(operator, **fields):
    return dict({"operator": operator, "launches": 1, "expandedRows": 0,
                 "joinFragmentAggs": 0}, **fields)


# a Q13 as this change runs it (one statement), and a statement with no
# expansion and no placed aggregation (q3's shape)
Q13_ROWS = [_row("TableScan"), _row("Join", expandedRows=16777216),
            _row("Aggregation", joinFragmentAggs=1), _row("Aggregation")]
OTHER = [_row("TableScan"), _row("Join"), _row("Aggregation")]
WANT = {"expanded_rows_per_stmt.analytic": (16777216.0, 0.0, 8388608.0),
        "join_fragment_aggs_per_stmt.analytic": (1.0, 0.0, 0.5)}


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_each_counter_file_drives_kernel_counter(name):
    from benchmark.readers import kernel_counter

    field, better = COUNTERS[name]
    body = spec.load_layer_metric(name)
    assert (body["reader"], body["field"]) == ("kernel_counter", field)
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert entry["better"] == better and entry["moves"] == "geomean_ms"
    assert entry["source"] == "program_counter" and "workloads" not in entry
    a, b, both = WANT[name]
    assert kernel_counter.read(body, _run({"a": Q13_ROWS})) == a
    assert kernel_counter.read(body, _run({"b": OTHER})) == b
    assert kernel_counter.read(body, _run({"a": Q13_ROWS, "b": OTHER})) == both


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
        "timeline_detail", ["*/expand-join"])
    entry = {m["name"]: m for m in
             spec.load_benchmark_json()["per_layer"]}[SPAN_METRIC]
    assert (entry["unit"], entry["source"]) == ("ms", "program_span")
    assert "workloads" not in entry
    records = [_record({"device-execute/expand-join": 0.25,
                        "device-execute/residual-join": 0.5,
                        "device-execute/op:Join": 1.0}),
               _record({"exchange-wait/expand-join": 0.125})]
    assert timeline_detail.read(body, _run(records=records)) == 187.5
    # the parent's ledger has a second level but no such label: 0
    assert timeline_detail.read(body, _run(records=[_record(
        {"device-execute/op:Join": 1.0})])) == 0.0
    bare = types.SimpleNamespace(error=None, stats={"timeline": {}})
    assert timeline_detail.read(body, _run(records=[bare])) is None


def test_the_layers_are_ones_the_benchmark_already_names():
    bench = spec.load_benchmark_json()
    known = {m["layer"] for m in bench["per_layer"]
             if m["name"] not in NEW_METRICS}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["layer"] in known
    # the placement is the fragmenter's, as colocated_aggs' is
    assert by_name["join_fragment_aggs_per_stmt.analytic"]["layer"] == \
        by_name["colocated_aggs_per_stmt.analytic"]["layer"]


# --------------------------------------------------- the reference
def test_the_reference_matches_the_row_oracle_at_tiny():
    from benchmark.reference import tpch_q13
    from tests import tpch_oracle as oracle

    (rows,) = tpch_q13.q13("tiny", [{"word1": "special",
                                     "word2": "requests"}])
    assert rows == [list(r) for r in oracle.q13("tiny")]
    assert rows[0][0] == 0 and sum(d for _c, d in rows) == 1500


@pytest.mark.parametrize("pattern, text, hit", [
    ("%special%requests%", "special packages among the requests", True),
    ("%special%requests%", "requests special", False),
    ("%a_c%", "xabcx", True),
    ("%a_c%", "xacx", False),
    ("%1.5%", "1x5", False),
])
def test_the_reference_reads_like_as_sql_does(pattern, text, hit):
    from benchmark.reference import tpch_q13

    assert (tpch_q13.like(pattern).fullmatch(text) is not None) == hit


# --------------------------------------------------- the rehearsal, CPU, tiny
def _tiny_cell(workload, root=spec.ROOT, _load=spec.load_cell):
    """The cell at tiny, where orders' 15,000 rows would be broadcast: a
    broadcast limit under them partitions the join, as SF 10's size does."""
    cell = _load(workload, root)
    props = dict(cell.config["session_properties"],
                 join_max_broadcast_rows="1024")
    cell.config = dict(cell.config, schema="tiny", row_counts=TINY_ROWS,
                       session_properties=props)
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
    # one expansion a statement, its capacity a power of two over the
    # 15,000 orders (less the excluded) and the 500 customers with none
    assert metrics["expanded_rows_per_stmt.analytic"] == 16384.0
    assert metrics["join_fragment_aggs_per_stmt.analytic"] == 1.0
    assert metrics["join_build_sorts_per_stmt.analytic"] == 1.0
    assert metrics["residual_pairs_per_stmt.analytic"] == 0.0
    assert metrics[SPAN_METRIC] >= 0.0
