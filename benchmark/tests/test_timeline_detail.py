"""The readers of level two of the phase ledger, every per-layer metric file
that uses them, and the naming of idle gaps by the three leaf spans, on the
CPU rehearsal (``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``)."""
import glob
import json
import os
import types

import pytest

from benchmark import run, spec, trace
from benchmark.loadgen import Record
from benchmark.readers import kernel_counter, timeline_detail
from benchmark.tests import helpers

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_READERS = ("timeline_detail", "kernel_counter")


def _new_metric_files():
    out = []
    for path in sorted(glob.glob(os.path.join(
            spec.BENCH_DIR, "layer_metrics", "*.json"))):
        with open(path, encoding="utf-8") as f:
            body = json.load(f)
        if body["reader"] in NEW_READERS:
            out.append(body)
    return out


def _record(detail=None, error=None, timeline=True):
    rec = Record("q1", "{}", "sql", 0, "0.0", 0.0, 0.0, error=error)
    if timeline:
        tl = {"phases": {"device-execute": 1.0}}
        if detail is not None:
            tl["detail"] = detail
        rec.stats = {"timeline": tl}
    return rec


def _run(records=(), profiles=None):
    return types.SimpleNamespace(
        window=types.SimpleNamespace(records=list(records)),
        profiles=profiles or {})


# ------------------------------------------------------------- the readers
def test_detail_reader_sums_matching_keys_per_statement():
    records = [
        _record({"device-execute/op:Filter": 0.5, "device-execute/op:Join": 0.25,
                 "device-execute/host-sync": 0.125, "exchange-wait/host-sync": 0.125,
                 "device-execute/remainder": 0.1}),
        _record({"device-execute/op:Filter": 0.25}),
        _record({"device-execute/op:Filter": 9.0}, error="boom"),  # not counted
    ]
    ops = {"patterns": ["device-execute/op:*"]}
    assert timeline_detail.read(ops, _run(records)) == pytest.approx(
        1000 * (0.75 + 0.25) / 2)
    syncs = {"patterns": ["*/host-sync"]}
    assert timeline_detail.read(syncs, _run(records)) == pytest.approx(
        1000 * 0.25 / 2)
    two = {"patterns": ["device-execute/remainder", "device-execute/host-sync"]}
    assert timeline_detail.read(two, _run(records)) == pytest.approx(
        1000 * 0.225 / 2)
    # a statement with detail and no matching key counts as 0, not as absent
    assert timeline_detail.read({"patterns": ["*/gc-pause"]},
                                _run(records)) == 0.0


def test_detail_reader_finds_nothing_on_a_program_without_level_two():
    before = [_record(detail=None), _record(timeline=False)]
    assert timeline_detail.read({"patterns": ["*"]}, _run(before)) is None
    assert timeline_detail.read({"patterns": ["*"]}, _run([])) is None


def test_kernel_counter_reads_a_field_and_finds_nothing_where_rows_lack_it():
    body = {"field": "hostSyncs"}
    profiles = {"a": [{"launches": 3, "hostSyncs": 2}, {"launches": 1, "hostSyncs": 5}],
                "b": [{"launches": 2, "hostSyncs": 1}]}
    assert kernel_counter.read(body, _run(profiles=profiles)) == pytest.approx(4.0)
    parent = {"a": [{"launches": 3}], "b": [{"launches": 2}]}
    assert kernel_counter.read(body, _run(profiles=parent)) is None
    assert kernel_counter.read(body, _run()) is None


@pytest.mark.parametrize("body", _new_metric_files(), ids=lambda b: b["name"])
def test_every_new_metric_file_drives_its_reader(body):
    bench = {m["name"]: m for m in spec.load_benchmark_json()["per_layer"]}
    entry = bench[body["name"]]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert "workloads" not in entry
    reader = __import__(f"benchmark.readers.{body['reader']}",
                        fromlist=["read"])
    detail = {"device-execute/op:Filter": 0.5, "device-execute/host-sync": 0.25,
              "exchange-wait/task-output": 0.125, "device-execute/remainder": 0.0625,
              "device-staging/scan": 0.03125, "device-staging/decode": 0.015625,
              "device-staging/transfer": 0.0078125, "dispatch/gc-pause": 0.00390625}
    data = _run([_record(detail)], {"a": [{"hostSyncs": 7}]})
    value = reader.read(body, data)
    want = {"execute_operator_ms": 500.0, "host_sync_ms": 250.0,
            "task_output_ms": 125.0, "execute_remainder_ms": 62.5,
            "staging_scan_ms": 46.875, "staging_transfer_ms": 7.8125,
            "gc_pause_ms": 3.90625, "host_syncs_per_stmt": 7.0}
    assert value == pytest.approx(want[body["name"].split(".")[0]])
    assert reader.read(body, _run([_record(None)], {"a": [{}]})) is None


def test_the_issue_s_ten_metrics_are_all_there():
    names = sorted(b["name"] for b in _new_metric_files())
    assert names == sorted([
        "execute_operator_ms.analytic", "host_sync_ms.analytic",
        "host_sync_ms.point", "task_output_ms.analytic",
        "execute_remainder_ms.analytic", "host_syncs_per_stmt.analytic",
        "staging_scan_ms.analytic", "staging_transfer_ms.analytic",
        "staging_transfer_ms.point", "gc_pause_ms.point"])
    tail = [m["name"] for m in spec.load_benchmark_json()["per_layer"]][-10:]
    assert sorted(tail) == names, "appended at the end of per_layer"


# ------------------------------------------------- idle gaps by leaf spans
class _Served:
    """Hands ``run._phase_spans`` one statement's span tree."""

    def __init__(self, root):
        self.root = root

    def get(self, _path):
        return {"root": self.root}


def _node(name, start_ns, end_ns, children=()):
    return {"name": name, "start": start_ns / 1e9,
            "durationS": (end_ns - start_ns) / 1e9, "children": list(children)}


def test_a_gap_inside_a_leaf_span_is_named_for_it_on_the_recorded_trace():
    with open(os.path.join(HERE, "data", "recorded_trace.json"),
              encoding="utf-8") as f:
        events = json.load(f)["events"]
    plain = trace.reduce_events(events)
    ops = [e for e in events if e["line"] == trace.OP_LINE]
    w0 = min(e["start_ns"] for e in ops)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in ops)
    # the three longest gaps, found again with their ends: idle_gaps gives
    # lengths only
    busy = trace.union((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops)
    gaps = sorted(((b0, a1) for (_a0, b0), (a1, _b1) in zip(busy, busy[1:])),
                  key=lambda g: g[0] - g[1])[:3]
    assert [round((b - a) / 1e9, 9) for a, b in gaps] == [
        round(s, 9) for _n, s in plain["idle_gaps"][:3]]
    (g0, g1, g2) = gaps
    # synthetic program spans: an execute window over the first two gaps,
    # a leaf of each kind over the whole of one gap each, and a collector
    # pause over 60% of the third gap, which no window covers (the point
    # cell's case: statements in flight and no phase span over the gap)
    lo, hi = min(g0[0], g1[0]) - 10, max(g0[1], g1[1]) + 10
    assert not (lo < g2[0] < hi), "the third gap lies outside the window"
    pause_end = g2[0] + int(0.6 * (g2[1] - g2[0]))
    tree = _node("query", w0, w1, [
        _node("execute/root-fragment", lo, hi, [
            _node("device/execute", lo, hi, [
                _node("operator/Aggregation", lo, hi, [
                    _node("host/sync", g0[0] - 5, g0[1] + 5)]),
                _node("task/output", g1[0], g1[1])])]),
        _node("process/gc", g2[0], pause_end)])
    rec = Record("q1", "{}", "sql", 0, "0.0", 0.0, 0.0)
    rec.query_id = "q"
    spans = run._phase_spans(_Served(tree), [rec], 0)
    assert {name for _a, _b, name in spans} == {
        "device-execute", "host-sync", "task-output", "gc-pause"}, \
        "operator spans have no entry: the outermost would cover every gap"
    named = trace.reduce_events(events, (w0, w1), spans)["idle_gaps"]
    assert [n for n, _s in named[:3]] == ["host-sync", "task-output", "gc-pause"]
    # the harness names a gap for the span that covers MOST of it: a pause
    # over 60% of a gap that an execute window covers whole leaves the gap
    # to the window (PERF.md section 7: the innermost span should win)
    inside = _node("query", w0, w1, [_node("device/execute", w0, w1, [
        _node("process/gc", g2[0], pause_end)])])
    spans = run._phase_spans(_Served(inside), [rec], 0)
    assert trace.reduce_events(events, (w0, w1), spans)["idle_gaps"][2][0] \
        == "device-execute"


# --------------------------------------------------- the whole rehearsal
@pytest.mark.parametrize("name", ["tpch_sf1.scan_agg", "tpch_sf10.q3",
                                  "tpch_sf1_serving.point_lookup"])
def test_traced_rehearsal_reports_the_new_metrics_in_their_cells(monkeypatch, name):
    from benchmark import loadgen

    kept = []
    real_window = loadgen.run_window

    def keep(*args, **kwargs):
        window = real_window(*args, **kwargs)
        kept.append(window)
        return window

    monkeypatch.setattr(loadgen, "run_window", keep)
    rc, result, err = helpers.rehearse(monkeypatch, name, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    suffix = ".point" if name.endswith("point_lookup") else ".analytic"
    want = {b["name"] for b in _new_metric_files()
            if b["name"].endswith(suffix)}
    assert want <= set(result["metrics"]), sorted(want - set(result["metrics"]))
    # level two is the same instants as level one, cut finer
    records = [r for r in kept[0].records if r.error is None]
    for phase, metric in (("device-execute", "execute_ms"),
                          ("device-staging", "staging_ms")):
        split = [sum(v for k, v in r.stats["timeline"]["detail"].items()
                     if k.startswith(phase + "/")) for r in records]
        mean_ms = 1000.0 * sum(split) / len(split)
        assert mean_ms == pytest.approx(
            result["metrics"][metric + suffix]["value"], rel=1e-3, abs=1e-3)
    if suffix == ".analytic":
        assert result["metrics"]["host_syncs_per_stmt.analytic"]["value"] >= 1
        assert result["metrics"]["execute_operator_ms.analytic"]["value"] > 0
