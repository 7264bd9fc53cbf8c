"""Query phase ledger (obs/timeline.py): attribution units + acceptance.

Acceptance (ISSUE 11): the ledger sums to >=95% of query wall
(unattributed residual <=5%) on (a) a distributed TPC-H Q1, (b) a
fast-path point query, and (c) the SECOND EXECUTE of a prepared
statement; ``trino_tpu_query_phase_seconds{phase="queued"}`` is
observable via /v1/metrics and system.metrics; the ledger rides
queryStats.timeline on statement responses, the trace payload, the new
system.runtime.queries columns, the CLI summary, and the EXPLAIN
ANALYZE header.
"""
import json
import time
import urllib.request

import pytest

from trino_tpu.client.remote import StatementClient
from trino_tpu.obs.timeline import (
    PHASES, compute_timeline, observe_phases, summarize)
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer

from tests.tpch_sql import QUERIES as TPCH


# ------------------------------------------------------------ sweep units
def _span(name, start, dur, sid="s", parent=None, **attrs):
    return {"name": name, "start": start, "durationS": dur, "spanId": sid,
            "parentId": parent, "attributes": attrs}


def test_exclusive_attribution_with_overlap():
    """Worker staging overlapping the coordinator's schedule window is
    charged to device-staging exactly once; the schedule phase keeps only
    its exclusive remainder."""
    spans = [
        _span("query", 10.1, 0.9, "r"),
        _span("schedule", 10.2, 0.4, "sc"),
        _span("device/staging", 10.3, 0.2, "st"),
        _span("execute/root-fragment", 10.6, 0.35, "ex"),
        _span("exchange/pull", 10.62, 0.1, "p1"),
        _span("exchange/pull", 10.65, 0.1, "p2"),  # overlapping pulls
    ]
    tl = compute_timeline(spans, 10.0, 11.0)
    d = tl.to_dict()
    assert abs(d["phases"]["queued"] - 0.1) < 1e-9
    assert abs(d["phases"]["device-staging"] - 0.2) < 1e-9
    assert abs(d["phases"]["schedule"] - 0.2) < 1e-9  # 0.4 minus staging
    # two overlapping pulls cover [10.62, 10.75): charged once
    assert abs(d["phases"]["exchange-wait"] - 0.13) < 1e-9
    assert abs(d["phases"]["device-execute"] - (0.35 - 0.13)) < 1e-9
    # the root span's exclusive remainder (pre-schedule + post-execute
    # connective tissue) is dispatch, not a hidden gap
    assert abs(d["phases"]["dispatch"] - 0.15) < 1e-9
    assert d["unattributedS"] == pytest.approx(0.0)
    # attributed + unattributed == wall, exactly (segment-fetch and
    # client-drain sit OUTSIDE the wall)
    in_wall = sum(v for p, v in d["phases"].items()
                  if p not in ("client-drain", "segment-fetch"))
    assert in_wall == pytest.approx(d["wallS"], abs=1e-6)
    assert tl.wall_s == pytest.approx(1.0)


def test_phase_sums_never_exceed_wall():
    spans = [
        _span("query", 0.0, 100.0, "r"),
        _span("device/execute", 0.0, 100.0, "a"),
        _span("device/staging", 0.0, 100.0, "b"),
        _span("exchange/pull", 0.0, 100.0, "c"),
    ]
    tl = compute_timeline(spans, 0.0, 1.0)  # spans clip to the wall
    attributed = sum(tl.phases.values())
    assert attributed <= tl.wall_s + 1e-9
    # staging (higher priority) owns the whole contested second
    assert tl.phases["device-staging"] == pytest.approx(1.0)
    assert tl.unattributed_s == pytest.approx(0.0)


def test_open_spans_run_to_wall_end_and_missing_root_is_queued():
    spans = [_span("query", 0.2, None, "r"),
             _span("device/execute", 0.3, None, "e")]
    tl = compute_timeline(spans, 0.0, 1.0)
    assert tl.phases["queued"] == pytest.approx(0.2)
    assert tl.phases["device-execute"] == pytest.approx(0.7)
    # no spans at all: the whole wall was queued (failed pre-dispatch)
    tl2 = compute_timeline([], 5.0, 7.0)
    assert tl2.phases["queued"] == pytest.approx(2.0)
    assert tl2.coverage == pytest.approx(1.0)


def test_observe_phases_covers_every_label():
    from trino_tpu.obs import metrics as M

    tl = compute_timeline([_span("query", 0.0, 1.0, "r")], 0.0, 1.0)
    before = {p: M.QUERY_PHASE_SECONDS.snapshot(p)[2] for p in PHASES}
    observe_phases(tl.to_dict())
    for p in PHASES:
        assert M.QUERY_PHASE_SECONDS.snapshot(p)[2] == before[p] + 1


def test_summarize_is_compact_and_ordered():
    spans = [_span("query", 0.0, 1.0, "r"),
             _span("device/execute", 0.0, 0.6, "e"),
             _span("parse", 0.6, 0.2, "p")]
    line = summarize(compute_timeline(spans, 0.0, 1.0).to_dict())
    assert line.index("device-execute") < line.index("parse-analyze")
    assert "% attributed)" in line


# ------------------------------------------------- acceptance, live cluster
@pytest.fixture(scope="module")
def cluster():
    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"ledger-w{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _wait_terminal(q, timeout=90.0):
    deadline = time.time() + timeout
    while not q.state.is_terminal() and time.time() < deadline:
        time.sleep(0.02)
    return q.state.get()


def _assert_ledger(tl, where):
    assert tl is not None, f"no timeline for {where}"
    assert tl["wallS"] > 0
    assert tl["coverage"] >= 0.95, (
        f"{where}: unattributed {tl['unattributedS'] * 1e3:.1f}ms of "
        f"{tl['wallS'] * 1e3:.1f}ms wall ({tl['coverage'] * 100:.1f}% "
        f"attributed): {tl['phases']}")
    assert tl["unattributedS"] <= 0.05 * tl["wallS"] + 1e-9
    # exclusive phases can never total more than the wall (per-phase
    # values are rounded to the microsecond, hence the slack);
    # segment-fetch and client-drain sit outside the wall
    in_wall = sum(v for p, v in tl["phases"].items()
                  if p not in ("client-drain", "segment-fetch"))
    assert in_wall <= tl["wallS"] + 2e-5
    return tl


def test_ledger_distributed_tpch_q1(cluster):
    coord, _ = cluster
    q = coord.submit(TPCH[1], {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    tl = _assert_ledger(q.timeline_dict(), "tpch q1 distributed")
    # a distributed scan-heavy query attributes real time to the workers'
    # device phases (staging + execute), not just the coordinator drain
    assert (tl["phases"]["device-staging"] + tl["phases"]["device-execute"]
            + tl["phases"]["exchange-wait"]) > 0
    # the ledger rides query info / statement stats and the trace payload
    info = q.info()
    assert info["queryStats"]["timeline"]["coverage"] >= 0.95
    trace = json.loads(urllib.request.urlopen(
        f"{coord.base_url}/v1/query/{q.query_id}/trace").read())
    assert trace["timeline"]["coverage"] >= 0.95


def test_ledger_fast_path_point_query(cluster):
    coord, _ = cluster
    q = coord.submit(
        "select n_name from nation where n_nationkey = 7",
        {"catalog": "tpch", "schema": "tiny",
         "short_query_fast_path": "true"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    assert q.fast_path == "fast-path"
    _assert_ledger(q.timeline_dict(), "fast-path point query")


def test_ledger_second_execute_of_prepared(cluster):
    coord, _ = cluster
    client = StatementClient(coord.base_url, {
        "catalog": "tpch", "schema": "tiny"})
    client.execute(
        "PREPARE ledger_pt FROM select n_name from nation "
        "where n_nationkey = ?")
    client.execute("EXECUTE ledger_pt USING 3")
    columns, rows = client.execute("EXECUTE ledger_pt USING 7")
    assert rows == [["GERMANY"]]
    q = coord.get_query(client.query_id)
    tl = _assert_ledger(q.timeline_dict(), "second EXECUTE")
    # the bind phase exists on the EXECUTE path (fold + substitution)
    assert tl["phases"]["prepare-bind"] >= 0
    # the statement response carried the same ledger
    assert client.stats["timeline"]["coverage"] >= 0.95


def test_queued_phase_histogram_on_metrics_and_system_table(cluster):
    coord, _ = cluster
    q = coord.submit("select 1 as x", {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    body = urllib.request.urlopen(coord.base_url + "/v1/metrics").read() \
        .decode()
    assert 'trino_tpu_query_phase_seconds_bucket{phase="queued"' in body
    assert 'trino_tpu_query_phase_seconds_count{phase="queued"}' in body
    # and through system.metrics (the SQL surface of the same registry)
    q2 = coord.submit(
        "select name, labels from system.metrics "
        "where name like 'trino_tpu_query_phase_seconds%'", {})
    assert _wait_terminal(q2) == "FINISHED", q2.failure
    assert any("queued" in (r[1] or "") for r in q2.rows), q2.rows[:5]


def test_queries_table_carries_ledger_columns(cluster):
    coord, _ = cluster
    q = coord.submit("select count(*) from nation",
                     {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    q2 = coord.submit(
        "select query_id, queued_ms, planning_ms, execution_ms, "
        "unattributed_ms from system.runtime.queries "
        "where state = 'FINISHED'", {})
    assert _wait_terminal(q2) == "FINISHED", q2.failure
    row = next(r for r in q2.rows if r[0] == q.query_id)
    assert row[1] is not None and row[1] >= 0          # queued_ms
    assert row[2] is not None and row[2] > 0           # planning_ms
    assert row[3] is not None and row[3] > 0           # execution_ms
    tl = q.timeline_dict()
    assert row[4] == pytest.approx(
        tl["phases"]["unattributed"] * 1000.0, abs=1.0)


def test_cli_summary_and_explain_analyze_render_ledger(cluster):
    from trino_tpu.client.cli import render_summary

    coord, _ = cluster
    client = StatementClient(coord.base_url,
                             {"catalog": "tpch", "schema": "tiny"})
    client.execute("select count(*) from region")
    line = render_summary(client.stats)
    assert "phases:" in line and "% attributed" in line
    # EXPLAIN ANALYZE prints the ledger header from the real execution
    columns, rows = client.execute(
        "explain analyze select count(*) from region")
    text = "\n".join(r[0] for r in rows)
    assert "Phase ledger:" in text


# ---------------------------------------------------- level two: the detail
def test_nested_operator_spans_get_self_time():
    """Nested spans of one kind give each instant to the innermost one: an
    operator's share of ``detail`` is its self time, and the leaves it
    contains (a blocking read, a staging stage) come off it."""
    spans = [
        _span("query", 0.0, 10.0, "r"),
        _span("device/execute", 1.0, 8.0, "x"),
        _span("operator/Aggregation", 1.5, 7.0, "a"),
        _span("operator/Filter", 2.0, 4.0, "f"),        # inside a
        _span("operator/TableScan", 2.5, 2.0, "s"),     # inside f
        _span("device/staging", 2.6, 1.5, "st"),        # inside s
        _span("staging/transfer", 3.0, 1.0, "tr"),      # inside st
        _span("host/sync", 5.0, 0.5, "h"),              # inside f
        _span("process/gc", 5.2, 0.1, "g"),             # inside h
        _span("task/output", 9.2, 0.6, "o"),            # after the body
    ]
    d = compute_timeline(spans, 0.0, 10.5).to_dict()
    detail = d["detail"]
    want = {
        "device-execute/op:Aggregation": 0.5 + 2.5,     # [1.5,2) + [6,8.5)
        "device-execute/op:Filter": 0.5 + 0.5 + 0.5,    # [2,2.5) [4.5,5) [5.5,6)
        "device-execute/op:TableScan": 0.1 + 0.4,       # [2.5,2.6) [4.1,4.5)
        "device-execute/host-sync": 0.4,                # 0.5 less the pause
        "device-execute/gc-pause": 0.1,
        "device-execute/remainder": 0.5 + 0.5,          # [1,1.5) [8.5,9)
        "device-staging/op:TableScan": 0.4 + 0.1,       # staging, no stage open
        "device-staging/transfer": 1.0,
        "dispatch/task-output": 0.6,
        "dispatch/remainder": 1.0 + 0.2 + 0.2,
        "unattributed/remainder": 0.5,
    }
    assert set(detail) == set(want)
    for key, seconds in want.items():
        assert detail[key] == pytest.approx(seconds, abs=1e-9), key
    for phase, seconds in d["phases"].items():
        assert sum(v for k, v in detail.items()
                   if k.startswith(phase + "/")) == pytest.approx(
            seconds, abs=1e-6), phase


def test_level_one_is_the_same_with_and_without_detail_spans():
    """``phases`` is swept from the same span names as before level two
    existed: the detail-only spans (operators, blocking reads, the output
    path, collector pauses, listener compiles) move no phase."""
    level_one = [
        _span("query", 10.1, 0.9, "r"),
        _span("schedule", 10.2, 0.4, "sc"),
        _span("device/staging", 10.3, 0.2, "st"),
        _span("staging/scan", 10.3, 0.1, "ss"),
        _span("device/execute", 10.5, 0.1, "de"),
        _span("execute/root-fragment", 10.6, 0.35, "ex"),
        _span("exchange/pull", 10.62, 0.1, "p1"),
    ]
    detail_only = [
        _span("operator/Join", 10.5, 0.1, "o1"),
        _span("operator/Output", 10.75, 0.1, "o2"),
        _span("host/sync", 10.52, 0.03, "h"),
        _span("task/output", 10.6, 0.05, "t"),
        _span("process/gc", 10.05, 0.2, "g"),    # over queued and dispatch
        _span("xla/compile", 10.26, 0.02, "c"),
    ]
    plain = compute_timeline(level_one, 10.0, 11.0).to_dict()
    both = compute_timeline(level_one + detail_only, 10.0, 11.0).to_dict()
    assert list(both["phases"]) == list(PHASES) and len(PHASES) == 14
    assert both["phases"] == plain["phases"]
    assert both["unattributedS"] == plain["unattributedS"]
    assert both["detail"]["queued/gc-pause"] == pytest.approx(0.05)
    assert both["detail"]["dispatch/gc-pause"] == pytest.approx(0.1)
    assert both["detail"]["schedule/gc-pause"] == pytest.approx(0.05)
    assert both["detail"]["schedule/compile"] == pytest.approx(0.02)
    assert both["detail"]["exchange-wait/task-output"] == pytest.approx(0.03)
    # without detail spans every phase is its own remainder, the staging
    # stage and the pull excepted: they were spans before
    assert plain["detail"]["device-execute/remainder"] == pytest.approx(
        plain["phases"]["device-execute"])
    assert plain["detail"]["device-staging/scan"] == pytest.approx(0.1)
    assert plain["detail"]["exchange-wait/pull"] == pytest.approx(0.1)


def _span_names(node, out):
    out.setdefault(node["name"], []).append(node)
    for child in node["children"]:
        _span_names(child, out)
    return out


_POINT = ("PREPARE ledger_order FROM select o_orderkey, o_custkey, "
          "o_totalprice from orders where o_orderkey = ?")
_DETAIL_STATEMENTS = {
    "q1": TPCH[1], "q3": TPCH[3], "q6": TPCH[6], "q18": TPCH[18],
    "point": "EXECUTE ledger_order USING 7",
}


@pytest.mark.parametrize("name", list(_DETAIL_STATEMENTS))
def test_detail_sums_to_phases_through_the_statement_protocol(cluster, name):
    """Through ``/v1/statement``: 14 phases, every phase's detail summing
    to it, an ``operator/<Kind>`` span for every plan node kind executed,
    and every blocking read counted once in a kernel row and once under
    its site."""
    coord, _ = cluster
    props = {"catalog": "tpch", "schema": "tiny",
             "result_cache_enabled": "false"}
    if name == "point":
        props["short_query_fast_path"] = "true"
    client = StatementClient(coord.base_url, props)
    if name == "point":
        client.execute(_POINT)
    _columns, rows = client.execute(_DETAIL_STATEMENTS[name])
    assert rows
    tl = client.stats["timeline"]
    assert list(tl["phases"]) == list(PHASES) and len(tl["phases"]) == 14
    for phase, seconds in tl["phases"].items():
        split = sum(v for k, v in tl["detail"].items()
                    if k.startswith(phase + "/"))
        assert split == pytest.approx(seconds, abs=1e-5), (phase, tl["detail"])
    assert any(k.startswith("device-execute/op:") for k in tl["detail"])
    # every operator kind the kernel ledger saw executing has its spans
    prof = json.loads(urllib.request.urlopen(
        f"{coord.base_url}/v1/query/{client.query_id}/profile").read())
    trace = json.loads(urllib.request.urlopen(
        f"{coord.base_url}/v1/query/{client.query_id}/trace").read())
    spans = _span_names(trace["root"], {})
    executed = {k["operator"] for k in prof["kernels"] if k["launches"]}
    assert executed, prof["kernels"]
    for kind in executed:
        assert f"operator/{kind}" in spans, (kind, sorted(spans))
    for sp in spans.get("operator/Filter", []):
        assert "planNodeId" in sp["attributes"] and "rows" in sp["attributes"]
    # the counters at the same boundaries
    syncs = sum(k["hostSyncs"] for k in prof["kernels"])
    assert syncs >= 1
    assert syncs == sum(s["count"] for s in prof["hostSyncSites"].values())
    assert sum(k["d2hBytes"] for k in prof["kernels"]) == sum(
        s["bytes"] for s in prof["hostSyncSites"].values())
    assert "operator-stats" in prof["hostSyncSites"]
    if name != "point":
        # a worker's output path is spanned, in every driver shape
        assert "task/output" in spans
