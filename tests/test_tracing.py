"""Query-lifecycle tracing + typed metrics registry (trino_tpu/obs/).

Covers: span nesting/parenting (explicit + ambient surfaces), traceparent
propagation across the control plane (2-worker distributed query -> one
rooted trace tree), Prometheus text rendering (histogram buckets, label
escaping), the /v1/metrics superset guarantee, compiled-tier device spans
+ compile-cache counters, the slow-query listener, and listener-exception
logging.
"""
import json
import logging
import time
import urllib.request

import pytest

from trino_tpu.obs import trace as tracing
from trino_tpu.obs.metrics import (
    Counter, Histogram, MetricsRegistry, escape_label_value)
from trino_tpu.obs.trace import Tracer, build_tree, flatten_tree, parse_traceparent
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer


# ------------------------------------------------------------- tracer unit
def test_span_nesting_and_parenting():
    t = Tracer()
    with t.span("query") as q:
        with t.span("plan") as p:
            with t.span("optimize") as o:
                pass
        with t.span("schedule") as s:
            pass
    spans = {sp.name: sp for sp in t.spans()}
    assert spans["query"].parent_id is None
    assert spans["plan"].parent_id == q.span_id
    assert spans["optimize"].parent_id == p.span_id
    assert spans["schedule"].parent_id == q.span_id
    assert all(sp.end is not None for sp in spans.values())
    assert o.duration_s >= 0 and s.duration_s >= 0


def test_ambient_span_attaches_to_active_tracer():
    t = Tracer()
    with tracing.activate(t):
        with tracing.span("outer") as outer:
            with tracing.span("inner", rows=7):
                pass
    spans = {sp.name: sp for sp in t.spans()}
    assert spans["inner"].parent_id == outer.span_id
    assert spans["inner"].attributes["rows"] == 7


def test_ambient_span_noops_without_tracer():
    with tracing.span("nowhere") as sp:
        sp.set("x", 1)  # attribute write must be accepted and dropped
    assert sp is tracing.NOOP_SPAN


def test_explicit_and_ambient_surfaces_share_nesting():
    """A tracer.span inside an ambient activation nests under the ambient
    chain, and ambient spans nest under explicit ones (one mechanism)."""
    t = Tracer()
    with t.span("query") as q:
        with tracing.span("ambient-child") as a:
            with t.span("explicit-grandchild") as g:
                pass
    assert a.parent_id == q.span_id
    assert g.parent_id == a.span_id


def test_traceparent_round_trip():
    t = Tracer()
    with t.span("schedule") as sp:
        header = t.traceparent()
    assert parse_traceparent(header) == (t.trace_id, sp.span_id)
    assert parse_traceparent(None) is None
    assert parse_traceparent("garbage") is None
    # a worker tracer built from the header parents its root spans there
    ctx = parse_traceparent(header)
    wt = Tracer(trace_id=ctx[0], root_parent_id=ctx[1])
    task = wt.start_span("task")
    assert wt.trace_id == t.trace_id
    assert task.parent_id == sp.span_id


def test_build_tree_single_root_with_orphans():
    t = Tracer()
    with t.span("query"):
        with t.span("schedule"):
            pass
    dicts = t.to_dicts()
    # an orphan (unknown parent — e.g. worker spans whose coordinator
    # parent got lost) must attach under the root, not vanish
    dicts.append({"spanId": "feed", "parentId": "dead", "name": "orphan",
                  "start": time.time(), "durationS": 0.1, "attributes": {}})
    tree = build_tree(dicts)
    assert tree["name"] == "query"
    names = {n["name"] for n in flatten_tree(tree)}
    assert names == {"query", "schedule", "orphan"}
    assert len(list(flatten_tree(tree))) == len(dicts)


def test_tracer_thread_safety_under_concurrent_spans():
    import threading

    t = Tracer()
    def worker(i):
        for _ in range(50):
            sp = t.start_span(f"w{i}", parent_id="root")
            t.end_span(sp)
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.spans()) == 400


# ------------------------------------------------------------ metrics unit
def test_counter_and_gauge_render():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help text")
    g = reg.gauge("t_gauge", "state gauge", ("state",))
    c.inc()
    c.inc(4)
    g.set(3, "RUNNING")
    out = reg.render()
    assert "# HELP t_total help text" in out
    assert "# TYPE t_total counter" in out
    assert "t_total 5" in out.splitlines()
    assert 't_gauge{state="RUNNING"} 3' in out.splitlines()


def test_histogram_bucket_rendering():
    h = Histogram("t_seconds", "latency", ("state",), buckets=(0.1, 1, 5))
    h.observe(0.05, "FINISHED")
    h.observe(2.0, "FINISHED")
    lines = h.render()
    assert "# TYPE t_seconds histogram" in lines
    assert 't_seconds_bucket{state="FINISHED",le="0.1"} 1' in lines
    assert 't_seconds_bucket{state="FINISHED",le="1"} 1' in lines
    assert 't_seconds_bucket{state="FINISHED",le="5"} 2' in lines
    assert 't_seconds_bucket{state="FINISHED",le="+Inf"} 2' in lines
    assert 't_seconds_sum{state="FINISHED"} 2.05' in lines
    assert 't_seconds_count{state="FINISHED"} 2' in lines


def test_label_value_escaping():
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"
    # a hostile label value renders to ONE well-formed line
    c = Counter("t_esc_total", "h", ("q",))
    c.inc(1, 'he said "hi\\there"\nnext')
    (line,) = [l for l in c.render() if not l.startswith("#")]
    assert "\n" not in line
    assert line == (
        't_esc_total{q="he said \\"hi\\\\there\\"\\nnext"} 1')


def test_histogram_snapshot():
    h = Histogram("t_snap_seconds", "x", buckets=(1, 10))
    h.observe(0.5)
    h.observe(20)
    counts, total, n = h.snapshot()
    assert counts == [1, 1] and total == 20.5 and n == 2


# ----------------------------------------------------- events + listeners
def test_listener_exceptions_are_logged_not_swallowed(caplog):
    from trino_tpu.server.events import (
        EventListener, EventListenerManager, QueryCreatedEvent)

    class Exploder(EventListener):
        def query_created(self, event):
            raise RuntimeError("listener bug")

    class Recorder(EventListener):
        def __init__(self):
            self.events = []

        def query_created(self, event):
            self.events.append(event)

    mgr = EventListenerManager()
    rec = Recorder()
    mgr.add(Exploder())
    mgr.add(rec)
    ev = QueryCreatedEvent("q1", "alice", "select 1", time.time())
    with caplog.at_level(logging.ERROR, logger="trino_tpu.events"):
        mgr.fire_created(ev)  # must not raise
    assert rec.events == [ev]  # isolation: later listeners still fire
    assert "Exploder" in caplog.text and "query_created" in caplog.text
    assert "listener bug" in caplog.text  # traceback included


def _completed_event(wall_s, spans=(), session_properties=None):
    from trino_tpu.server.events import QueryCompletedEvent

    return QueryCompletedEvent(
        "q42", "alice", "select * from lineitem", "FINISHED",
        0.0, wall_s, wall_s, 10, None, spans=spans,
        session_properties=session_properties or {})


def test_slow_query_listener_logs_with_span_breakdown(caplog):
    from trino_tpu.obs.listeners import SlowQueryLogListener

    spans = (
        {"name": "device/execute", "durationS": 0.9, "attributes": {}},
        {"name": "schedule", "durationS": 0.05, "attributes": {}},
        {"name": "open-span", "durationS": None, "attributes": {}},
    )
    lsn = SlowQueryLogListener(threshold_ms=500)
    with caplog.at_level(logging.WARNING, logger="trino_tpu.slow_query"):
        lsn.query_completed(_completed_event(1.0, spans=spans))
    assert "slow query q42" in caplog.text
    assert "device/execute=900ms" in caplog.text
    assert "schedule=50ms" in caplog.text


def test_slow_query_listener_quiet_under_threshold(caplog):
    from trino_tpu.obs.listeners import SlowQueryLogListener

    lsn = SlowQueryLogListener(threshold_ms=500)
    with caplog.at_level(logging.WARNING, logger="trino_tpu.slow_query"):
        lsn.query_completed(_completed_event(0.1))
    assert caplog.text == ""


def test_slow_query_listener_session_property_override(caplog):
    from trino_tpu.obs.listeners import SlowQueryLogListener

    lsn = SlowQueryLogListener(threshold_ms=500)
    with caplog.at_level(logging.WARNING, logger="trino_tpu.slow_query"):
        # session property RAISES the threshold past this query's wall
        lsn.query_completed(_completed_event(
            1.0, session_properties={"slow_query_log_threshold_ms": "2000"}))
    assert caplog.text == ""
    with caplog.at_level(logging.WARNING, logger="trino_tpu.slow_query"):
        # and LOWERS it below a fast query's wall (header strings coerce)
        lsn.query_completed(_completed_event(
            0.2, session_properties={"slow_query_log_threshold_ms": "100"}))
    assert "slow query q42" in caplog.text


# ------------------------------------------------- compiled-tier tracing
def test_compiled_query_spans_and_compile_cache_counters():
    from trino_tpu.client.session import Session
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.obs import metrics as M

    session = Session({"catalog": "tpch", "schema": "tiny"})
    root = plan_sql(session,
                    "select n_regionkey, count(*) from nation group by n_regionkey")
    hits0 = M.COMPILE_CACHE_HITS.value()
    misses0 = M.COMPILE_CACHE_MISSES.value()
    t = Tracer()
    with tracing.activate(t):
        with tracing.span("query"):
            cq = CompiledQuery.build(session, root)
            cq.run()
            cq.run()  # steady state: reuses the executable
    names = [sp.name for sp in t.spans()]
    assert "device/staging" in names
    assert "device/compile" in names  # first run traced+compiled
    assert "device/execute" in names  # second run reused the executable
    staging = next(sp for sp in t.spans() if sp.name == "device/staging")
    assert staging.attributes["staged_rows"] > 0
    execute = next(sp for sp in t.spans() if sp.name == "device/execute")
    assert execute.attributes["device_seconds"] >= 0
    assert M.COMPILE_CACHE_MISSES.value() >= misses0 + 1
    assert M.COMPILE_CACHE_HITS.value() >= hits0 + 1


# --------------------------------------------- distributed trace + metrics
@pytest.fixture(scope="module")
def cluster():
    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"trace-w{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    assert coord.registry.wait_for_workers(2, timeout=15.0)
    yield coord, workers
    for w in workers:
        w.stop()
    coord.stop()


def _wait_terminal(q, timeout=60.0):
    deadline = time.time() + timeout
    while not q.state.is_terminal() and time.time() < deadline:
        time.sleep(0.05)
    return q.state.get()


def _get_json(url):
    with urllib.request.urlopen(url) as resp:
        return json.loads(resp.read())


def test_distributed_query_produces_single_rooted_trace_tree(cluster):
    coord, workers = cluster
    q = coord.submit(
        "select l_returnflag, count(*) c from lineitem group by l_returnflag"
        " order by l_returnflag",
        {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    trace = _get_json(f"{coord.base_url}/v1/query/{q.query_id}/trace")
    assert trace["queryId"] == q.query_id
    assert trace["traceId"] == q.tracer.trace_id
    root = trace["root"]
    assert root["name"] == "query"
    assert root["attributes"]["query_id"] == q.query_id
    nodes = list(flatten_tree(root))
    # single rooted tree: every collected span is reachable from the root
    assert len(nodes) == trace["spanCount"]
    by_name = {}
    for n in nodes:
        by_name.setdefault(n["name"], []).append(n)
    # coordinator lifecycle spans
    for name in ("parse", "analyze/plan", "optimize", "fragment", "schedule",
                 "execute/root-fragment"):
        assert name in by_name, f"missing coordinator span {name}"
    # worker task spans parent to the coordinator's schedule span via the
    # propagated traceparent header
    schedule = by_name["schedule"][0]
    tasks = by_name["task"]
    assert len(tasks) >= 2  # one per worker on the source fragment at least
    assert {t["parentId"] for t in tasks} == {schedule["spanId"]}
    task_ids = {t["attributes"]["task_id"] for t in tasks}
    assert any(".0." in tid for tid in task_ids)  # source fragment tasks
    # device spans carry row/time attributes
    staging = by_name["device/staging"]
    assert sum(s["attributes"]["staged_rows"] for s in staging) > 0
    execs = by_name["device/execute"]
    assert all("device_seconds" in e["attributes"] for e in execs)
    assert any(e["attributes"].get("staged_rows", 0) > 0 for e in execs)
    # exchange pulls appear on the coordinator (root fragment) side at least
    pulls = by_name["exchange/pull"]
    assert any(p["attributes"].get("bytes", 0) > 0 for p in pulls)
    # spans rode onto QueryCompletedEvent too
    assert any(s["name"] == "schedule" for s in q.tracer.to_dicts())


def test_trace_of_unknown_query_is_404(cluster):
    coord, _ = cluster
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(f"{coord.base_url}/v1/query/nope/trace")
    assert err.value.code == 404


def test_metrics_superset_of_seed_names_with_histogram(cluster):
    coord, workers = cluster
    # ensure at least one terminal query exists for the histogram series
    q = coord.submit("select 1 as x", {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    body = urllib.request.urlopen(coord.base_url + "/v1/metrics").read().decode()
    # seed metric names, byte-compatible
    assert 'trino_tpu_queries{state="FINISHED"}' in body
    assert "trino_tpu_queries_total" in body
    assert "trino_tpu_result_rows" in body
    assert "trino_tpu_workers 2" in body
    assert "trino_tpu_uptime_seconds" in body
    # engine metrics from the registry
    assert "trino_tpu_exchange_bytes_total" in body
    assert "trino_tpu_staging_seconds_total" in body
    assert "trino_tpu_device_seconds_total" in body
    # at least one histogram with populated series
    assert "# TYPE trino_tpu_query_seconds histogram" in body
    assert 'trino_tpu_query_seconds_bucket{state="FINISHED",le="+Inf"}' in body
    assert 'trino_tpu_query_seconds_count{state="FINISHED"}' in body


def test_worker_metrics_endpoint(cluster):
    _, workers = cluster
    body = urllib.request.urlopen(
        workers[0].base_url + "/v1/metrics").read().decode()
    assert "trino_tpu_tasks_total" in body
    assert "# TYPE trino_tpu_staging_seconds_total counter" in body


def test_completed_event_carries_spans(cluster):
    from trino_tpu.server.events import EventListener

    coord, _ = cluster

    class Recorder(EventListener):
        def __init__(self):
            self.completed = []

        def query_completed(self, event):
            self.completed.append(event)

    rec = Recorder()
    coord.events.add(rec)
    q = coord.submit(
        "select count(*) from nation", {"catalog": "tpch", "schema": "tiny"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    deadline = time.time() + 5
    while (not any(e.query_id == q.query_id for e in rec.completed)
           and time.time() < deadline):
        time.sleep(0.05)
    ev = next(e for e in rec.completed if e.query_id == q.query_id)
    names = {s["name"] for s in ev.spans}
    assert "query" in names and "schedule" in names
    assert ev.session_properties.get("catalog") == "tpch"


# --------------------------------------- traceparent under FTE retries
def test_fte_retry_reparents_into_same_trace_exactly_once(tmp_path,
                                                          monkeypatch):
    """Satellite (ISSUE 11): a task whose first attempt FAILS under
    retry_policy=TASK re-parents its retried attempt's spans into the
    SAME query trace exactly once — the assembled tree holds ONE task
    span for the retried slot (the winning attempt), no duplicate
    subtree from the failed attempt, all under the coordinator's
    schedule span."""
    monkeypatch.setenv("TRINO_TPU_SPOOL_DIR", str(tmp_path / "spool"))
    coord = CoordinatorServer()
    coord.start()
    workers = [
        WorkerServer(coordinator_url=coord.base_url, node_id=f"ftetr{i}")
        for i in range(2)
    ]
    for w in workers:
        w.start()
    try:
        assert coord.registry.wait_for_workers(2, timeout=15.0)
        q = coord.submit(
            "select o_orderpriority, count(*) c from orders group by "
            "o_orderpriority order by o_orderpriority",
            {"catalog": "tpch", "schema": "tiny",
             "retry_policy": "TASK",
             # first attempt of slot 0 of the source fragment fails
             "failure_injection": ".0.0.a0"})
        assert _wait_terminal(q) == "FINISHED", q.failure
        assert any(t.endswith(".0.0.a0") for t in q.retried_tasks)
        trace = _get_json(f"{coord.base_url}/v1/query/{q.query_id}/trace")
        nodes = list(flatten_tree(trace["root"]))
        tasks = [n for n in nodes if n["name"] == "task"]
        task_ids = [t["attributes"]["task_id"] for t in tasks]
        # exactly one task span per SLOT: the retried slot appears once,
        # as its winning attempt (a1), never the failed a0
        slots = [tid.rsplit(".a", 1)[0] for tid in task_ids]
        assert len(slots) == len(set(slots)), task_ids
        retried_slot = f"{q.query_id}.0.0"
        winning = [tid for tid in task_ids
                   if tid.rsplit(".a", 1)[0] == retried_slot]
        assert winning == [f"{retried_slot}.a1"], task_ids
        assert not any(tid.endswith(".0.0.a0") for tid in task_ids)
        # every task span (including the retry) parents into THIS trace's
        # schedule span — the retried attempt re-propagated the same
        # traceparent, so nothing dangles or re-roots
        by_name = {}
        for n in nodes:
            by_name.setdefault(n["name"], []).append(n)
        schedule_ids = {s["spanId"] for s in by_name["schedule"]}
        assert {t["parentId"] for t in tasks} <= schedule_ids
        assert trace["spanCount"] == len(nodes)  # single-rooted, lossless
    finally:
        for w in workers:
            w.stop()
        coord.stop()


def test_process_self_metrics_on_both_servers(cluster):
    """Satellite (ISSUE 11): RSS / FDs / threads / GC gauges refresh on
    every render — the host-sick-vs-engine-slow discriminators, on
    coordinator AND worker /v1/metrics."""
    coord, workers = cluster
    for url in (coord.base_url, workers[0].base_url):
        body = urllib.request.urlopen(url + "/v1/metrics").read().decode()
        for name in ("trino_tpu_process_rss_bytes",
                     "trino_tpu_process_open_fds",
                     "trino_tpu_process_threads"):
            line = next(l for l in body.splitlines()
                        if l.startswith(name + " "))
            assert float(line.split()[-1]) > 0, line
        assert 'trino_tpu_process_gc_collections{generation="0"}' in body
    # and as rows through system.metrics
    q = coord.submit(
        "select name, value from system.metrics "
        "where name = 'trino_tpu_process_rss_bytes'", {})
    assert _wait_terminal(q) == "FINISHED", q.failure
    assert q.rows and q.rows[0][1] > 0


# ------------------------------------- one clock, stored spans, GC pauses
def test_spans_are_profiler_annotations_on_the_trace_clock(tmp_path):
    """Every span is also a ``jax.profiler.TraceAnnotation`` of its name: a
    profiler session holds the program's spans in the xplane, so they can
    be read in Perfetto above the device operations."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t = Tracer()
        with t.span("operator/Probe"):
            opened = t.start_span("exchange/pull")
            time.sleep(0.002)
            t.end_span(opened)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                names[ev.name] = ev.duration_ns
    assert "operator/Probe" in names and "exchange/pull" in names
    assert names["exchange/pull"] >= 2e6   # the span's own interval, in ns
    assert names["operator/Probe"] >= names["exchange/pull"]


def test_record_span_stores_a_finished_span_under_the_ambient_parent():
    t = Tracer()
    with t.span("operator/Filter") as parent:
        tracing.record("host/sync", 100.0, 0.25, site="compact", bytes=8)
    tracing.record("host/sync", 200.0, 0.25)   # no tracer: nothing, no error
    by_name = {s["name"]: s for s in t.to_dicts()}
    sync = by_name["host/sync"]
    assert sync["parentId"] == parent.span_id
    assert (sync["start"], sync["durationS"]) == (100.0, 0.25)
    assert sync["attributes"] == {"site": "compact", "bytes": 8}
    # the cap holds for recorded spans too
    small = Tracer(max_spans=1)
    small.record_span("host/sync", 1.0, 0.1)
    small.record_span("host/sync", 2.0, 0.1)
    assert len(small.spans()) == 1 and small.dropped_spans == 1


def test_gc_recorder_keeps_pauses_and_hands_out_overlapping_spans():
    rec = tracing.GcRecorder(capacity=3)
    rec("start", {"generation": 2})
    time.sleep(0.002)
    t_mid = time.time()
    rec("stop", {"generation": 2, "collected": 0})
    rec("start", {"generation": 0})
    rec("stop", {"generation": 0})            # far under 50 us: counted only
    (start, pause, gen), = list(rec.pauses)
    assert gen == 2 and pause >= 0.002 and start <= t_mid <= start + pause + 1e-3
    assert rec.total_s[2] == pytest.approx(pause) and 0 in rec.total_s
    (span,) = rec.spans_between(start - 1, start + 1, parent_id="root")
    assert span["name"] == "process/gc" and span["parentId"] == "root"
    assert span["attributes"] == {"generation": 2}
    assert span["durationS"] == pytest.approx(pause, abs=1e-6)
    assert rec.spans_between(start + pause + 1, start + pause + 2) == []
    for _ in range(5):                         # a bounded ring
        rec.pauses.append((0.0, 1.0, 1))
    assert len(rec.pauses) == 3


def test_a_forced_collection_inside_a_statement_shows_as_gc_pause(
        cluster, monkeypatch):
    """The process's collector pauses that overlap a statement's wall join
    its span export: ``process/gc`` in the trace, ``<phase>/gc-pause`` in
    the ledger's detail."""
    import gc

    from trino_tpu.exec.executor import Executor

    coord, _ = cluster
    real = Executor.execute_checked

    def collect_first(self, node):
        gc.collect()
        return real(self, node)

    monkeypatch.setattr(Executor, "execute_checked", collect_first)
    q = coord.submit(
        "select count(*) from orders where o_totalprice > 1000",
        {"catalog": "tpch", "schema": "tiny",
         "result_cache_enabled": "false"})
    assert _wait_terminal(q) == "FINISHED", q.failure
    tl = q.timeline_dict()
    paused = {k: v for k, v in tl["detail"].items() if k.endswith("/gc-pause")}
    assert paused and sum(paused.values()) > 0, tl["detail"]
    trace = _get_json(f"{coord.base_url}/v1/query/{q.query_id}/trace")
    pauses = [n for n in flatten_tree(trace["root"])
              if n["name"] == "process/gc"]
    assert any(n["attributes"]["generation"] == 2 for n in pauses)
    assert trace["root"]["name"] == "query", "pauses hang under the root"
    # level one did not move: the 14 phases still sum to the wall
    in_wall = sum(v for p, v in tl["phases"].items()
                  if p not in ("client-drain", "segment-fetch"))
    assert in_wall == pytest.approx(tl["wallS"], abs=2e-5)
    # and the registry has the seconds, by generation
    text = urllib.request.urlopen(f"{coord.base_url}/v1/metrics").read().decode()
    assert 'trino_tpu_gc_pause_seconds_total{generation="2"}' in text


def test_back_to_back_reads_of_one_site_are_one_span():
    t = Tracer()
    with t.span("task/output") as parent:
        tracing.record_burst("host/sync", 10.0, 0.001, "compact", 4)
        tracing.record_burst("host/sync", 10.0015, 0.001, "compact", 8)   # 0.5 ms on
        tracing.record_burst("host/sync", 10.003, 0.001, "serialize", 2)  # another site
        tracing.record_burst("host/sync", 10.1, 0.001, "serialize", 2)    # 96 ms on
    with t.span("task/output"):
        tracing.record_burst("host/sync", 10.1015, 0.001, "serialize", 2)  # another parent
    tracing.record_burst("host/sync", 11.0, 0.001, "compact", 1)           # no tracer
    syncs = [s for s in t.to_dicts() if s["name"] == "host/sync"]
    assert [(s["start"], s["attributes"]) for s in syncs] == [
        (10.0, {"site": "compact", "reads": 2, "bytes": 12}),
        (10.003, {"site": "serialize", "reads": 1, "bytes": 2}),
        (10.1, {"site": "serialize", "reads": 1, "bytes": 2}),
        (10.1015, {"site": "serialize", "reads": 1, "bytes": 2}),
    ]
    assert syncs[0]["durationS"] == pytest.approx(0.002)   # the reads alone
    assert syncs[0]["parentId"] == parent.span_id
