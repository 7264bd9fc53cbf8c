"""Coordinator: dispatch, discovery, scheduling, and the client protocol.

Reference: ``dispatcher/QueuedStatementResource.java:103`` +
``dispatcher/DispatchManager.java:173`` (statement submission),
``execution/SqlQueryExecution.java:393`` (analyze→plan→schedule),
``metadata/DiscoveryNodeManager.java:68`` +
``failuredetector/HeartbeatFailureDetector.java:76`` (membership/liveness),
``server/remotetask/HttpRemoteTask.java:132`` (task CRUD client),
``server/protocol/ExecutingStatementResource.java:69`` (paged results with
``nextUri`` chaining).

Scheduling model (walking skeleton of PipelinedQueryScheduler): every
*source* fragment gets one task per alive worker with splits round-robin
assigned (UniformNodeSelector analog); all stages are scheduled at once and
stream through long-polled output buffers (phased scheduling is a later
refinement); the root *single* fragment executes on the coordinator itself,
pulling upstream pages with the exchange client.
"""
from __future__ import annotations

import itertools
import json
import re
import threading
import time
import traceback
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from trino_tpu.obs import trace as tracing
from trino_tpu.server import wire
from trino_tpu.server.exchange_client import ExchangeClient, TaskLocation
from trino_tpu.server.statemachine import StateMachine, query_state_machine
from trino_tpu.server.task import TaskRequest
from trino_tpu.sql.planner import plan as P
from trino_tpu.sql.planner.fragmenter import RemoteSourceNode, fragment_plan

_ANNOUNCE_RE = re.compile(r"^/v1/announce/([^/]+)$")
_RESULT_RE = re.compile(r"^/v1/statement/executing/([^/]+)/(\d+)$")
_QUERY_RE = re.compile(r"^/v1/query/([^/]+)$")
_TRACE_RE = re.compile(r"^/v1/query/([^/]+)/trace$")
_PROFILE_RE = re.compile(r"^/v1/query/([^/]+)/profile$")
_FLOWS_RE = re.compile(r"^/v1/query/([^/]+)/flows$")
_SEGMENT_RE = re.compile(r"^/v1/segment/([^/]+)$")

RESULT_PAGE_ROWS = 10_000

# sentinel returned by QueryExecution._consult_result_cache when the query
# was answered from the result cache (columns/rows already populated)
_SERVED_FROM_CACHE = "__served_from_cache__"


class NodeRegistry:
    """Worker membership with announce-age liveness (discovery + failure
    detection collapsed: an entry not re-announced within ``max_age`` is
    dead — the push analog of heartbeat ping + decayed failure ratio)."""

    def __init__(self, max_age: float = 10.0):
        self._nodes: Dict[str, dict] = {}
        self._lock = threading.Lock()
        self.max_age = max_age

    def announce(self, node_id: str, url: str,
                 info: Optional[dict] = None) -> None:
        with self._lock:
            self._nodes[node_id] = {"url": url, "last_seen": time.monotonic(),
                                    "info": dict(info or {})}

    def alive(self) -> List[dict]:
        now = time.monotonic()
        with self._lock:
            return [
                {"nodeId": nid, **info}
                for nid, info in sorted(self._nodes.items())
                if now - info["last_seen"] <= self.max_age
            ]

    def snapshot(self) -> List[dict]:
        """Every known node with its last announce payload and heartbeat
        age — including DEAD entries (announce aged out), which the
        ``system.runtime.nodes`` table surfaces instead of hiding."""
        now = time.monotonic()
        with self._lock:
            return [
                {"nodeId": nid, "url": info["url"],
                 "info": dict(info.get("info") or {}),
                 "ageS": now - info["last_seen"],
                 "alive": now - info["last_seen"] <= self.max_age}
                for nid, info in sorted(self._nodes.items())
            ]

    def wait_for_workers(self, count: int, timeout: float = 30.0) -> bool:
        """ClusterSizeMonitor analog: block dispatch until enough workers."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(self.alive()) >= count:
                return True
            time.sleep(0.1)
        return False


class QueryExecution:
    """One query's lifecycle on the coordinator."""

    def __init__(self, query_id: str, sql: str, session_properties: dict,
                 registry: NodeRegistry, session_factory, user: str = "anonymous",
                 query_cache=None, prepared_registry=None):
        self.query_id = query_id
        self.sql = sql
        self.user = user
        self.session_properties = dict(session_properties)
        self.state: StateMachine[str] = query_state_machine()
        self.registry = registry
        self.session_factory = session_factory
        # server-wide QueryCache (trino_tpu/cache/) or None (caching off)
        self.query_cache = query_cache
        # server-wide PreparedStatementRegistry (server/prepared.py) or
        # None: PREPARE registers, EXECUTE binds + runs, DEALLOCATE drops
        self.prepared_registry = prepared_registry
        # which control-plane path executed the SELECT: "fast-path"
        # (single-stage plan run coordinator-local), "distributed",
        # "local-catalog" (process-local catalog forced local), or None
        # (non-SELECT / served from the result cache)
        self.fast_path: Optional[str] = None
        # PREPARE/DEALLOCATE round-trip to the client (the
        # X-Trino-Added-Prepare / X-Trino-Deallocated-Prepare analog,
        # carried in the result payload like set/reset session)
        self.add_prepared: Dict[str, str] = {}
        self.deallocated_prepared: List[str] = []
        # result-cache disposition, surfaced as X-Trino-Tpu-Cache:
        # HIT (served from cache / a concurrent leader), MISS (executed,
        # filled the cache), BYPASS (ineligible or cache disabled)
        self.cache_status: Optional[str] = None
        self.failure: Optional[str] = None
        self.columns: List[str] = []
        self.rows: List[tuple] = []
        # SET/RESET SESSION results: the protocol carries them back to the
        # client, which applies them to its subsequent requests (reference:
        # the X-Trino-Set-Session / X-Trino-Clear-Session headers) — the
        # coordinator itself is stateless per query.
        self.set_session: Dict[str, object] = {}
        self.reset_session: List[str] = []
        # FTE bookkeeping: successful attempt index per task + retried ids
        self.task_attempts: Dict[str, int] = {}
        self.retried_tasks: List[str] = []
        # IN-FLIGHT duplicate straggler attempts: entries are pruned when
        # their slot resolves (the speculated task or its original
        # completes), so long queries can't grow this without bound
        self.speculative_tasks: List[str] = []
        # bounded record of every speculation launched (observability/tests)
        from collections import deque

        self.speculation_history = deque(maxlen=64)
        self.fragment_tasks: Dict[int, List[TaskLocation]] = {}
        # distributed stats pipeline (reference: QueryStats/StageStats fed
        # by TaskStatus updates): worker-reported task stats keyed by task
        # SLOT (query.fragment.worker — retried attempts replace their
        # slot), folded into per-stage and per-query rollups on read.
        # Populated by the status-polling loop + the task-create response;
        # a FINISHED attempt's record is never downgraded, so stats freeze
        # naturally once the query reaches a terminal state.
        self.task_stats: Dict[str, dict] = {}
        self._tstats_lock = threading.Lock()
        # fragments of the last distributed execution (EXPLAIN ANALYZE
        # rendering + stage count); None for coordinator-local queries
        self.fragments = None
        # versioned plan changes applied by the adaptive re-planner
        # (trino_tpu/adaptive/), surfaced via GET /v1/query/{id} and the
        # EXPLAIN ANALYZE [adapted: ...] annotations
        self.plan_versions: List[dict] = []
        self.created_at = time.time()
        self.ended_at: Optional[float] = None
        # one trace per query; the trace id doubles as the propagation key
        # stamped on worker/exchange requests (reference: the otel Tracer
        # injected into DispatchManager + the traceparent headers of the
        # internal HTTP clients)
        self.tracer = tracing.Tracer()
        # the coordinator's flight recorder (obs/flightrecorder.py), set
        # by CoordinatorServer.submit — the tracer mirrors closed spans
        # into it, and the FAILED postmortem snapshots it
        self.recorder = None
        # merged coordinator+worker flight-recorder postmortem, captured
        # at FAILED (GET /v1/query/{id}/trace?recorder=1 + the query log)
        self.postmortem: Optional[dict] = None
        # completion-time phase ledger (obs/timeline.QueryTimeline),
        # computed once from the merged span tree and cached
        self._timeline = None
        # when the client last fetched a FINISHED result page — feeds the
        # ledger's client-drain phase (outside the query wall)
        self.last_drain_at: Optional[float] = None
        # dispatch/executor split (server/dispatch.py): which plane ran
        # this query ("dispatch-lane" inline, "executor-process:N" when
        # forwarded), the queue-residency span the lane closes on
        # dequeue, and spans pulled from an executor process's trace
        # (merged into the ledger and the trace endpoint)
        self.plane: str = "dispatch-lane"
        self._dispatch_queue_span = None
        self.extra_spans: List[dict] = []
        # resource-group admission (server/resource_groups.py): the full
        # dotted group path this query was classified into by the
        # selector chain (None under an injected legacy gate), and the
        # client-reported source the selectors may route on
        # (X-Trino-Source); queued-ahead count captured at enqueue
        self.resource_group: Optional[str] = None
        self.source: str = ""
        self.queued_ahead: Optional[int] = None
        # set by the server at submit: the shared IO thread pool for
        # parallel worker pulls (span dumps, flight-recorder rings) and
        # the dispatcher completion hook
        self.io_pool = None
        self.dispatcher = None
        # serving-index learning (dispatch.ServingIndex): whether the
        # statement was a plain SELECT shape, and the result-cache key +
        # captured data versions of a led flight
        self.is_plain_select = False
        self.result_cache_key: Optional[str] = None
        self.result_cache_versions = None
        # materialized-view substitutions applied to this query's plan
        # (qualified view names, in decision order) + the full decision
        # notes — queryStats.mvHits/mvNames and EXPLAIN ANALYZE headers
        self.mv_substitutions: List[str] = []
        self.mv_notes: List[dict] = []
        # spooled result protocol (server/segments.py): when the query's
        # results went to segments, the statement response carries this
        # MANIFEST ({uri, ackUri, id, rows, bytes, codec} per segment)
        # instead of inline rows; ``spooled`` records which producer
        # wrote them ("worker-direct" — root-fragment tasks, the
        # coordinator never touched the data — or "coordinator")
        self.result_segments: Optional[List[dict]] = None
        self.spooled: Optional[str] = None
        # segment id -> owning worker base url (ack forwarding + early
        # discard); empty for coordinator-spooled queries
        self._segment_workers: Dict[str, str] = {}
        # set by CoordinatorServer.submit: this coordinator's segment
        # store + public base url (None for bare embedded executions,
        # which then never spool)
        self.segment_store = None
        self.segment_base_url: Optional[str] = None
        # when a client last fetched/acked a result segment through this
        # coordinator — feeds the ledger's segment-fetch phase (outside
        # the query wall, beside client-drain)
        self.last_segment_fetch_at: Optional[float] = None
        # query_max_execution_time: the timer that ends this statement
        # through kill() once it has executed for that long (armed when
        # the lifecycle starts, disarmed when run() returns), and the root
        # span it marks
        self._time_limit_timer: Optional[threading.Timer] = None
        self._root_span = None

    def start(self) -> None:
        """Run the lifecycle on a fresh thread (legacy surface — the
        server's executor lanes call ``run()`` inline instead)."""
        self._thread = threading.Thread(target=self.run, daemon=True)
        self._thread.start()

    def cancel(self) -> None:
        self.ended_at = self.ended_at or time.time()
        if self.state.set("CANCELED"):
            self._cancel_tasks()

    def kill(self, reason: str) -> None:
        """Administrative kill (low-memory killer): FAILED with the given
        reason; running tasks are canceled (reference:
        QueryExecution.fail from ClusterMemoryManager's killer)."""
        self.failure = reason
        self.ended_at = self.ended_at or time.time()
        if self.state.set("FAILED"):
            self._cancel_tasks()

    def _arm_time_limit(self, session) -> None:
        """``query_max_execution_time``: from now (the statement has left
        the queue) it may execute for that long; then the coordinator ends
        it through :meth:`kill` (FAILED, tasks cancelled, error name
        ``EXCEEDED_TIME_LIMIT``) and keeps serving (reference:
        QueryTracker.enforceTimeLimits over query.max-execution-time)."""
        text = session.properties.get("query_max_execution_time")
        if text is None:
            return
        from trino_tpu.client.properties import parse_duration

        def expire() -> None:
            if self.state.is_terminal():
                return
            from trino_tpu.obs import metrics as M

            M.QUERIES_TIME_LIMITED.inc()
            if self._root_span is not None:
                self._root_span.set("time-limit", text)
            self.kill(f"Query exceeded the maximum execution time limit "
                      f"of {text} (EXCEEDED_TIME_LIMIT)")

        timer = threading.Timer(parse_duration(text), expire)
        timer.daemon = True
        self._time_limit_timer = timer
        timer.start()

    # ------------------------------------------------------------ lifecycle
    def run(self) -> None:
        root_span = self._root_span = self.tracer.start_span(
            "query", query_id=self.query_id, user=self.user)
        # the dispatch-queue span opened before this root existed (the
        # HTTP thread enqueued, a lane dequeued): adopt it so the trace
        # tree stays single-rooted under the query span
        qs = getattr(self, "_dispatch_queue_span", None)
        if qs is not None:
            qs.parent_id = root_span.span_id
        try:
            with tracing.activate(self.tracer, root_span.span_id):
                self._run_lifecycle()
            # close the trace BEFORE the terminal transition: the state
            # machine's listeners (QueryCompletedEvent) snapshot the spans,
            # and a query that completes on THIS thread must carry its
            # duration by then (a cancel/kill from another thread still
            # fires with whatever was recorded at that instant)
            self.tracer.end_span(root_span)
            # stop the stats clock BEFORE the terminal transition so a poll
            # racing the state change never reads a live elapsed time on a
            # terminal query
            self.ended_at = time.time()
            # warm the phase ledger on THIS thread before the terminal
            # transition: the compute pulls worker span dumps over HTTP,
            # and the state listeners (history recording, events) must
            # stay fast — they read the cached result
            self._warm_timeline()
            self.state.set("FINISHED")
        except Exception as e:  # noqa: BLE001 — reported through query info
            self.ended_at = self.ended_at or time.time()
            if self.failure is None:
                # an administrative kill() may already have set the real
                # reason; the task-cancellation fallout must not clobber it
                self.failure = f"{e}\n{traceback.format_exc()}"
            root_span.set("error", str(e).split("\n")[0][:300])
            self._cancel_tasks()
            self.tracer.end_span(root_span)
            self._warm_timeline()
            # capture the flight-recorder postmortem BEFORE FAILED is
            # visible (same fast-listener contract as the ledger): the
            # workers' rings still hold the context around the failure
            try:
                self.capture_postmortem(
                    timeout=self.COMPLETION_PULL_TIMEOUT)
            except Exception:  # noqa: BLE001 — best-effort forensics
                pass
            self.state.set("FAILED")
        finally:
            if self._time_limit_timer is not None:
                self._time_limit_timer.cancel()
            self.ended_at = self.ended_at or time.time()
            self.tracer.end_span(root_span)  # idempotent safety net
            # the latch decides: a kill()/cancel() racing this thread may
            # already have set CANCELED/FAILED — record what actually stuck
            root_span.set("state", self.state.get())
            self._cleanup_spool()

    def _run_lifecycle(self) -> None:
        """The coordinator half of the query, span-per-phase (reference:
        SqlQueryExecution.start's analyze -> plan -> schedule with otel
        spans around each)."""
        self.state.set("PLANNING")
        session = self.session_factory(self.session_properties)
        from trino_tpu.server.security import Identity

        session.identity = Identity(self.user)
        # procedures (CALL) resolve the calling query through the session:
        # system.runtime.kill_query refuses to kill its own query
        session.query_id = self.query_id
        self._arm_time_limit(session)
        from trino_tpu.exec.query import run_query
        from trino_tpu.sql.parser import ast
        from trino_tpu.sql.parser.parser import parse_statement

        # statement-kind probe, unspanned: plan_sql re-parses under its own
        # "parse" span, and two parse spans would double-attribute the time
        stmt = parse_statement(self.sql)
        if (isinstance(stmt, ast.Explain) and stmt.analyze
                and isinstance(stmt.statement, ast.Query)):
            # distributed EXPLAIN ANALYZE: run the statement through the
            # real fragment/schedule/execute path, then print the fragments
            # annotated with the workers' rolled-up OperatorStats — no
            # coordinator-local re-execution (reference:
            # ExplainAnalyzeOperator consuming the stage stats it ran under)
            self.cache_status = "BYPASS"
            text = self._explain_analyze(session, stmt)
            # the deliverable is the annotated plan, not the inner
            # query's rows: release any segments the execution spooled
            self._discard_spooled_result()
            self.columns = ["Query Plan"]
            self.rows = [(line,) for line in text.split("\n")]
            return
        if isinstance(stmt, (ast.Prepare, ast.ExecutePrepared,
                             ast.Deallocate)) \
                and self.prepared_registry is not None:
            # the serving surface (server/prepared.py): PREPARE registers
            # against the server-wide registry (per-user), EXECUTE binds
            # into the cached parameterized plan, DEALLOCATE drops — none
            # of this can run on the throwaway per-query session, whose
            # state dies with this statement
            self._run_prepared_statement(session, stmt)
            return
        if isinstance(stmt, (ast.CreateMaterializedView,
                             ast.RefreshMaterializedView,
                             ast.DropMaterializedView)):
            # materialized views (trino_tpu/matview/): the REFRESH's
            # defining query executes through the NORMAL path
            # (_execute_query: fast-path / local-catalog / distributed),
            # then the rows swap into the storage table and the registry
            # change replicates to the executor-process plane
            self._run_mv_statement(session, stmt, self.sql)
            return
        if not isinstance(stmt, ast.Query):
            # metadata statements (SHOW …, EXPLAIN), CALL, and DML/DDL run
            # coordinator-local and always bypass the result cache — the
            # mutation itself is what bumps the connector data versions
            # that invalidate cached SELECTs over the touched tables
            self.cache_status = "BYPASS"
            self.state.set("RUNNING")
            with self.tracer.span("execute/coordinator-local"):
                result = run_query(session, self.sql)
            self.columns, self.rows = result.column_names, result.rows
            if isinstance(stmt, ast.SetSession):
                # run_query validated+coerced it on the throwaway session
                self.set_session[stmt.name] = session.properties[stmt.name]
            elif isinstance(stmt, ast.ResetSession):
                self.reset_session.append(stmt.name)
            return
        self.is_plain_select = True
        root, versions = self._plan_query(session, stmt)
        root, versions = self._substitute_matviews(session, root, versions)
        key = self._consult_result_cache(session, stmt, root, versions)
        self._finish_with_result_cache(session, root, key)

    # ------------------------------------------------- materialized views
    def _run_mv_statement(self, session, stmt, sql) -> None:
        """CREATE / REFRESH / DROP MATERIALIZED VIEW on the coordinator.
        The refresh's defining query runs through ``_execute_query`` so
        big definitions fragment and schedule across workers exactly like
        a user SELECT; the materialized rows then swap into the storage
        table (matview/lifecycle.py owns the version bookkeeping).
        ``sql`` is the CREATE statement's own text (the prepared path
        passes the registered inner text, or None when bound parameters
        made the stored text no longer describe the bound AST)."""
        from trino_tpu.matview import lifecycle as mv_lifecycle

        self.cache_status = "BYPASS"

        def execute_fn(root):
            # the refresh consumes materialized rows: spooled manifests
            # would leave them in segments nobody decodes server-side
            session.properties["spooled_results_enabled"] = False
            self._execute_query(session, root)
            rows, self.rows = self.rows, []
            return rows

        # under the executor-process plane, substituted SELECTs run in
        # the children — warming THIS process's device cache on refresh
        # would stage a table no query here ever scans
        warm = getattr(self.dispatcher, "process_plane", None) is None
        columns, rows = mv_lifecycle.dispatch_mv_statement(
            session, stmt, sql=sql, execute_fn=execute_fn, warm=warm)
        if self.state.get() in ("QUEUED", "PLANNING", "STARTING"):
            self.state.set("RUNNING")
        self.columns, self.rows = columns, rows
        self._replicate_mv_change(session, stmt)

    def _replicate_mv_change(self, session, stmt) -> None:
        """Process plane only: ship the registry mutation to every booted
        executor process (``CALL system.runtime.sync_materialized_view``
        with a base64 payload), so sticky-routed SELECTs substitute — or
        stop substituting — there too. Best-effort, like the prepared-
        registry broadcast."""
        pp = getattr(self.dispatcher, "process_plane", None)
        if pp is None:
            return
        import base64
        import json as _json

        from trino_tpu.matview import registry as mv_registry
        from trino_tpu.matview.lifecycle import resolve_mv_name
        from trino_tpu.sql.parser import ast

        catalog, schema, name = resolve_mv_name(session, stmt.name)
        if isinstance(stmt, ast.DropMaterializedView):
            payload = mv_registry.drop_payload(catalog, schema, name)
        else:
            mv = session.matviews.get(catalog, schema, name)
            if mv is None or mv.definition_sql is None:
                return
            payload = mv_registry.to_payload(mv)
        blob = base64.b64encode(
            _json.dumps(payload).encode()).decode()
        # signed with the cluster-internal secret (children inherit it
        # via their spawn env): the receiving procedure rejects anything
        # an ordinary client could forge
        sig = wire.sign(blob.encode())
        pp.broadcast(
            f"CALL system.runtime.sync_materialized_view('{blob}', "
            f"'{sig}')",
            self.user, self.session_properties)

    def _substitute_matviews(self, session, root, versions):
        """The MV substitution pass, applied AFTER the plan cache (a
        cached plan must stay substitution-free — freshness varies per
        execution; the pass copies-on-write, never mutating the cached
        tree) with the captured versions recomputed for the result-cache
        key: the substituted plan's own scans (storage + any remaining
        base scans) UNION the views' recorded base versions, so a
        REFRESH and a base-table DML both invalidate cached results."""
        from trino_tpu.matview.substitute import (
            substitute_plan, substitution_versions)

        new_root, notes = substitute_plan(session, root)
        self.mv_notes = notes
        self.mv_substitutions = [
            n["view"] for n in notes if n["result"] == "substituted"]
        if not self.mv_substitutions:
            return root, versions
        return new_root, substitution_versions(session, new_root, notes)

    def _finish_with_result_cache(self, session, root, key) -> None:
        """Shared tail of the SELECT lifecycle: serve/lead/bypass against
        the result cache, executing through ``_execute_query`` otherwise.
        A leader that fails abandons its flight (waiters re-execute)."""
        if key == _SERVED_FROM_CACHE:
            self.state.set("FINISHING")
            return
        if key is None:
            self._execute_query(session, root)
            return
        try:
            self._execute_query(session, root)
        except BaseException:
            self.query_cache.results.abandon(key)
            raise
        if self.result_segments is not None:
            # spooled results never enter the result cache: the rows were
            # deliberately never materialized on this coordinator —
            # abandon the flight so single-flight waiters re-execute
            # instead of inheriting an empty payload
            self.query_cache.results.abandon(key)
            return
        self.query_cache.results.complete(
            key, self.columns, self.rows,
            ttl_ms=session.properties.get("result_cache_ttl_ms", 60_000),
            max_bytes=session.properties.get("result_cache_max_bytes"))

    # ------------------------------------------------- prepared statements
    def _run_prepared_statement(self, session, stmt) -> None:
        """PREPARE / EXECUTE / DEALLOCATE against the server-wide registry
        (reference: PrepareTask/DeallocateTask + the EXECUTE rewrite of
        QueuedStatementResource, collapsed onto the query thread)."""
        from trino_tpu.sql.parser import ast

        reg = self.prepared_registry
        if isinstance(stmt, ast.Prepare):
            self.cache_status = "BYPASS"
            self.state.set("RUNNING")
            inner = stmt.statement
            if isinstance(inner, (ast.Prepare, ast.ExecutePrepared,
                                  ast.Deallocate)):
                raise ValueError(
                    "cannot PREPARE another prepared-statement control "
                    "statement")
            # the inner statement's text, for display surfaces: the PREPARE
            # grammar is rigid, so stripping the one fixed prefix is exact
            m = re.match(r"(?is)^\s*prepare\s+\S+\s+from\s+(.*)$",
                          self.sql.strip())
            sql_text = (m.group(1) if m else self.sql).strip()
            reg.put(self.user, stmt.name, inner, sql_text)
            self.add_prepared[stmt.name] = sql_text
            self.columns, self.rows = ["result"], [("PREPARE",)]
            self._replicate_registry_change()
            return
        if isinstance(stmt, ast.Deallocate):
            self.cache_status = "BYPASS"
            self.state.set("RUNNING")
            if not reg.remove(self.user, stmt.name):
                raise ValueError(
                    f"prepared statement not found: {stmt.name}")
            self.deallocated_prepared.append(stmt.name)
            self.columns, self.rows = ["result"], [("DEALLOCATE",)]
            self._replicate_registry_change()
            return
        self._run_execute_prepared(session, stmt)

    def _replicate_registry_change(self) -> None:
        """Process plane only: replay this PREPARE/DEALLOCATE on every
        executor process so their replica registries track the dispatch
        process's authoritative one (the owner of the structure)."""
        pp = getattr(self.dispatcher, "process_plane", None)
        if pp is not None:
            pp.broadcast(self.sql, self.user, self.session_properties)

    def _run_execute_prepared(self, session, stmt) -> None:
        """EXECUTE name [USING ...]: constant-fold the bindings, reuse (or
        create) the ONE cached parameterized plan for this statement+type
        signature, substitute the bound constants into a copy, and run it
        through the normal result-cache + execution pipeline. The second
        EXECUTE of a statement does zero parse/analyze/plan/optimize work
        — only the bind pass (microseconds) and execution."""
        from trino_tpu.obs import metrics as M
        from trino_tpu.server import prepared as prep
        from trino_tpu.sql.parser import ast

        ps = self.prepared_registry.get(self.user, stmt.name)
        if ps is None:
            raise ValueError(f"prepared statement not found: {stmt.name}")
        inner = ps.statement
        # bind step 1 — fold + arity: USING arguments must be constant
        # expressions whatever the inner statement kind, and the
        # executions counter only moves once the binding is valid
        t0 = time.perf_counter()
        with self.tracer.span("prepare/bind") as sp:
            sp.set("statement", stmt.name)
            sp.set("step", "fold")
            values = prep.fold_execute_args(stmt.params)
            prep.check_arity(ps, values)
            sp.set("parameters", len(values))
        fold_s = time.perf_counter() - t0
        self.prepared_registry.touch(self.user, stmt.name)
        if not isinstance(inner, ast.Query):
            # prepared DML/DDL/metadata: bind at the AST level (the raw
            # USING exprs, proven constant above) and run coordinator-
            # local — the mutation bumps data versions exactly like the
            # unprepared spelling
            from trino_tpu.exec.query import bind_parameters
            from trino_tpu.exec.query import dispatch_statement

            self.cache_status = "BYPASS"
            bound = bind_parameters(inner, stmt.params)
            M.EXECUTE_BIND_SECONDS.observe(fold_s)
            if isinstance(bound, (ast.CreateMaterializedView,
                                  ast.RefreshMaterializedView,
                                  ast.DropMaterializedView)):
                # prepared MV DDL takes the SAME path as the unprepared
                # spelling: distributed refresh + executor-plane registry
                # replication. The registered inner text serves as the
                # definition SQL; with bound parameters the stored text no
                # longer describes the bound AST, so replication (which
                # ships definitions as SQL) degrades to local-only
                self._run_mv_statement(
                    session, bound,
                    ps.sql if not stmt.params else None)
                return
            self.state.set("RUNNING")
            with self.tracer.span("execute/coordinator-local"):
                result = dispatch_statement(session, bound)
            self.columns, self.rows = result.column_names, result.rows
            return
        self.is_plain_select = True
        ptypes = tuple(c.type for c in values)
        # planning (plan-cache miss only) stays OUTSIDE the bind timer and
        # span: trino_tpu_execute_bind_seconds measures exactly the
        # per-request work a warm EXECUTE pays (fold + substitute)
        root, versions = self._plan_prepared(session, ps, ptypes)
        t1 = time.perf_counter()
        with self.tracer.span("prepare/bind") as sp:
            sp.set("step", "substitute")
            bound_root = prep.bind_plan_parameters(root, values)
        M.EXECUTE_BIND_SECONDS.observe(
            fold_s + (time.perf_counter() - t1))
        # per-binding consult metadata, computed ONCE per parameterized
        # plan OBJECT (a replanned/evicted plan is a new object, so this
        # can never serve a stale canonical): the determinism verdict and
        # the canonical plan string are binding-independent — only the
        # bound values (in `extra`) and data versions vary per request
        meta = getattr(root, "_consult_meta", None)
        if meta is None:
            from trino_tpu.cache.determinism import uncachable_reason
            from trino_tpu.cache.plan_key import canonicalize_plan

            reason = uncachable_reason(inner, root)
            meta = (reason,
                    canonicalize_plan(root) if reason is None else None)
            root._consult_meta = meta
        binding = "params=" + repr(
            [(str(c.type), repr(c.value)) for c in values])
        # MV substitution on the BOUND plan (outside the bind timer): the
        # result-cache key stays the parameterized canonical — still
        # correct because the merged versions (storage + base) move on
        # both REFRESH and base DML
        bound_root, versions = self._substitute_matviews(
            session, bound_root, versions)
        key = self._consult_result_cache(session, inner, bound_root,
                                         versions, prepared_meta=meta,
                                         binding=binding)
        self._finish_with_result_cache(session, bound_root, key)

    def _plan_prepared(self, session, ps, ptypes):
        """The parameterized plan for one prepared statement + binding
        type signature, through the server's logical-plan cache: ONE cache
        entry serves every binding of that signature (the plan keeps
        symbolic ``ir.Parameter`` placeholders — values never bake in).
        Returns ``(root, versions)`` like ``_plan_query``."""
        from trino_tpu.sql.analyzer.expr_analyzer import parameter_types

        def plan_fn():
            from trino_tpu.sql.planner.optimizer import optimize
            from trino_tpu.sql.planner.planner import Planner

            inner = ps.statement
            udfs = getattr(session, "udfs", None)
            if udfs:
                from trino_tpu.sql.routines import expand_udfs

                inner = expand_udfs(inner, udfs)
            with parameter_types(ptypes):
                with tracing.span("analyze/plan"):
                    root = Planner(session).plan(inner)
                with tracing.span("optimize") as sp:
                    return optimize(root, session, span=sp)

        return self._through_plan_cache(
            session, ps.statement, ps.plan_cache_sql(ptypes), plan_fn)

    def _through_plan_cache(self, session, stmt, key_sql, plan_fn):
        """Plan-cache choreography shared by plain SELECTs and prepared
        EXECUTEs: serve a still-valid entry (hit metric + span), else plan
        via ``plan_fn`` and admit. Table-function statements never cache
        (their rows freeze into the plan at plan time). Returns
        ``(root, versions)`` — versions None when the cache is off."""
        from trino_tpu.cache.determinism import contains_table_function
        from trino_tpu.cache.plan_key import capture_versions
        from trino_tpu.obs import metrics as M

        cache = self.query_cache
        use_plan_cache = (cache is not None and bool(
            session.properties.get("logical_plan_cache_enabled", True))
            and not contains_table_function(stmt))
        if use_plan_cache:
            hit = cache.plans.get(session, key_sql)
            if hit is not None:
                M.PLAN_CACHE_HITS.inc()
                with self.tracer.span("plan-cache/hit"):
                    pass
                return hit
            M.PLAN_CACHE_MISSES.inc()
        root = plan_fn()
        versions = None
        if use_plan_cache:
            versions = capture_versions(session, root)
            cache.plans.put(session, key_sql, root, versions)
        return root, versions

    def _plan_query(self, session, stmt):
        """Optimized plan for this SELECT, through the server's logical-
        plan cache when enabled (skipping parse/analyze/plan/optimize on
        canonical-SQL repeat; entries revalidate against connector data
        versions inside PlanCache.get). Table-function statements never
        plan-cache: their rows materialize into the plan at plan time.

        Returns ``(root, versions)`` — the data versions captured while
        planning/revalidating (None when not computed), handed onward so
        the result-cache lookup doesn't re-stat every table."""
        from trino_tpu.exec.query import plan_sql

        # plan_sql emits nested parse + analyze/plan + optimize spans
        return self._through_plan_cache(
            session, stmt, self.sql, lambda: plan_sql(session, self.sql))

    def _consult_result_cache(self, session, stmt, root, versions=None,
                              prepared_meta=None, binding=None):
        """One admission pass against the server result cache. Returns
        ``_SERVED_FROM_CACHE`` (columns/rows already populated), a cache
        key string (this query leads the flight and must complete/abandon
        it), or None (bypass / follower fallback: execute, don't store).
        ``prepared_meta`` = (reason, canonical-of-parameterized-plan) from
        the EXECUTE hot path — skips the per-request determinism walk and
        plan re-serialization; ``binding`` discriminates the key per bound
        values."""
        from trino_tpu.cache.determinism import uncachable_reason
        from trino_tpu.cache.plan_key import (
            capture_versions, fingerprint_from_canonical, plan_fingerprint)
        from trino_tpu.obs import metrics as M

        cache = self.query_cache
        if cache is None or not bool(
                session.properties.get("result_cache_enabled", False)):
            self.cache_status = "BYPASS"
            return None
        canonical = None
        if prepared_meta is not None:
            reason, canonical = prepared_meta
        else:
            reason = uncachable_reason(stmt, root)
        if reason is None:
            # captured at plan time (threaded through from _plan_query
            # when it already did the capture): a later mutation bumps the
            # version, the next identical query fingerprints differently,
            # and the stale entry misses naturally
            if versions is None:
                versions = capture_versions(session, root)
            if versions is None:
                reason = "unversioned table"
        with self.tracer.span("cache/lookup") as sp:
            if reason is not None:
                self.cache_status = "BYPASS"
                M.RESULT_CACHE_BYPASSES.inc()
                sp.set("disposition", "BYPASS")
                sp.set("reason", reason)
                return None
            # the user partitions the key: plan-time access control must
            # re-fire per principal, never be laundered through a cache hit
            from trino_tpu.cache.result_cache import session_user

            extra = (f"user={session_user(session)}",) + (
                (binding,) if binding else ())
            key = (fingerprint_from_canonical(canonical, versions, extra)
                   if canonical is not None
                   else plan_fingerprint(root, versions, extra=extra))
            sp.set("key", key[:16])
            # serving-index learning (server/dispatch.py): on FINISHED
            # MISS, the dispatcher maps (user, SQL) -> this key so a
            # repeat serves on the dispatch plane without planning
            self.result_cache_key = key
            self.result_cache_versions = versions
            kind, payload = cache.results.begin(key)
            if kind == "wait":
                # single-flight: a concurrent identical query is already
                # executing — park on its flight instead of duplicating
                sp.set("single_flight", True)
                M.RESULT_CACHE_SINGLE_FLIGHT_WAITS.inc()
                done = payload.wait(timeout=600.0)
                if done and payload.ok:
                    kind, payload = "hit", payload.value
                else:
                    # the leader failed or timed out: execute ourselves,
                    # uncached (no flight ownership to publish through)
                    self.cache_status = "MISS"
                    M.RESULT_CACHE_MISSES.inc()
                    sp.set("disposition", "MISS")
                    return None
            if kind == "hit":
                columns, rows = payload
                self.cache_status = "HIT"
                M.RESULT_CACHE_HITS.inc()
                sp.set("disposition", "HIT")
                sp.set("rows", len(rows))
                self.columns, self.rows = list(columns), list(rows)
                return _SERVED_FROM_CACHE
            self.cache_status = "MISS"
            M.RESULT_CACHE_MISSES.inc()
            sp.set("disposition", "MISS")
            return key

    def _execute_query(self, session, root) -> None:
        """Run an already-optimized SELECT plan: coordinator-local for
        process-local catalogs and fast-path-eligible short queries, else
        fragment + schedule + root fragment."""
        from trino_tpu.obs import metrics as M

        if any(
            isinstance(n, P.TableScanNode)
            and session.catalogs[n.catalog].coordinator_only
            for n in P.walk_plan(root)
        ):
            # scans over process-local catalogs (memory, system) cannot be
            # shipped to workers — execute on the coordinator's own
            # engine (its embedded worker role). RUNNING is set so the
            # query observes ITSELF truthfully through
            # system.runtime.queries while its scan materializes.
            self._run_local(session, root, path="local-catalog",
                            span_name="execute/coordinator-local")
            return
        from trino_tpu.server import fastpath

        take, reason = fastpath.fast_path_decision(session, root)
        if take:
            # short-query fast path (server/fastpath.py): the plan would
            # fragment into at most one distributed stage, so the task
            # round-trips buy nothing — run it on the coordinator's own
            # engine, with the decision on the span/query info/EXPLAIN
            self.fast_path_reason = reason
            self._run_local(session, root, path="fast-path",
                            span_name="fastpath/execute", reason=reason)
            return
        self.fast_path = "distributed"
        M.FAST_PATH_QUERIES.inc(1, "distributed")
        with self.tracer.span("fragment") as sp:
            fragments = fragment_plan(root, session)
            sp.set("fragments", len(fragments))
        self.fragments = fragments
        # spooled-results decision for the export shape, made BEFORE
        # scheduling: the producing fragment's tasks then write result
        # segments directly and the coordinator never pulls the data
        spool_fid = self._mark_worker_direct_spool(session, root, fragments)
        # the schedule span covers the whole dispatch tail — worker
        # selection, task creation, the RUNNING transition (whose state
        # listeners run inline), and the stats-poller spawn — so the
        # phase ledger attributes all of it to `schedule` instead of
        # leaving sub-millisecond gaps around the task POSTs
        with self.tracer.span("schedule") as sp:
            self.state.set("STARTING")
            workers = self.registry.alive()
            if not workers:
                raise RuntimeError("no alive workers")
            sp.set("workers", len(workers))
            self._schedule(session, fragments, workers)
            self.state.set("RUNNING")
            self._start_stats_poller()
        result_page = None
        if spool_fid is not None:
            # worker-direct spooled results: wait for the producers to
            # finish writing their segments, assemble the manifest from
            # their status payloads — metadata only, no page ever crosses
            # this process (the coordinator is off the data path)
            with self.tracer.span("segments/collect") as sp:
                self._collect_result_segments(spool_fid)
                sp.set("segments", len(self.result_segments or ()))
        else:
            with self.tracer.span("execute/root-fragment"):
                result_page = self._run_root_fragment(session, fragments)
        # freeze the rollup on the workers' terminal numbers before the
        # query leaves RUNNING (tasks are at least FLUSHING once the root
        # fragment has drained their buffers); spanned so the ledger can
        # attribute this control-plane wall instead of leaving a gap
        with self.tracer.span("stats/sweep") as sp:
            sp.set("polled", self._sweep_task_stats())
        self.state.set("FINISHING")
        self.columns = fragments[-1].root.column_names
        if result_page is not None:
            with self._root_executor.charging(fragments[-1].root):
                self._materialize_result(session, result_page)

    def _cleanup_spool(self) -> None:
        """Drop this query's spooled task outputs (reference: exchange
        lifecycle — sink files are deleted when the query completes)."""
        import glob
        import os

        from trino_tpu.server.task import spool_directory

        spool_dir = spool_directory()
        if not spool_dir:
            return
        for path in glob.glob(os.path.join(spool_dir, f"{self.query_id}.*.pages")):
            try:
                os.remove(path)
            except OSError:
                pass

    # ------------------------------------------------- spooled results
    def result_rows(self) -> int:
        """Result cardinality across both protocols: materialized rows
        inline, summed manifest rows when spooled."""
        if self.result_segments is not None:
            return sum(int(e.get("rows", 0)) for e in self.result_segments)
        return len(self.rows)

    def _spool_config(self, session) -> Optional[dict]:
        """The spooled-results knobs, or None when the protocol is off
        for this query (disabled, or no segment store — bare embedded
        executions)."""
        props = session.properties
        if self.segment_store is None or not bool(
                props.get("spooled_results_enabled", False)):
            return None
        return {
            "threshold": int(
                props.get("spooled_results_threshold_bytes", 8 << 20)),
            "segment_bytes": int(
                props.get("spooled_results_segment_bytes", 8 << 20)),
            "ttl_s": int(props.get("result_segment_ttl_ms",
                                   300_000)) / 1e3,
        }

    def _materialize_result(self, session, page) -> None:
        """The result tail every SELECT path funnels through: serve the
        page inline (result/serialize -> Python rows) or — when the
        ACTUAL bytes cross the spool threshold — encode it into this
        coordinator's segment store and publish a manifest instead. The
        inline-result memory guard lives here too: over
        ``inline_result_max_bytes`` the query auto-spools (protocol
        enabled) or FAILS loudly — one export query must never OOM the
        dispatch plane by silently materializing in process memory."""
        from trino_tpu.obs import metrics as M

        est = (int(page.live_count("result-rows"))
               * int(page.row_byte_estimate()))
        cfg = self._spool_config(session)
        cap = int(session.properties.get("inline_result_max_bytes",
                                         256 << 20))
        if cfg is not None and est >= min(cfg["threshold"], cap):
            self._spool_result_page(session, page, cfg)
            return
        if est > cap:
            M.INLINE_RESULT_REJECTIONS.inc()
            raise RuntimeError(
                f"result is ~{est} serialized bytes, over "
                f"inline_result_max_bytes={cap}: the coordinator refuses "
                "to materialize it in process memory "
                "(INLINE_RESULT_TOO_LARGE) — enable "
                "spooled_results_enabled to serve it as a spooled "
                "segment manifest, or narrow the query")
        with self.tracer.span("result/serialize") as sp:
            self.rows = page.to_pylist()
            sp.set("rows", len(self.rows))

    def _spool_result_page(self, session, page, cfg) -> None:
        """Coordinator-side spool: chunk + serde-encode the result page
        into size-bounded segments in this coordinator's own store
        (coordinator-local, fast-path, and non-trivial-root distributed
        queries — the decision is plan-shape-independent; only the
        worker-direct shape also skips this process's encode)."""
        from trino_tpu.data.serde import serialize_page
        from trino_tpu.obs import metrics as M
        from trino_tpu.server.task import _chunk_pages

        page = page.compact()
        chunk_target = int(session.properties.get(
            "task_output_chunk_bytes", 4 << 20))
        chunk_rows = (max(1, chunk_target // page.row_byte_estimate())
                      if page.num_rows else 1)
        writer = self.segment_store.writer(
            self.query_id, target_bytes=cfg["segment_bytes"],
            ttl_s=cfg["ttl_s"])
        with self.tracer.span("result/spool") as sp:
            for c in _chunk_pages(page, chunk_rows):
                writer.add(serialize_page(c), int(c.num_rows))
            metas = writer.finish()
            sp.set("segments", len(metas))
            sp.set("rows", int(page.num_rows))
        base = self.segment_base_url or ""
        self.result_segments = [
            {**m.manifest_entry(),
             "uri": f"{base}/v1/segment/{m.segment_id}",
             "ackUri": f"{base}/v1/segment/{m.segment_id}"}
            for m in metas]
        self.spooled = "coordinator"
        self.rows = []
        M.SPOOLED_RESULT_QUERIES.inc(1, "coordinator")

    def _mark_worker_direct_spool(self, session, root, fragments):
        """Worker-direct spooling decision, made BEFORE scheduling: when
        the root single fragment is a pure gather pass-through
        (OutputNode over one RemoteSourceNode — the export shape) and
        the ESTIMATED result crosses the spool threshold, the producing
        fragment's tasks write result segments directly and the
        coordinator never runs the root fragment at all. Returns the
        producing fragment id, or None — in which case the actual-bytes
        decision in ``_materialize_result`` still applies, so the
        protocol choice stays plan-shape-independent."""
        cfg = self._spool_config(session)
        if cfg is None:
            return None
        if str(self.session_properties.get(
                "retry_policy", "NONE")).upper() == "TASK":
            # FTE may run duplicate attempts whose losing segments would
            # outlive the manifest; large FTE results still spool through
            # the coordinator path
            return None
        src = self._gather_passthrough(fragments[-1])
        if src is None:
            return None
        frag = next((f for f in fragments if f.id == src.fragment_id),
                    None)
        if frag is None or getattr(frag, "output_partition_channels",
                                   None):
            return None
        out = fragments[-1].root
        from trino_tpu.server import fastpath

        est_rows = fastpath.scan_rows_estimate(session, root)
        est_bytes = est_rows * 8 * max(1, len(out.column_names or ()))
        if est_bytes < cfg["threshold"]:
            return None
        frag.spool_results = True
        return frag.id

    @staticmethod
    def _gather_passthrough(root_frag):
        """The gather RemoteSourceNode when the root single fragment is
        a pure pass-through (OutputNode over one gather source — the
        export shape, where gathered bytes == result bytes), else
        None."""
        out = root_frag.root
        src = out.source if isinstance(out, P.OutputNode) else out
        if (isinstance(src, RemoteSourceNode)
                and src.exchange_type == "gather"):
            return src
        return None

    SEGMENT_COLLECT_TIMEOUT = 600.0

    def _collect_result_segments(self, fid: int) -> None:
        """Wait for the result-producing tasks to FINISH (their segments
        are durable by then) and assemble the statement manifest from
        their status payloads, in task order — the coordinator handles
        only metadata. Data fetches go straight to the owning worker;
        ACKs route through the coordinator (a tiny control-plane DELETE)
        so segment-fetch activity is attributable per query."""
        from trino_tpu.obs import metrics as M

        deadline = time.monotonic() + self.SEGMENT_COLLECT_TIMEOUT
        entries: List[dict] = []
        base = self.segment_base_url or ""
        for loc in self.fragment_tasks.get(fid, ()):
            info = None
            while True:
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"result task {loc.task_id} did not finish "
                        f"within {self.SEGMENT_COLLECT_TIMEOUT:g}s")
                if self.state.is_terminal():
                    raise RuntimeError("query was canceled")
                try:
                    status, body, _ = wire.http_request(
                        "GET",
                        f"{loc.base_url}/v1/task/{loc.task_id}/status",
                        timeout=10.0)
                except Exception:  # noqa: BLE001 — retry until deadline
                    time.sleep(0.1)
                    continue
                if status >= 400:
                    raise RuntimeError(
                        f"result task {loc.task_id} unreachable: "
                        f"{status}")
                info = json.loads(body)
                self._note_task_status(loc.task_id, info)
                state = info.get("state")
                if state == "FINISHED":
                    break
                if state in ("FAILED", "CANCELED"):
                    raise RuntimeError(
                        f"result task {loc.task_id} {state}: "
                        f"{info.get('failure')}")
                time.sleep(0.05)
            for seg in info.get("resultSegments", ()):
                self._segment_workers[seg["id"]] = loc.base_url
                entries.append({
                    **seg,
                    "uri": f"{loc.base_url}/v1/segment/{seg['id']}",
                    "ackUri": f"{base}/v1/segment/{seg['id']}",
                })
        self.result_segments = entries
        self.spooled = "worker-direct"
        self.rows = []
        M.SPOOLED_RESULT_QUERIES.inc(1, "worker-direct")

    def _discard_spooled_result(self) -> None:
        """A statement whose deliverable is NOT the inner query's rows
        (EXPLAIN ANALYZE) ran a query that spooled: release the segments
        now — no manifest will ever reach a client."""
        if self.result_segments is None:
            return
        for e in self.result_segments:
            worker = self._segment_workers.get(e["id"])
            if worker is not None:
                try:
                    wire.http_request(
                        "DELETE", f"{worker}/v1/segment/{e['id']}",
                        timeout=5.0)
                except Exception:  # noqa: BLE001 — TTL is the backstop
                    pass
            elif self.segment_store is not None:
                self.segment_store.discard(e["id"])
        self.result_segments = None
        self.spooled = None

    # ------------------------------------------------------ stats pipeline
    def _note_task_status(self, task_id: str, info: dict) -> None:
        """Record one task-status payload (state + worker-reported stats)
        into the slot map the stage/query rollups read."""
        parts = task_id.split(".")
        try:
            frag = int(parts[-3])
        except (ValueError, IndexError):
            return
        slot = task_id.rsplit(".a", 1)[0]
        entry = {
            "fragment": frag,
            "taskId": task_id,
            "state": info.get("state") or "RUNNING",
            "stats": info.get("stats") or {},
        }

        def progress(e):
            s = e.get("stats") or {}
            return (int(s.get("completedSplits", 0)),
                    int(s.get("inputRows", 0)),
                    int(s.get("outputRows", 0)))

        with self._tstats_lock:
            have = self.task_stats.get(slot)
            # a FINISHED attempt's stats are authoritative for its slot —
            # a late poll of a canceled speculative twin must not clobber
            if (have is not None and have["state"] == "FINISHED"
                    and entry["state"] != "FINISHED"):
                return
            # concurrent attempts (speculation / a retry's create response)
            # share the slot: while neither is FINISHED, keep whichever has
            # made MORE progress, so live numbers never regress or flicker.
            # Dead (FAILED/CANCELED) records never win in either direction:
            # a dead twin must not displace a live attempt's record, and a
            # dead existing record never blocks the live retry — a stage
            # must not read FAILED while an attempt is still running.
            dead = ("FAILED", "CANCELED")
            if (have is not None and have["taskId"] != task_id
                    and entry["state"] in dead
                    and have["state"] not in dead):
                return
            if (have is not None and entry["state"] != "FINISHED"
                    and have["state"] not in dead
                    and have["taskId"] != task_id
                    and progress(entry) < progress(have)):
                return
            self.task_stats[slot] = entry

    def _run_local(self, session, root, path: str, span_name: str,
                   reason: Optional[str] = None) -> None:
        """The coordinator-local execution tail shared by the forced
        local-catalog path and the short-query fast path: run the whole
        plan on this process's engine, record the path, and feed the
        stats rollups through the synthetic local task slot."""
        from trino_tpu.exec.executor import Executor
        from trino_tpu.obs import metrics as M

        self.fast_path = path
        M.FAST_PATH_QUERIES.inc(1, path)
        self.state.set("RUNNING")
        t0 = time.perf_counter()
        with self.tracer.span(span_name) as sp:
            if reason is not None:
                sp.set("reason", reason)
            ex = Executor(session)
            # memory-ledger attribution: the coordinator-local path runs
            # ONE executor per query, so owner mode is exact here (the
            # worker tier attributes at the task level instead)
            ex.memory.owner = f"query:{self.query_id}"
            page = ex.execute_checked(root)
            if reason is not None:
                with ex.charging(root):
                    sp.set("rows", page.live_count("result-rows"))
        self._local_executor = ex  # EXPLAIN ANALYZE annotation source
        self.columns = list(root.column_names)
        # same spool/inline decision as the distributed tail: the
        # protocol choice is plan-shape-independent — a fast-path or
        # local-catalog export spools from the coordinator's own store
        with ex.charging(root):
            self._materialize_result(session, page)
        self._note_local_stats(ex, time.perf_counter() - t0)
        ex.memory.release()

    def _note_local_stats(self, ex, elapsed_s: float) -> None:
        """Fold a coordinator-local execution's stats into the task-stats
        map so the stage/query rollups, the protocol stats block, and
        ``system.runtime.queries``/``tasks`` cover fast-path queries
        exactly like distributed ones (one synthetic task slot in
        fragment 0 — the coordinator IS that task's worker)."""
        scan_rows = sum(getattr(ex, "scan_stats", {}).values())
        scan_cache = getattr(ex, "scan_cache", {})
        stats = {
            "elapsedS": round(elapsed_s, 6),
            "deviceS": round(sum(
                st.device_s for st in ex.node_stats.values()), 6),
            "completedSplits": max(1, len(getattr(ex, "scan_stats", {}))),
            "totalSplits": max(1, len(getattr(ex, "scan_stats", {}))),
            "inputRows": int(scan_rows),
            "outputRows": self.result_rows(),
            "outputBytes": sum(
                st.output_bytes for st in ex.node_stats.values()),
            "peakBytes": int(ex.memory.peak),
            "spills": len(ex.memory.spills),
            "shedBytes": int(ex.memory.shed_bytes),
            "yieldEvents": int(ex.memory.yields),
            "deviceCacheHits": sum(
                1 for d in scan_cache.values() if d == "hit"),
            "deviceCacheMisses": sum(
                1 for d in scan_cache.values() if d == "miss"),
            "operatorStats": [st.to_dict()
                              for st in ex.node_stats.values()],
        }
        self._note_task_status(f"{self.query_id}.0.local.a0",
                               {"state": "FINISHED", "stats": stats})

    def _sweep_task_stats(self) -> int:
        """One status sweep over every scheduled task (the coordinator's
        status-polling loop body; also the terminal freeze). Tasks whose
        record is already terminal — FINISHED, or FAILED/CANCELED (e.g.
        producers the adaptive re-planner superseded) — are skipped, and
        the timeout is sub-second so one unreachable worker cannot stall
        the live-stats cadence. Returns the number of tasks actually
        polled (the poller's backoff signal)."""
        with self._tstats_lock:
            done = {e["taskId"] for e in self.task_stats.values()
                    if e["state"] in ("FINISHED", "FAILED", "CANCELED")}
        locations = [loc for locs in list(self.fragment_tasks.values())
                     for loc in list(locs)
                     if loc is not None and loc.task_id not in done]
        for loc in locations:
            try:
                status, body, _ = wire.http_request(
                    "GET", f"{loc.base_url}/v1/task/{loc.task_id}/status",
                    timeout=0.8)
                if status < 400:
                    self._note_task_status(loc.task_id, json.loads(body))
            except Exception:  # noqa: BLE001 — a gone worker loses its stats
                pass
        return len(locations)

    STATS_POLL_INTERVAL = 0.25
    STATS_POLL_MAX_BACKOFF = 16.0  # x the base interval

    def _start_stats_poller(self) -> None:
        """Background status poll while the query RUNs, so
        ``GET /v1/query/{id}`` serves LIVE stage/query stats (reference:
        ContinuousTaskStatusFetcher feeding the coordinator's stage state
        machines). Each sleep is JITTERED so many concurrent RUNNING
        queries de-phase instead of hitting every worker in lockstep, and
        a sweep that found nothing left to poll (every slot frozen
        FINISHED — e.g. the root fragment is still draining results)
        backs off exponentially instead of hammering workers with no-op
        status rounds."""

        def poll():
            import random

            backoff = 1.0
            while not self.state.is_terminal():
                polled = self._sweep_task_stats()
                backoff = (min(backoff * 2.0, self.STATS_POLL_MAX_BACKOFF)
                           if polled == 0 else 1.0)
                time.sleep(self.STATS_POLL_INTERVAL * backoff
                           * random.uniform(0.75, 1.25))

        self._stats_poller = threading.Thread(target=poll, daemon=True)
        self._stats_poller.start()

    def stage_stats(self, include_operators: bool = True) -> List[dict]:
        """Per-stage rollups of the latest worker-reported task stats.
        ``include_operators=False`` skips the per-node OperatorStats merge
        for callers that only read the scalar summary (protocol polls,
        UI) — O(tasks) instead of O(tasks × plan nodes)."""
        from trino_tpu.exec.operator_stats import rollup_tasks_to_stage

        with self._tstats_lock:
            entries = [dict(e) for e in self.task_stats.values()]
        by_frag: Dict[int, List[dict]] = {}
        for e in entries:
            by_frag.setdefault(e["fragment"], []).append(e)
        return [rollup_tasks_to_stage(fid, es,
                                      include_operators=include_operators)
                for fid, es in sorted(by_frag.items())]

    def task_records(self) -> List[dict]:
        """Per-slot task records with the assigned worker uri attached —
        the public read surface ``system.runtime.tasks`` materializes from
        (no caller reaches into ``task_stats``/``_tstats_lock``)."""
        url_by_task = {
            loc.task_id: loc.base_url
            for locs in list(self.fragment_tasks.values())
            for loc in list(locs) if loc is not None
        }
        with self._tstats_lock:
            entries = [dict(e) for e in self.task_stats.values()]
        for e in entries:
            e["workerUri"] = url_by_task.get(e["taskId"])
        return entries

    # ------------------------------------------------------- phase ledger
    def worker_spans(self, timeout: float = 3.0) -> List[dict]:
        """Every scheduled task's span dump, fetched in parallel with a
        short timeout (a gone/partitioned worker loses its spans, never
        the whole read). Shared by the trace endpoint and the ledger —
        the completion-path caller passes a tighter timeout because it
        runs BEFORE the terminal state publishes."""
        locations = [loc for locs in list(self.fragment_tasks.values())
                     for loc in list(locs) if loc is not None]
        if not locations:
            return []

        def fetch(loc):
            try:
                status, body, _ = wire.http_request(
                    "GET", f"{loc.base_url}/v1/task/{loc.task_id}/spans",
                    timeout=timeout)
                if status < 400:
                    return json.loads(body).get("spans", ())
            except Exception:  # noqa: BLE001
                pass
            return ()

        spans: List[dict] = []
        pool = self.io_pool
        if pool is not None:
            try:
                for dump in pool.map(fetch, locations):
                    spans.extend(dump)
                return spans
            except RuntimeError:  # pool shut down mid-stop: inline below
                pass
        # no shared pool (bare QueryExecution use): fetch serially — the
        # per-call ThreadPoolExecutor churn this replaced cost more than
        # the fan-in it bought on the hot path
        for loc in locations:
            spans.extend(fetch(loc))
        return spans

    # pre-publication pulls (ledger warm + postmortem capture) run on the
    # query thread BEFORE the terminal state is visible — a blackholed
    # worker must cost ~a second of failure-reporting latency, not the
    # trace endpoint's full on-demand timeout
    COMPLETION_PULL_TIMEOUT = 1.5

    def _warm_timeline(self) -> None:
        """Compute + cache the ledger (requires ``ended_at``); called on
        the query thread right before the terminal transition so state
        listeners — and every later read — get the cached result."""
        if self._timeline is not None or self.ended_at is None:
            return
        try:
            from trino_tpu.obs.timeline import compute_timeline

            # the collector holds the interpreter lock, whoever it runs
            # for: the process's pauses that overlap this statement's wall
            # join its span export (the trace endpoint shows them, the
            # ledger's detail names them)
            root = next((sp.span_id for sp in self.tracer.spans()
                         if sp.name == "query"), None)
            self.extra_spans = list(self.extra_spans) + (
                tracing.GC_RECORDER.spans_between(
                    self.created_at, self.ended_at, parent_id=root))
            spans = (self.tracer.to_dicts() + list(self.extra_spans)
                     + self.worker_spans(
                         timeout=self.COMPLETION_PULL_TIMEOUT))
            self._timeline = compute_timeline(
                spans, self.created_at, self.ended_at)
        except Exception:  # noqa: BLE001 — the ledger is observability,
            pass  # never a reason to fail the terminal transition

    def timeline_dict(self) -> Optional[dict]:
        """The query's phase ledger: None while running, computed ONCE
        from the merged coordinator+worker span tree at terminal and
        cached (normally warmed by the query thread just before the
        terminal transition; a kill/cancel from another thread computes
        here on first read). ``client-drain`` refreshes on every read —
        result pages keep draining after the wall ends."""
        if not self.state.is_terminal() or self.ended_at is None:
            return None
        if self._timeline is None:
            self._warm_timeline()
        tl = self._timeline
        if tl is None:
            return None
        if self.last_drain_at is not None:
            tl.client_drain_s = max(0.0, self.last_drain_at - self.ended_at)
        if self.last_segment_fetch_at is not None:
            # segment fetch/ack activity seen by this coordinator —
            # refreshed per read, like client-drain (outside the wall)
            tl.segment_fetch_s = max(
                0.0, self.last_segment_fetch_at - self.ended_at)
        return tl.to_dict()

    def _timeline_now(self) -> dict:
        """A ledger over the spans recorded SO FAR (EXPLAIN ANALYZE's
        header renders mid-query, before the wall closes)."""
        from trino_tpu.obs.timeline import compute_timeline

        spans = (self.tracer.to_dicts() + list(self.extra_spans)
                 + self.worker_spans())
        return compute_timeline(spans, self.created_at,
                                time.time()).to_dict()

    # ---------------------------------------------------- flight recorder
    def capture_postmortem(self, store: bool = True,
                           timeout: float = 3.0) -> dict:
        """Merge this process's flight-recorder ring with every involved
        worker's (pulled via ``GET /v1/task/{id}/recorder``) into one
        postmortem. Called on FAILED (stored on the execution + shipped
        on QueryCompletedEvent) and on demand by
        ``GET /v1/query/{id}/trace?recorder=1``."""
        from trino_tpu.obs.flightrecorder import pull_worker_rings
        from trino_tpu.obs.memledger import MEMORY_LEDGER

        locations = [loc for locs in list(self.fragment_tasks.values())
                     for loc in list(locs) if loc is not None]
        # the failure-path capture runs BEFORE the FAILED transition is
        # published (fast-listener contract) — a set failure reason means
        # the query IS failing, and the record must say so
        state = self.state.get()
        if self.failure is not None and not self.state.is_terminal():
            state = "FAILED"
        pm = {
            "queryId": self.query_id,
            "state": state,
            "failure": (self.failure or "").split("\n")[0] or None,
            "capturedAt": time.time(),
            "coordinator": {
                "nodeId": getattr(self.recorder, "node_id", "coordinator"),
                "records": (self.recorder.snapshot()
                            if self.recorder is not None else []),
                # memory-ledger snapshot: per-pool live/peak bytes, top
                # consumers by owner, and the last shed events — names
                # WHO was holding memory when the query died
                "memory": MEMORY_LEDGER.memory_snapshot(),
                # device-profiler snapshot: the newest compile-ledger
                # events + utilization counters — a recompile storm
                # preceding the failure is visible right here
                "profiler": _profiler_snapshot(),
                # flow-ledger snapshot: per-link rollups + the last
                # transfers + stall rollups — what was moving (and who
                # was blocked on whom) when the query died
                "flows": _flows_snapshot(),
            },
            "workers": pull_worker_rings(locations, timeout=timeout,
                                         pool=self.io_pool),
        }
        if store:
            self.postmortem = pm
        return pm

    def query_stats(self, stages: Optional[List[dict]] = None) -> dict:
        """Query-level rollup: live while RUNNING, frozen at terminal.
        Pass precomputed ``stages`` to avoid re-rolling the task map when
        the caller already has them (info(), the UI page)."""
        from trino_tpu.exec.operator_stats import rollup_stages_to_query

        qs = rollup_stages_to_query(
            self.stage_stats() if stages is None else stages)
        end = (self.ended_at
               if self.state.is_terminal() and self.ended_at else time.time())
        qs["elapsedMs"] = int((end - self.created_at) * 1000)
        qs["state"] = self.state.get()
        qs["cacheStatus"] = self.cache_status
        # which resource group admitted this query (None under a legacy
        # injected gate) — clients (CLI summary tag) and system tables
        qs["resourceGroup"] = self.resource_group
        # which control-plane path served the SELECT (fast-path /
        # distributed / local-catalog), for clients and system tables
        qs["fastPath"] = self.fast_path
        qs["resultRows"] = self.result_rows()
        # spooled result protocol: which producer wrote the segments
        # (None = inline rows) + the manifest's footprint, for clients
        # (CLI summary) and system tables
        qs["spooled"] = self.spooled
        if self.result_segments is not None:
            qs["resultSegments"] = len(self.result_segments)
            qs["resultSegmentBytes"] = sum(
                int(e.get("bytes", 0)) for e in self.result_segments)
        # adaptive plan changes applied so far — rides every statement
        # response so clients can render "[adapted: N]" live
        qs["adaptations"] = len(self.plan_versions)
        # materialized-view substitutions in this query's plan (CLI
        # prints "mv: <name>"; 0/absent when nothing matched fresh)
        qs["mvHits"] = len(self.mv_substitutions)
        if self.mv_substitutions:
            qs["mvNames"] = list(self.mv_substitutions)
        # the phase ledger (obs/timeline.py): per-phase exclusive wall +
        # unattributed residual, None until the query is terminal
        qs["timeline"] = self.timeline_dict()
        # the memory block: peak by pool plus what was shed/yielded on
        # this query's behalf (cluster memory ledger read surface — the
        # CLI summary tag and system.runtime.queries columns feed here)
        qs["memory"] = {
            "peakBytes": int(qs.get("peakBytes") or 0),
            "shedBytes": int(qs.get("shedBytes") or 0),
            "yieldEvents": int(qs.get("yieldEvents") or 0),
            "spills": int(qs.get("spills") or 0),
        }
        # the data-plane block (flow ledger): drain throughput for the
        # CLI summary tag + the straggler count, absent on any ledger
        # hiccup rather than failing a stats poll
        try:
            qs["flows"] = self.flow_stats_block()
        except Exception:  # noqa: BLE001 — observability only
            pass
        return qs

    def flow_stats_block(self) -> dict:
        """The ``stats.flows`` block of the statement protocol: this
        query's client-drain rollup (bytes + effective MB/s) and the
        straggler count. Re-read by ``_drain_body`` on the final result
        page so the CLI summary includes that response's own bytes."""
        from trino_tpu.obs.flowledger import FLOW_LEDGER

        owner = f"drain:{self.query_id}"
        drain_bytes = 0
        drain_s = 0.0
        for r in FLOW_LEDGER.transfer_rows():
            if r["owner"] == owner:
                drain_bytes += r["bytes"]
                drain_s += r["seconds"]
        return {
            "drainBytes": drain_bytes,
            "drainMbPerS": (round(drain_bytes / drain_s / 1e6, 3)
                            if drain_s > 0 else None),
            "stragglers": len(self.straggler_rows()),
        }

    # ---------------------------------------------------- device profiler
    def kernel_rows_live(self) -> List[dict]:
        """This query's merged kernel-ledger rows (obs/devprofiler.py):
        worker rows from the task records (stamped with the assigned
        worker uri), coordinator rows from the local/root executors.
        Live while RUNNING — the same merge the terminal fold persists."""
        from trino_tpu.obs.devprofiler import merge_kernel_rows

        merged: Dict[tuple, dict] = {}
        # adaptive re-planner: superseded fragments re-ran as copies with
        # the same plan-node ids — keep them out, exactly like the
        # EXPLAIN ANALYZE operator merge
        superseded = {fid for ch in self.plan_versions
                      for fid in ch.get("supersedes", ())}
        for rec in self.task_records():
            if rec.get("fragment") in superseded:
                continue
            node = rec.get("workerUri") or "coordinator"
            rows = (rec.get("stats") or {}).get("kernelStats") or []
            merge_kernel_rows(merged, [
                dict(r, nodeId=r.get("nodeId") or node) for r in rows])
        for ex in (getattr(self, "_local_executor", None),
                   getattr(self, "_root_executor", None)):
            if ex is None:
                continue
            merge_kernel_rows(merged, [
                dict(r, nodeId="coordinator")
                for r in getattr(ex, "kernel_stats", {}).values()])
        rows = []
        for k in sorted(merged):
            row = dict(merged[k])
            row["queryId"] = self.query_id
            row["dispatchOverheadS"] = round(
                max(0.0, row["wallS"] - row["deviceS"]), 6)
            rows.append(row)
        return rows

    def fold_kernel_profile(self) -> None:
        """Persist the merged kernel rows into the process device
        profiler ONCE at terminal (the ``system.runtime.kernels`` store;
        per-operator launch/overhead metrics bump here, never
        per-dispatch)."""
        if getattr(self, "_kernels_folded", False):
            return
        self._kernels_folded = True
        from trino_tpu.obs.devprofiler import DEVICE_PROFILER

        rows = self.kernel_rows_live()
        if rows:
            DEVICE_PROFILER.record_query_kernels(self.query_id, rows)
        # set once the store holds them: profile_dict reads the store from
        # here on, and tells "folded none" from "folded some, since aged
        # out" by the count
        self._kernel_rows_folded = len(rows)

    def profile_dict(self) -> Optional[dict]:
        """The ``GET /v1/query/{id}/profile`` payload: merged kernel
        rows, this query's compile-ledger events, the phase ledger, and
        recent utilization samples from the coordinator's profiler.
        ``None`` where the query folded kernel rows and they have since
        left the profiler's LRU: "aged out" is never answered as a
        statement that launched nothing (``"kernels": []``)."""
        from trino_tpu.obs.devprofiler import DEVICE_PROFILER, sync_sites_of

        folded = getattr(self, "_kernel_rows_folded", None)
        kernels = (self.kernel_rows_live() if folded is None
                   else DEVICE_PROFILER.kernel_rows(self.query_id))
        if folded and not kernels:
            return None
        return {
            "queryId": self.query_id,
            "state": self.state.get(),
            "kernels": kernels,
            # the kernels' blocking device->host reads by call site
            "hostSyncSites": sync_sites_of(kernels),
            "compiles": DEVICE_PROFILER.compile_rows(
                query_id=self.query_id),
            "utilization": DEVICE_PROFILER.utilization_rows(limit=8),
            "counters": DEVICE_PROFILER.counters(),
            "timeline": self.timeline_dict(),
        }

    # ------------------------------------------------------- flow ledger
    def _owns_flow(self, owner: str) -> bool:
        """Does a flow-ledger rollup owner belong to this query? Owners
        are ``task:{qid}.{frag}.{slot}.a{n}``, ``query:{qid}`` (spool
        writes / segment fetches) and ``drain:{qid}`` (client drain)."""
        return (owner == f"query:{self.query_id}"
                or owner == f"drain:{self.query_id}"
                or owner.startswith(f"task:{self.query_id}."))

    def _straggler_multiple(self) -> float:
        """The ``straggler_multiple`` session property (elapsed must
        exceed this multiple of the stage median to flag); malformed
        values fall back to the ledger default."""
        from trino_tpu.obs.flowledger import DEFAULT_STRAGGLER_MULTIPLE

        try:
            return float(self.session_properties.get(
                "straggler_multiple", DEFAULT_STRAGGLER_MULTIPLE))
        except (TypeError, ValueError):
            return DEFAULT_STRAGGLER_MULTIPLE

    def flow_rows_live(self) -> List[dict]:
        """This query's per-link transfer rollups, merged cluster-wide:
        worker rows ride the announce payload (``flows``), the
        coordinator contributes its own process ledger directly. A
        worker ledger sharing this process (in-process test clusters
        stamp the global ledger with the first server's id) is NOT
        double-reported: announce rows win for that node id — the
        kernel/memory ledger fold pattern."""
        from trino_tpu.obs.flowledger import FLOW_LEDGER

        rows = []
        announced = set()
        for n in self.registry.snapshot():
            flows = (n.get("info") or {}).get("flows")
            if flows is None:
                continue
            announced.add(n["nodeId"])
            rows.extend(dict(r, nodeId=n["nodeId"]) for r in flows
                        if self._owns_flow(str(r.get("owner", ""))))
        nid = FLOW_LEDGER.node_id or "coordinator"
        if nid not in announced:
            rows.extend(dict(r, nodeId=nid)
                        for r in FLOW_LEDGER.transfer_rows()
                        if self._owns_flow(r["owner"]))
        return rows

    def straggler_rows(self) -> List[dict]:
        """Straggler verdicts over this query's task records: frozen at
        terminal by :meth:`fold_flow_profile`, detected live while
        RUNNING (same live/folded split as the kernel rows)."""
        folded = getattr(self, "_stragglers", None)
        if folded is not None:
            return folded
        from trino_tpu.obs.flowledger import detect_stragglers

        return detect_stragglers(self.task_records(),
                                 multiple=self._straggler_multiple())

    def fold_flow_profile(self) -> None:
        """Freeze the straggler verdicts ONCE at terminal and bump the
        per-cause straggler counter (metrics fire at query end, never
        per stats poll)."""
        if getattr(self, "_flows_folded", False):
            return
        self._flows_folded = True
        self._stragglers = self.straggler_rows()
        if self._stragglers:
            from trino_tpu.obs import metrics as M

            for f in self._stragglers:
                M.STRAGGLER_TASKS.inc(1, f["cause"])

    def flows_dict(self) -> dict:
        """The ``GET /v1/query/{id}/flows`` payload: this query's
        cluster-merged per-link rows, the straggler verdicts, and the
        process backpressure stall rollups (stage-labelled; the stall
        series is process-scoped like the metrics registry)."""
        from trino_tpu.obs.flowledger import FLOW_LEDGER

        return {
            "queryId": self.query_id,
            "state": self.state.get(),
            "transfers": self.flow_rows_live(),
            "stragglers": self.straggler_rows(),
            "stalls": FLOW_LEDGER.stall_rows(),
            "net": FLOW_LEDGER.net_totals(),
        }

    def _explain_analyze(self, session, stmt) -> str:
        """Distributed EXPLAIN ANALYZE: plan, execute through the real
        fragment/schedule path, then render the fragments with the
        coordinator's rolled-up per-node worker stats injected (reference:
        PlanPrinter.textDistributedPlan with stats)."""
        import time as _time

        from trino_tpu.exec.operator_stats import (
            merge_operator_dicts, wall_time_header)
        from trino_tpu.sql.planner.fragmenter import format_fragments
        from trino_tpu.sql.planner.optimizer import (
            optimize, stamp_join_estimates)
        from trino_tpu.sql.planner.planner import Planner

        inner = stmt.statement
        udfs = getattr(session, "udfs", None)
        if udfs:
            from trino_tpu.sql.routines import expand_udfs

            inner = expand_udfs(inner, udfs)
        t_plan = _time.perf_counter()
        with tracing.span("analyze/plan"):
            root = Planner(session).plan(inner)
        with tracing.span("optimize") as sp:
            root = optimize(root, session, span=sp)
        stamp_join_estimates(root, session)
        root, _versions = self._substitute_matviews(session, root, None)
        plan_s = _time.perf_counter() - t_plan
        t_exec = _time.perf_counter()
        self._execute_query(session, root)
        exec_s = _time.perf_counter() - t_exec
        header = [wall_time_header(plan_s, exec_s)]
        from trino_tpu.exec.query import mv_notes_header

        mv_lines = mv_notes_header(self.mv_notes)
        if mv_lines:
            header.extend(mv_lines.rstrip("\n").split("\n"))
        # the phase ledger over the spans recorded so far (the EXPLAIN
        # query itself is still running while this renders)
        from trino_tpu.obs.timeline import summarize as summarize_timeline

        ledger = summarize_timeline(self._timeline_now())
        if ledger:
            header.append(f"Phase ledger: {ledger}")
        if self.fragments is None:
            # process-local catalogs / fast-path queries executed on the
            # coordinator's own engine: annotate from that executor,
            # exactly the local path — with the path decision on display
            from trino_tpu.sql.planner.plan import format_plan

            ex = getattr(self, "_local_executor", None)
            if self.fast_path == "fast-path":
                header.append(
                    "Fast path: coordinator-local ("
                    + getattr(self, "fast_path_reason", "short query") + ")")
            header.append(
                f"Peak working set: "
                f"{(ex.memory.peak if ex else 0) // 1024}KiB (coordinator)")
            return "\n".join(header) + "\n" + format_plan(
                root, executor=ex, verbose=stmt.verbose)
        # _execute_query already swept terminal task stats before FINISHING
        stages = self.stage_stats()
        stage_by_id = {s["stageId"]: s for s in stages}
        # fragments the adaptive re-planner superseded re-ran as COPIES
        # with the same plan-node ids — merging both runs would double
        # every per-node annotation, so the superseded stage's operators
        # stay out of the merge (its own [adapted: superseded] fragment
        # header still shows its stage totals)
        superseded = {fid for ch in self.plan_versions
                      for fid in ch.get("supersedes", ())}
        with self._tstats_lock:
            op_lists = [e["stats"].get("operatorStats")
                        for e in self.task_stats.values()
                        if e["fragment"] not in superseded]
        # the root single fragment ran on the coordinator itself — its
        # executor's stats complete the tree (that is its assigned worker,
        # not a re-execution)
        root_ex = getattr(self, "_root_executor", None)
        if root_ex is not None:
            op_lists.append(
                [st.to_dict() for st in root_ex.node_stats.values()])
        node_stats = merge_operator_dicts(op_lists)
        qs = self.query_stats(stages)
        header.append(
            f"Stages: {len(stages)} scheduled + 1 coordinator,"
            f" splits: {qs['completedSplits']}/{qs['totalSplits']},"
            f" input rows: {qs['totalRows']},"
            f" peak task memory: {qs['peakBytes'] // 1024}KiB,"
            f" spills: {qs['spills']}")
        if qs.get("shedBytes"):
            header.append(
                f"Memory pressure: {qs['shedBytes'] // 1024}KiB shed from "
                f"revocable caches across {qs.get('yieldEvents', 0)} "
                f"yield event(s)")
        # per-node peak annotation (memory ledger): the MAX task peak
        # each worker reached for this query — spots the skewed node a
        # cluster-wide rollup hides
        node_peaks: Dict[str, int] = {}
        for rec in self.task_records():
            node = rec.get("workerUri") or "coordinator"
            pb = int((rec.get("stats") or {}).get("peakBytes") or 0)
            if pb > node_peaks.get(node, 0):
                node_peaks[node] = pb
        if node_peaks:
            header.append("Peak task memory by node: " + ", ".join(
                f"{node} {pb // 1024}KiB"
                for node, pb in sorted(node_peaks.items())))
        # data-flow annotations (flow ledger): per-link bytes + effective
        # throughput for this query, then any straggler verdicts with
        # their dominant cause — the skewed node reads right here
        try:
            by_link: Dict[str, list] = {}
            for r in self.flow_rows_live():
                agg = by_link.setdefault(r["link"], [0, 0.0])
                agg[0] += int(r["bytes"])
                agg[1] += float(r["seconds"])
            if by_link:
                header.append("Data flow: " + ", ".join(
                    f"{link} {b / 1e6:.1f}MB"
                    + (f" @ {b / s / 1e6:.1f}MB/s" if s > 0 else "")
                    for link, (b, s) in sorted(by_link.items())))
            for f in self.straggler_rows():
                header.append(
                    f"Straggler: task {f['taskId']} {f['elapsedS']:.2f}s"
                    f" vs stage median {f['stageMedianS']:.2f}s"
                    f" ({f['ratio']:.1f}x, {f['cause']})")
        except Exception:  # noqa: BLE001 — annotations are observability
            pass
        # kernel-ledger annotations (device profiler): VERBOSE prints a
        # per-node launches=/dispatch_overhead= line from the merged rows
        kern = None
        if stmt.verbose:
            from trino_tpu.sql.planner.plan import kernel_annotations

            kern = kernel_annotations(self.kernel_rows_live())
        return "\n".join(header) + "\n" + format_fragments(
            self.fragments, stats=node_stats, stage_stats=stage_by_id,
            verbose=stmt.verbose, adapted=self._adapted_notes(),
            kernels=kern)

    def _schedule(self, session, fragments, workers) -> None:
        """Create one task per worker for each source fragment, splits
        round-robin across workers (SOURCE_DISTRIBUTION placement)."""
        # Declared consumer set per producing fragment (reference:
        # OutputBuffers): a fragment consumed by a source fragment is pulled
        # by every one of its tasks (broadcast — one buffer id per task); a
        # fragment consumed by the root single fragment has one consumer
        # (the coordinator's exchange client).
        consumer_counts: Dict[int, int] = {}
        for frag in fragments:
            for node in P.walk_plan(frag.root):
                if isinstance(node, RemoteSourceNode):
                    consumer_counts[node.fragment_id] = (
                        len(workers)
                        if frag.partitioning in ("source", "hash") else 1)
        fte = str(self.session_properties.get("retry_policy", "NONE")).upper() == "TASK"
        if fte:
            from trino_tpu.server.task import spool_directory

            if spool_directory() is None:
                # the retry contract needs durable outputs (reference: TASK
                # retry requires a configured exchange manager)
                raise RuntimeError(
                    "retry_policy=TASK requires the spooled exchange: set "
                    "TRINO_TPU_SPOOL_DIR to a cluster-shared directory")
        # Phased execution (reference: scheduler/policy/
        # PhasedExecutionSchedule): a fragment whose JOIN BUILD side is fed
        # by a leaf (scan-only) fragment does not schedule until that build
        # fragment's tasks finished executing (>= FLUSHING) — probe-side
        # tasks then never sit on workers holding memory while builds
        # compute. Leaf-only gating is deliberate: a build fragment that is
        # itself a consumer may park on its own output watermark before
        # FLUSHING, and gating on it could deadlock the pipeline.
        # wire-protocol values arrive as header STRINGS: normalize like the
        # typed property registry would ("false"/"0" disable)
        phased = str(self.session_properties.get(
            "phased_execution", True)).lower() not in ("false", "0", "no")
        by_id = {f.id: f for f in fragments}
        build_deps: Dict[int, List[int]] = {}
        for frag in fragments:
            deps = []
            for node in P.walk_plan(frag.root):
                if isinstance(node, P.JoinNode) and isinstance(
                        node.right, RemoteSourceNode):
                    dep = by_id.get(node.right.fragment_id)
                    if dep is not None and not any(
                            isinstance(n, RemoteSourceNode)
                            for n in P.walk_plan(dep.root)):
                        deps.append(dep.id)
            if deps:
                build_deps[frag.id] = deps
        self.phase_waits = []  # (fragment, [deps]) log for tests/EXPLAIN
        # adaptive execution (trino_tpu/adaptive/): between stage
        # completions, the re-planner may rewrite a fragment whose tasks
        # don't exist yet — this is the stage-boundary hook of the
        # reference's AdaptivePlanner, placed after the phased-execution
        # build waits so completed-build actuals are available
        adaptive = self._make_adaptive_planner(session, fragments, workers)
        for frag in list(fragments):
            if phased and not fte and frag.id in build_deps:
                self._await_build_fragments(build_deps[frag.id])
                self.phase_waits.append((frag.id, build_deps[frag.id]))
            if adaptive is not None and frag.partitioning != "single":
                for nf in self._adapt_fragment(
                        adaptive, frag, by_id, fragments, consumer_counts,
                        workers):
                    self._schedule_fragment(
                        session, nf, workers, consumer_counts, fte)
            self._schedule_fragment(session, frag, workers, consumer_counts,
                                    fte)

    def _schedule_fragment(self, session, frag, workers, consumer_counts,
                           fte) -> None:
        """Create the tasks of ONE fragment (source or hash partitioning;
        the root single fragment executes on the coordinator instead)."""
        if frag.partitioning == "hash":
            # one task per key partition (hash-distributed final
            # aggregations and co-partitioned joins): task i pulls
            # buffer/partition i from every upstream producer. Under
            # FTE these tasks retry like source tasks — their inputs
            # are durable per-partition spool files.
            if fte:
                self.fragment_tasks[frag.id] = self._run_fragment_fte(
                    frag, [dict() for _ in workers], workers,
                    consumer_counts)
            else:
                self.fragment_tasks[frag.id] = [
                    self._create_task(frag, wi, 0, {}, workers[wi],
                                      consumer_counts)
                    for wi in range(len(workers))
                ]
            return
        if frag.partitioning != "source":
            return
        # enumerate splits per scan node, interleave across workers
        from trino_tpu.exec import staging as _staging

        per_worker_splits: List[Dict[int, list]] = [dict() for _ in workers]
        scan_nodes = [n for n in P.walk_plan(frag.root)
                      if isinstance(n, P.TableScanNode)]
        for node in scan_nodes:
            conn = session.catalogs[node.catalog]
            floor = max(len(workers), 1)
            # adaptive split sizing (exec/staging.py): big tables fan out
            # finer than one-split-per-worker so task-side staging
            # pipelines over them — but ONLY for single-scan fragments:
            # a multi-scan fragment may be a co-located join whose
            # correctness depends on split i of both tables covering the
            # SAME key range (pushdown handles are guarded inside
            # target_split_count)
            target = floor
            if len(scan_nodes) == 1:
                target = _staging.target_split_count(
                    session, conn, node.schema, node.table, floor=floor,
                    handle=node.table_handle)
            splits = conn.get_splits(node.schema, node.table, target,
                                     constraint=node.constraint,
                                     handle=node.table_handle)
            for i, split in enumerate(splits):
                w = i % len(workers)
                per_worker_splits[w].setdefault(node.id, []).append(split)
        if fte:
            self.fragment_tasks[frag.id] = self._run_fragment_fte(
                frag, per_worker_splits, workers, consumer_counts)
        else:
            self.fragment_tasks[frag.id] = [
                self._create_task(
                    frag, wi, 0, per_worker_splits[wi], workers[wi],
                    consumer_counts)
                for wi in range(len(workers))
            ]

    # ------------------------------------------------- adaptive execution
    def _make_adaptive_planner(self, session, fragments, workers):
        """The per-query AdaptivePlanner, or None when adaptive execution
        is off (adaptive_execution_enabled session property)."""
        props = getattr(session, "properties", None) or {}
        if not bool(props.get("adaptive_execution_enabled", True)):
            return None
        from trino_tpu.adaptive import AdaptivePlanner, RuntimeStatsProvider
        from trino_tpu.sql.planner.fragmenter import fresh_fragment_ids

        def entries():
            with self._tstats_lock:
                return [dict(e) for e in self.task_stats.values()]

        provider = RuntimeStatsProvider(
            entries, sweep_fn=self._sweep_task_stats,
            expected_tasks_fn=lambda fid: len(
                self.fragment_tasks.get(fid, ())))
        return AdaptivePlanner(session, provider, len(workers),
                               fresh_fragment_ids(fragments))

    def _adapt_fragment(self, planner, frag, by_id, fragments,
                        consumer_counts, workers):
        """Run the adaptive rules against one not-yet-scheduled fragment;
        record every applied change as a versioned plan change (info(),
        EXPLAIN ANALYZE annotations, plan/adapt span, adaptive metrics),
        cancel superseded producer tasks, and return the new producer
        fragments to schedule first. Adaptation failures are recorded and
        swallowed — a stats-driven optimization must never fail a query
        that would have run fine unadapted — and rules are isolated from
        each other inside the planner, so a failing rule never discards an
        earlier rule's applied (and audited) change."""
        from trino_tpu.obs import metrics as M

        try:
            new_frags, changes, errors = planner.adapt_fragment(frag, by_id)
        except Exception as e:  # noqa: BLE001 — adaptivity is best-effort
            new_frags, changes, errors = [], [], [str(e)]
        for err in errors:
            with self.tracer.span("plan/adapt", fragment=frag.id) as sp:
                sp.set("error", str(err)[:300])
        for ch in changes:
            self.plan_versions.append(ch.to_dict())
            with self.tracer.span("plan/adapt", fragment=ch.fragment) as sp:
                sp.set("rule", ch.rule)
                sp.set("version", ch.version)
                sp.set("description", ch.description)
            M.ADAPTIVE_ADAPTATIONS.inc(1, ch.rule)
            if ch.rule == "join-distribution":
                direction = ("to_partitioned"
                             if ch.description.endswith("partitioned")
                             else "to_broadcast")
                M.ADAPTIVE_JOIN_FLIPS.inc(1, direction)
            elif ch.rule == "capacity-reseed":
                M.ADAPTIVE_RESEEDED_SOURCES.inc(
                    len(ch.detail.get("runtimeRows", {})))
            elif ch.rule == "skew-mitigation":
                M.ADAPTIVE_SKEW_HOT_PARTITIONS.inc(
                    len(ch.detail.get("hotPartitions", ())))
            # the rewrite re-runs superseded producers with a new output
            # shape; the originals' tasks only hold buffers nobody will
            # pull — cancel them (their frozen stats keep the record)
            for fid in ch.supersedes:
                for loc in self.fragment_tasks.get(fid, ()):
                    self._cancel_attempt(loc)
        for nf in new_frags:
            consumer_counts[nf.id] = len(workers)
            fragments.insert(fragments.index(frag), nf)
        return new_frags

    def _adapted_notes(self) -> Dict[int, str]:
        """fragment id -> change description, for the EXPLAIN ANALYZE
        ``[adapted: ...]`` annotations."""
        notes: Dict[int, str] = {}
        for ch in self.plan_versions:
            notes[ch["fragment"]] = ch["description"]
            for fid in ch.get("newFragments", ()):
                notes.setdefault(fid, ch["description"])
            for fid in ch.get("supersedes", ()):
                notes[fid] = "superseded"
        return notes

    MAX_TASK_ATTEMPTS = 3

    def _create_task(self, frag, wi: int, attempt: int, splits, worker,
                     consumer_counts) -> TaskLocation:
        task_id = f"{self.query_id}.{frag.id}.{wi}.a{attempt}"
        req = TaskRequest(
            task_id=task_id,
            query_id=self.query_id,
            fragment_root=frag.root,
            splits=splits,
            upstream=self._upstream_for(frag.root, consumer_index=wi),
            session_properties=self.session_properties,
            consumer_count=consumer_counts.get(frag.id, 1),
            output_partition_channels=getattr(
                frag, "output_partition_channels", None),
            skew_spread_partitions=getattr(
                frag, "skew_spread_partitions", None),
            skew_replicate_partitions=getattr(
                frag, "skew_replicate_partitions", None),
            spool_results=getattr(frag, "spool_results", False),
        )
        # trace-context propagation: the worker parents its task span under
        # the coordinator's current (schedule) span via this header
        status, resp, _ = wire.http_request(
            "POST", f"{worker['url']}/v1/task/{task_id}", req.to_bytes(),
            headers={tracing.TRACEPARENT_HEADER: self.tracer.traceparent()})
        if status >= 400:
            raise RuntimeError(
                f"task create failed on {worker['nodeId']}: "
                f"{resp[:300].decode(errors='replace')}")
        # the create response IS a task-info payload: seed the stats slot
        # immediately so totalSplits is known while the task still runs
        try:
            self._note_task_status(task_id, json.loads(resp))
        except Exception:  # noqa: BLE001 — stats seeding is best-effort
            pass
        return TaskLocation(worker["url"], task_id)

    TASK_ATTEMPT_TIMEOUT = 600.0

    def _run_fragment_fte(self, frag, per_worker_splits, workers,
                          consumer_counts) -> List[TaskLocation]:
        """Fault-tolerant stage execution (reference:
        EventDrivenFaultTolerantQueryScheduler.java:201): all of a stage's
        tasks run CONCURRENTLY; the stage barrier is that every task must
        FINISH (output spooled) before consumers schedule. A failed/
        unreachable/timed-out attempt is canceled (best effort) and retried
        on the next worker — upstreams are never recomputed because their
        outputs persist in the spool."""
        n = len(workers)
        locations: List[Optional[TaskLocation]] = [None] * n
        # per slot: LIST of concurrent attempts (attempt#, loc, deadline,
        # started) — normally one; a straggler gets a SPECULATIVE second
        # (reference: the event-driven FTE scheduler's speculative
        # execution — launch a duplicate of a slow task, first finish wins)
        slots: Dict[int, list] = {}
        top_attempt: Dict[int, int] = {}
        for wi in range(n):
            slots[wi] = [self._start_attempt(
                frag, wi, 0, per_worker_splits, workers, consumer_counts)]
            top_attempt[wi] = 0
        finished_durations: List[float] = []

        def fail_all(msg):
            for atts in slots.values():
                for _a, other, _dl, _t in atts:
                    self._cancel_attempt(other)
                    self._prune_speculative(other)
            raise RuntimeError(msg)

        while slots:
            if self.state.get() == "CANCELED":
                fail_all("query was canceled")
            for wi in list(slots):
                for att in list(slots[wi]):
                    attempt, loc, deadline, started = att
                    state, failure = self._poll_task(loc, deadline)
                    if state is None:
                        continue  # still running
                    if state == "FINISHED":
                        locations[wi] = loc
                        self.task_attempts[loc.task_id] = attempt
                        finished_durations.append(time.monotonic() - started)
                        for _a, other, _dl, _t in slots[wi]:
                            if other is not loc:
                                self._cancel_attempt(other)  # losers
                            self._prune_speculative(other)
                        del slots[wi]
                        break
                    # failed / unreachable / timed out / canceled remotely
                    self._cancel_attempt(loc)
                    self._prune_speculative(loc)
                    if loc is not None:
                        self.retried_tasks.append(loc.task_id)
                    slots[wi].remove(att)
                    if not slots[wi]:
                        if top_attempt[wi] + 1 >= self.MAX_TASK_ATTEMPTS:
                            fail_all(
                                f"task {frag.id}.{wi} failed after "
                                f"{self.MAX_TASK_ATTEMPTS} attempts: {failure}")
                        top_attempt[wi] += 1
                        slots[wi] = [self._start_attempt(
                            frag, wi, top_attempt[wi], per_worker_splits,
                            workers, consumer_counts)]
            # speculation: once siblings establish a duration baseline, a
            # slot still on its FIRST running attempt past factor x median
            # gets a duplicate on a different worker
            if finished_durations and slots:
                med = sorted(finished_durations)[len(finished_durations) // 2]
                threshold = max(self.SPECULATION_MIN_S,
                                self.SPECULATION_FACTOR * med)
                now = time.monotonic()
                for wi, atts in slots.items():
                    if len(atts) != 1:
                        continue  # already speculating (or mid-restart)
                    attempt, loc, _dl, started = atts[0]
                    if attempt != 0:
                        continue  # retried slots keep their attempt budget
                    if loc is None or now - started < threshold:
                        continue
                    if top_attempt[wi] + 1 >= self.MAX_TASK_ATTEMPTS:
                        continue
                    top_attempt[wi] += 1
                    spec = self._start_attempt(
                        frag, wi, top_attempt[wi], per_worker_splits,
                        workers, consumer_counts)
                    atts.append(spec)
                    if spec[1] is not None:
                        self.speculative_tasks.append(spec[1].task_id)
                        self.speculation_history.append(spec[1].task_id)
            time.sleep(0.05)
        return list(locations)

    # speculative-execution policy: duplicate a slot's first attempt when
    # it has run SPECULATION_FACTOR x the median sibling duration (and at
    # least SPECULATION_MIN_S)
    SPECULATION_MIN_S = 2.0
    SPECULATION_FACTOR = 2.0

    def _start_attempt(self, frag, wi, attempt, per_worker_splits, workers,
                       consumer_counts):
        """Create one attempt; creation failure (dead worker at POST) is a
        normal retryable outcome, represented as a slot with loc=None."""
        worker = workers[(wi + attempt) % len(workers)]
        deadline = time.monotonic() + self.TASK_ATTEMPT_TIMEOUT
        try:
            loc = self._create_task(
                frag, wi, attempt, per_worker_splits[wi], worker,
                consumer_counts)
        except Exception:  # noqa: BLE001 — retried like a task failure
            loc = None
        return (attempt, loc, deadline, time.monotonic())

    def _poll_task(self, loc: Optional[TaskLocation], deadline: float):
        """One non-blocking status check: (None, None) while running, else
        (terminal_state, failure)."""
        if loc is None:
            return "FAILED", "task creation failed (worker unreachable)"
        if time.monotonic() > deadline:
            return "FAILED", "task attempt timeout"
        try:
            status, body, _ = wire.http_request(
                "GET", f"{loc.base_url}/v1/task/{loc.task_id}/status",
                timeout=10.0)
        except Exception as e:  # noqa: BLE001 — worker gone counts as failed
            return "FAILED", f"status poll failed: {e}"
        if status >= 400:
            return "FAILED", f"status {status}"
        info = json.loads(body)
        self._note_task_status(loc.task_id, info)
        if info["state"] in ("FINISHED", "FAILED", "CANCELED"):
            return info["state"], info.get("failure")
        return None, None

    def _prune_speculative(self, loc: Optional[TaskLocation]) -> None:
        """Drop a resolved attempt from the in-flight speculation list (the
        speculated task — or the original it duplicated — completed); the
        bounded ``speculation_history`` keeps the record."""
        if loc is not None and loc.task_id in self.speculative_tasks:
            self.speculative_tasks.remove(loc.task_id)

    @staticmethod
    def _cancel_attempt(loc: Optional[TaskLocation]) -> None:
        """Best-effort cancel of a superseded/orphaned attempt so it stops
        consuming worker resources alongside its replacement."""
        if loc is None:
            return
        try:
            wire.http_request(
                "DELETE", f"{loc.base_url}/v1/task/{loc.task_id}", timeout=5.0)
        except Exception:  # noqa: BLE001
            pass

    def _upstream_for(self, root, consumer_index: int = 0) -> Dict[int, list]:
        up: Dict[int, list] = {}
        for node in P.walk_plan(root):
            if isinstance(node, RemoteSourceNode):
                locs = self.fragment_tasks.get(node.fragment_id, [])
                up[node.fragment_id] = [
                    (l.base_url, l.task_id, consumer_index) for l in locs]
        return up

    def _run_root_fragment(self, session, fragments):
        from trino_tpu.exec.memory import page_bytes
        from trino_tpu.obs import metrics as M
        from trino_tpu.server.task import FragmentExecutor

        root_frag = fragments[-1]
        assert root_frag.partitioning == "single"
        # inline-result memory guard, applied DURING the gather: with
        # spooling unavailable, a result past inline_result_max_bytes
        # fails while pulling — before the coordinator has accumulated
        # the whole columnar result in process memory (the post-gather
        # check in _materialize_result only bounds the Python-row
        # blowup). Scoped to the pass-through root shape, where gather
        # bytes == result bytes exactly — a reducing root (single-step
        # aggregation over gathered raw rows) may legitimately gather
        # far more than it outputs. With spooling enabled there is no
        # gather cap: the page is spooled from here, holding
        # ~wire-sized arrays once.
        budget = None
        if (self._spool_config(session) is None
                and self._gather_passthrough(root_frag) is not None):
            budget = int(session.properties.get(
                "inline_result_max_bytes", 256 << 20))
        remote_pages: Dict[int, list] = {}
        for node in P.walk_plan(root_frag.root):
            if isinstance(node, RemoteSourceNode):
                # flow-ledger attribution: the coordinator's root gather
                # is this query's exchange pull (the "task:{qid}." owner
                # prefix groups it with the workers' task pulls)
                client = ExchangeClient(self.fragment_tasks[node.fragment_id],
                                        tracer=self.tracer,
                                        owner=f"task:{self.query_id}.root",
                                        stall_key=(root_frag.id, None))
                client.start()
                if budget is None:
                    remote_pages[node.fragment_id] = client.pages()
                    continue
                pages, gathered = [], 0
                for p in client.iter_pages():
                    gathered += page_bytes(p)
                    if gathered > budget:
                        M.INLINE_RESULT_REJECTIONS.inc()
                        raise RuntimeError(
                            f"gathered result exceeds "
                            f"inline_result_max_bytes={budget} while "
                            "pulling the root fragment's input "
                            "(INLINE_RESULT_TOO_LARGE) — enable "
                            "spooled_results_enabled to serve it as a "
                            "spooled segment manifest, or narrow the "
                            "query")
                    pages.append(p)
                remote_pages[node.fragment_id] = pages
        ex = FragmentExecutor(session, {}, remote_pages)
        self._root_executor = ex  # EXPLAIN ANALYZE: the root stage's stats
        return ex.execute_checked(root_frag.root)

    PHASE_WAIT_TIMEOUT = 300.0

    def _await_build_fragments(self, dep_ids) -> None:
        """Block until every task of the given (already-scheduled) build
        fragments reports FLUSHING or later — its body is done and its
        output is buffered/spooled, so probe tasks can start pulling
        immediately (reference: PhasedExecutionSchedule's stage phases) —
        or reports its output buffer FULL: a split-at-a-time build whose
        frames pass the watermark parks in ``enqueue`` while still
        RUNNING, and only the fragment this wait holds back can drain it
        (customer's 45 MB of raw frames in Q18 at SF 10)."""
        deadline = time.monotonic() + self.PHASE_WAIT_TIMEOUT
        for fid in dep_ids:
            for loc in self.fragment_tasks.get(fid, ()):
                while time.monotonic() < deadline:
                    try:
                        status, body, _ = wire.http_request(
                            "GET",
                            f"{loc.base_url}/v1/task/{loc.task_id}/status",
                            timeout=10.0)
                        if status < 400:
                            info = json.loads(body)
                            self._note_task_status(loc.task_id, info)
                            state = info.get("state")
                            if info.get("outputFull") or state in (
                                    "FLUSHING", "FINISHED", "FAILED",
                                    "CANCELED"):
                                break
                    except Exception:  # noqa: BLE001 — retry until deadline
                        pass
                    if self.state.is_terminal():
                        return
                    time.sleep(0.05)

    def _cancel_tasks(self) -> None:
        for locations in self.fragment_tasks.values():
            for loc in locations:
                try:
                    wire.http_request(
                        "DELETE", f"{loc.base_url}/v1/task/{loc.task_id}",
                        timeout=5.0)
                except Exception:  # noqa: BLE001
                    pass

    def info(self) -> dict:
        stages = self.stage_stats()
        return {
            "queryId": self.query_id,
            "state": self.state.get(),
            "user": self.user,
            "query": self.sql,
            "failure": (self.failure or "").split("\n")[0] or None,
            "cacheStatus": self.cache_status,
            "fastPath": self.fast_path,
            "fragments": {
                str(fid): [l.task_id for l in locs]
                for fid, locs in self.fragment_tasks.items()
            },
            "retriedTasks": list(self.retried_tasks),
            # versioned plan changes the adaptive re-planner applied
            # (rule, fragment, description, superseded/new fragments)
            "planVersions": list(self.plan_versions),
            # live task→stage→query rollup of worker-reported OperatorStats
            # (frozen once the query is terminal — polling stops and
            # FINISHED slots never downgrade)
            "queryStats": self.query_stats(stages),
            "stageStats": stages,
        }


class CoordinatorServer:
    """The coordinator process: discovery registry + dispatch + protocol."""

    def __init__(self, port: int = 0, session_factory=None, resource_group=None,
                 cluster_memory_limit_bytes=None, low_memory_killer=None,
                 authenticator=None, executor_lanes=None,
                 dispatch_queue_capacity=None, executor_plane=None,
                 executor_processes=None, resource_groups_config=None):
        from trino_tpu.server.resource_groups import (
            ResourceGroupTree, config_from_env, load_config_file,
            parse_config)
        from trino_tpu.connector.registry import default_catalogs
        from trino_tpu.server.cluster_memory import (
            ClusterMemoryManager, total_reservation_killer)

        self.registry = NodeRegistry()
        self.cluster_memory = ClusterMemoryManager(
            kill=self._kill_query,
            cluster_limit_bytes=cluster_memory_limit_bytes,
            policy=low_memory_killer or total_reservation_killer)
        # one shared catalog map for every query this server runs: DDL/DML
        # against stateful connectors (memory) must be visible to later
        # statements (reference: MetadataManager's catalog handles living at
        # server scope, not query scope)
        self.catalogs = default_catalogs()
        # system catalog (trino_tpu/connector/system/): bounded completed-
        # query history ring (QueryTracker's query.max-history analog) +
        # the live provider that feeds system.runtime.* and system.metrics
        # from THIS server's state at scan time
        from trino_tpu.server.system_tables import (
            CoordinatorSystemTables, QueryHistory)

        self.history = QueryHistory()
        if "system" in self.catalogs:
            self.catalogs["system"].attach_live_provider(
                CoordinatorSystemTables(self))
        # shared across statements, like catalogs: CREATE FUNCTION on one
        # query is callable from the next (reference: global function store)
        self.udfs: Dict[str, object] = {}

        def _shared_catalog_session(properties):
            from trino_tpu.client.session import Session

            return Session(properties, catalogs=self.catalogs,
                           udfs=self.udfs, matviews=self.matviews)

        self.session_factory = session_factory or _shared_catalog_session
        # query caching subsystem (trino_tpu/cache/): logical-plan cache +
        # result cache shared by every query this server runs; per-query
        # opt-in via the result_cache_enabled session property
        from trino_tpu.cache import QueryCache

        self.query_cache = QueryCache()
        # prepared statements (server/prepared.py): server-wide registry
        # keyed (user, name) so PREPARE survives across statements — our
        # per-query sessions are throwaway; the reference holds these in
        # the client session and replays them per request, which collapses
        # to this registry for a single coordinator
        from trino_tpu.server.prepared import PreparedStatementRegistry

        self.prepared = PreparedStatementRegistry()
        # materialized views (trino_tpu/matview/): server-wide registry
        # shared by every session this coordinator creates; replicated to
        # executor processes via the sync_materialized_view procedure
        from trino_tpu.matview.registry import MaterializedViewRegistry

        self.matviews = MaterializedViewRegistry()
        self.queries: Dict[str, QueryExecution] = {}
        self._qlock = threading.Lock()
        self._qid = itertools.count(1)
        # admission control (reference: resource groups / DispatchManager's
        # resource-group submission). Default: the hierarchical
        # ResourceGroupTree — selector-routed, weighted-fair, with
        # per-group concurrency/queue/memory limits, configured from
        # `resource_groups_config` (a dict or a JSON file path) or the
        # TRINO_TPU_RESOURCE_GROUPS_CONFIG file; config validation runs
        # HERE so a bad file fails server start, not the first query.
        # An explicitly injected `resource_group` gate keeps the legacy
        # flat blocking-submit admission path.
        if resource_group is not None:
            self.resource_groups = None
            self.resource_group = resource_group
        else:
            if resource_groups_config is None:
                roots, selectors = config_from_env()
            elif isinstance(resource_groups_config, str):
                roots, selectors = load_config_file(resource_groups_config)
            else:
                roots, selectors = parse_config(resource_groups_config)
            self.resource_groups = ResourceGroupTree(roots, selectors)
            # group memory limits read the cluster ledger's live
            # per-query bytes (the PR 16 attribution spine)
            self.resource_groups.set_memory_probe(
                self.cluster_memory.query_reservations)
            # the tree also serves the flat gate's read surface (info()
            # feeds /ui); submit()/finish() calls never reach it — the
            # tree path admits at dequeue time
            self.resource_group = self.resource_groups
        # end-user authentication on the public API (None = open cluster;
        # reference: PasswordAuthenticatorManager / jwt — server/auth.py)
        self.authenticator = authenticator
        # event listener SPI (server/events.py; reference:
        # eventlistener/EventListenerManager)
        from trino_tpu.server.events import EventListenerManager

        self.events = EventListenerManager()
        # first in-tree SPI consumer, on by default: slow queries log with
        # their span breakdown (threshold: slow_query_log_threshold_ms
        # session property > TRINO_TPU_SLOW_QUERY_MS env > 30 s default;
        # listeners are exception-isolated, so this can never fail a query)
        from trino_tpu.obs.listeners import SlowQueryLogListener

        self.events.add(SlowQueryLogListener())
        # durable JSONL query history (obs/listeners.QueryLogListener):
        # opt-in via env, exception-isolated like every listener
        import os as _os

        query_log_path = _os.environ.get("TRINO_TPU_QUERY_LOG")
        if query_log_path:
            from trino_tpu.obs.listeners import QueryLogListener

            self.events.add(QueryLogListener(query_log_path))
        self.queries_submitted = 0
        self.start_time = time.time()
        # failure flight recorder (obs/flightrecorder.py): this process's
        # bounded ring of recent span/event/admission records — what the
        # FAILED-query postmortem snapshots on the coordinator side
        from trino_tpu.obs.flightrecorder import FlightRecorder

        self.recorder = FlightRecorder(node_id="coordinator")
        # cluster memory ledger (obs/memledger.py): the process-global
        # ring takes this node's identity once (an in-process worker may
        # have stamped it first — tests run both in one interpreter) and
        # mirrors shed events into the flight recorder for postmortems
        from trino_tpu.obs.memledger import MEMORY_LEDGER

        if not MEMORY_LEDGER.node_id:
            MEMORY_LEDGER.node_id = "coordinator"
        MEMORY_LEDGER.attach_recorder(self.recorder)
        # device profiler (obs/devprofiler.py): same first-server-wins
        # identity stamp; compile-ledger events mirror into the flight
        # recorder so postmortems show recompile storms
        from trino_tpu.obs.devprofiler import (
            DEVICE_PROFILER, install_process_hooks)

        if not DEVICE_PROFILER.node_id:
            DEVICE_PROFILER.node_id = "coordinator"
        DEVICE_PROFILER.attach_recorder(self.recorder)
        install_process_hooks()  # compile listener + GC pause recorder
        # data-plane flow ledger (obs/flowledger.py): same
        # first-server-wins identity stamp; retried transfers mirror
        # into the flight recorder so postmortems show flaky links
        from trino_tpu.obs.flowledger import FLOW_LEDGER

        if not FLOW_LEDGER.node_id:
            FLOW_LEDGER.node_id = "coordinator"
        FLOW_LEDGER.attach_recorder(self.recorder)
        # spooled result segments (server/segments.py): the coordinator's
        # own store — coordinator-local/fast-path queries (and
        # non-trivial-root distributed ones) spool here, so the protocol
        # decision is plan-shape-independent
        from trino_tpu.server.segments import SegmentStore

        self.segments = SegmentStore(node_id="coordinator")
        # OTLP export (obs/otlp.py): on only when TRINO_TPU_OTLP_ENDPOINT
        # is set — completed queries' span trees ship to the collector
        # from a background batch exporter, never the query path
        from trino_tpu.obs import otlp as _otlp

        self.otlp = _otlp.exporter_from_env("trino-tpu-coordinator")
        # dispatch plane / executor plane split (server/dispatch.py): the
        # bounded dispatch queue, the fixed pool of executor lanes that
        # replaced per-query thread creation, the dispatch-plane serving
        # index, and (opt-in) the executor-process pool
        from trino_tpu.server.dispatch import Dispatcher

        self.dispatcher = Dispatcher(
            self, lanes=executor_lanes,
            queue_capacity=dispatch_queue_capacity, plane=executor_plane,
            processes=executor_processes, groups=self.resource_groups)
        # shared IO pool for parallel worker pulls (span dumps, flight-
        # recorder rings): lazily created, shut down with the server —
        # replaces the fresh ThreadPoolExecutor these calls built per
        # invocation on the hot path
        self._io_pool = None
        self._io_pool_lock = threading.Lock()
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
        self.port = self.httpd.server_address[1]
        self.base_url = f"http://127.0.0.1:{self.port}"
        self._serve_thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)

    @property
    def io_pool(self):
        """The server-wide IO thread pool (created on first use)."""
        pool = self._io_pool
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            with self._io_pool_lock:
                if self._io_pool is None:
                    self._io_pool = ThreadPoolExecutor(
                        max_workers=16, thread_name_prefix="coord-io")
                pool = self._io_pool
        return pool

    def start(self) -> None:
        self._serve_thread.start()
        self.dispatcher.ensure_lanes()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.dispatcher.shutdown()
        self.segments.close()
        with self._io_pool_lock:
            pool, self._io_pool = self._io_pool, None
        if pool is not None:
            pool.shutdown(wait=False)
        if self.otlp is not None:
            # flush + stop the exporter thread: a stopped instance must
            # not keep reporting metrics under its service identity
            self.otlp.shutdown()

    # retained terminal queries (history for /v1/query) — oldest evicted
    # with their materialized result rows (reference: query.max-history)
    MAX_QUERY_HISTORY = 100

    def submit(self, sql: str, properties: Optional[dict] = None,
               user: str = "anonymous", source: str = "") -> QueryExecution:
        # resource-group classification runs FIRST (cheap: a regex chain
        # over user/source/session properties) so the overload turn-around
        # below can name the saturated group and its queue depth
        group = None
        if self.resource_groups is not None:
            group = self.resource_groups.select(user, source,
                                                properties or {})
        # typed overload turn-around BEFORE any per-query state is built:
        # a full dispatch queue raises DispatchRejected (the protocol
        # surface answers 429 + Retry-After), never a hang or a thread
        self.dispatcher.precheck(group)
        query_id = f"q{time.strftime('%Y%m%d')}_{next(self._qid):05d}_{uuid.uuid4().hex[:5]}"
        execution = QueryExecution(
            query_id, sql, properties or {}, self.registry, self.session_factory,
            user=user, query_cache=self.query_cache,
            prepared_registry=self.prepared)
        execution.resource_group = group
        execution.source = source
        # flight-recorder hookup: closed spans mirror into the process
        # ring, and the execution can snapshot it for its postmortem
        execution.recorder = self.recorder
        execution.tracer.recorder = self.recorder
        execution.io_pool = self.io_pool
        execution.dispatcher = self.dispatcher
        # spooled result protocol hookup + an opportunistic TTL sweep
        # (rate-limited in the store) on the submit cadence
        execution.segment_store = self.segments
        execution.segment_base_url = self.base_url
        self.segments.maybe_sweep()
        self.recorder.record("admission", "submitted", queryId=query_id,
                             user=user)
        with self._qlock:
            if len(self.queries) > self.MAX_QUERY_HISTORY:
                # scan for prunable terminals only once the registry can
                # actually be over budget — the per-submit full scan this
                # replaces was measurable on the serving hot path
                terminal = [qid for qid, q in self.queries.items()
                            if q.state.is_terminal()]
                for qid in terminal[: max(0, len(terminal)
                                          - self.MAX_QUERY_HISTORY)]:
                    del self.queries[qid]
            self.queries[query_id] = execution
            self.queries_submitted += 1
        from trino_tpu.server import events as ev

        created_at = time.time()
        self.events.fire_created(
            ev.QueryCreatedEvent(query_id, user, sql, created_at))
        def fire_terminal(state):
            if state not in ("FINISHED", "FAILED", "CANCELED"):
                return
            try:
                # serving-index maintenance (server/dispatch.py): learn
                # MISS-then-filled SELECTs, clear on non-SELECT statements
                self.dispatcher.note_completion(
                    execution, execution.is_plain_select)
            except Exception:  # noqa: BLE001 — index upkeep must never
                pass  # disturb the terminal transition
            now = time.time()
            wall = now - created_at
            from trino_tpu.obs import metrics as M

            M.QUERY_SECONDS.observe(wall, state)
            self.recorder.record("event", "query-completed",
                                 queryId=query_id, state=state,
                                 wallS=round(wall, 6))
            # query-peak histogram (memory ledger): one sample per
            # terminal query, from the task→stage→query rollup
            try:
                peak = int(execution.query_stats().get("peakBytes") or 0)
                if peak:
                    M.QUERY_PEAK_MEMORY_BYTES.observe(peak, state)
            except Exception:  # noqa: BLE001 — observability, never a
                pass  # reason to disturb the terminal transition
            # the phase ledger: computed ONCE here (the merged span tree
            # exists now) and fed into the per-phase histogram — this is
            # where every millisecond of the wall gets attributed
            timeline = None
            try:
                timeline = execution.timeline_dict()
                if timeline is not None:
                    from trino_tpu.obs.timeline import observe_phases

                    observe_phases(timeline)
            except Exception:  # noqa: BLE001 — the ledger is
                pass  # observability, never a reason to disturb terminal
            # kernel-ledger fold (device profiler): persist the merged
            # per-operator kernel rows ONCE — system.runtime.kernels and
            # the per-operator launch/overhead metrics read the folded
            # store, so nothing bumps per-dispatch on the serving path
            try:
                execution.fold_kernel_profile()
            except Exception:  # noqa: BLE001 — observability only
                pass
            # flow-ledger fold: freeze the straggler verdicts and bump
            # the per-cause counter ONCE — system.runtime.stragglers and
            # the /flows surface read the frozen verdicts after this
            try:
                execution.fold_flow_profile()
            except Exception:  # noqa: BLE001 — observability only
                pass
            # a FAILED/CANCELED query's result segments will never be
            # fetched — reclaim the coordinator-hosted ones now instead
            # of waiting out the TTL (worker-hosted ones TTL out; their
            # producing tasks normally abandoned them already)
            if state != "FINISHED":
                try:
                    self.segments.drop_query(query_id)
                except Exception:  # noqa: BLE001 — lifecycle best-effort
                    pass
            # FAILED queries carry the flight-recorder postmortem —
            # normally captured by the query thread before the terminal
            # transition; a kill() from another thread captures here
            if state == "FAILED" and execution.postmortem is None:
                try:
                    execution.capture_postmortem()
                except Exception:  # noqa: BLE001 — best-effort forensics
                    pass
            self.events.fire_completed(
                ev.QueryCompletedEvent(
                    query_id, user, sql, state, created_at, now,
                    wall, len(execution.rows), execution.failure,
                    spans=tuple(execution.tracer.to_dicts()),
                    session_properties=dict(execution.session_properties),
                    timeline=timeline,
                    postmortem=execution.postmortem,
                )
            )
            if self.otlp is not None:
                # ship the coordinator half of the trace (workers export
                # their own task spans at task completion) — with the
                # query's per-link flow totals + straggler count as
                # resource attributes, so the collector sees the data
                # plane without a second export path
                otlp_attrs = {"query_id": query_id, "query.user": user,
                              "query.state": state}
                try:
                    by_link: Dict[str, int] = {}
                    for r in execution.flow_rows_live():
                        by_link[r["link"]] = (by_link.get(r["link"], 0)
                                              + int(r["bytes"]))
                    for link, nbytes in sorted(by_link.items()):
                        otlp_attrs[f"flow.{link}.bytes"] = nbytes
                    otlp_attrs["flow.stragglers"] = len(
                        execution.straggler_rows())
                except Exception:  # noqa: BLE001 — observability only
                    pass
                self.otlp.export_spans(
                    execution.tracer.to_dicts(), execution.tracer.trace_id,
                    otlp_attrs)
            # completed-query history (system.runtime.queries coverage of
            # finished queries): retention knobs are session-property-
            # gated, read from THIS query's submitted properties — but the
            # ring is SHARED server state, so a session may only GROW
            # retention (clamped at the server defaults): otherwise any
            # session completing one query with query_max_history=1 would
            # wipe every other user's history
            from trino_tpu.server.system_tables import (
                DEFAULT_MAX_HISTORY, DEFAULT_MIN_EXPIRE_AGE_MS, query_record)

            try:
                self.history.record(
                    query_record(execution, state=state, ended_at=now),
                    max_history=max(DEFAULT_MAX_HISTORY, _int_property(
                        execution.session_properties, "query_max_history",
                        DEFAULT_MAX_HISTORY)),
                    min_expire_age_ms=max(
                        DEFAULT_MIN_EXPIRE_AGE_MS, _int_property(
                            execution.session_properties,
                            "query_min_expire_age_ms",
                            DEFAULT_MIN_EXPIRE_AGE_MS)))
            except Exception:  # noqa: BLE001 — history is observability,
                pass  # never a reason to disturb the terminal transition

        execution.state.add_listener(fire_terminal)
        # dispatch is ASYNC: the submit POST returns a QUEUED payload
        # and the client polls nextUri; the dispatcher either answers the
        # query on the dispatch plane (serving index), enqueues it for an
        # executor lane, or rejects it typed when the queue is full
        # (reference: QueuedStatementResource's queued/executing split)
        from trino_tpu.server.dispatch import DispatchRejected

        try:
            self.dispatcher.dispatch(execution)
        except DispatchRejected as e:
            # lost the capacity race after registration: unregister and
            # surface the same typed rejection the precheck gives. The
            # rejected statement executed NOTHING — it must not count as
            # a non-SELECT completion and wipe the serving index right
            # when overload needs it most
            execution.is_plain_select = True
            with self._qlock:
                self.queries.pop(query_id, None)
            execution.failure = str(e)
            execution.ended_at = time.time()
            execution.state.set("FAILED")
            self.recorder.record("admission", "dispatch-rejected",
                                 queryId=query_id, user=user)
            raise
        return execution

    def _admit(self, execution: QueryExecution) -> bool:
        """Admission, run on an executor lane after dequeue: the resource
        group (per-user fairness) then the cluster-memory gate. Returns
        False when the query failed admission or went terminal (canceled)
        while queued — the lane moves on."""
        user = execution.user
        if self.resource_groups is not None:
            # group-aware path: the tree ALREADY admitted this query at
            # dequeue time (weighted-fair pick under concurrency + memory
            # eligibility) — release its slot at terminal, or right now
            # if it went terminal (canceled) between dequeue and here
            qid = execution.query_id
            if execution.state.is_terminal():
                self.resource_groups.finish(qid)
                return False
            groups = self.resource_groups
            execution.state.add_listener(
                lambda s: groups.finish(qid)
                if s in ("FINISHED", "FAILED", "CANCELED") else None)
            self.recorder.record(
                "admission", "admitted", queryId=qid, user=user,
                group=execution.resource_group)
        else:
            if execution.state.is_terminal():  # canceled while queued
                return False
            if not self.resource_group.submit(timeout=600.0, user=user):
                execution.failure = (
                    "Query queue is full (resource group limit)")
                self.recorder.record("admission", "queue-full",
                                     queryId=execution.query_id, user=user)
                execution.state.set("FAILED")
                return False
            self.recorder.record("admission", "admitted",
                                 queryId=execution.query_id, user=user)
        # cluster-memory admission: dispatch blocks while the cluster
        # pool is over its limit (reference: ClusterMemoryManager's
        # query.max-memory gate) — the killer frees it if needed; a
        # cluster that stays saturated past the deadline FAILS the
        # query loudly (never silently dispatches over the limit)
        deadline = time.monotonic() + 600.0
        while (not self.cluster_memory.has_headroom()
               and not execution.state.is_terminal()
               and time.monotonic() < deadline):
            time.sleep(0.2)
        if (not execution.state.is_terminal()
                and not self.cluster_memory.has_headroom()):
            execution.failure = (
                "Cluster is out of memory and did not recover within the "
                "admission deadline (EXCEEDED_CLUSTER_MEMORY)")
            execution.state.set("FAILED")
        if execution.state.is_terminal():  # canceled/killed while queued
            # tree path: its terminal listener (registered above) already
            # released the group slot when the state flipped
            if self.resource_groups is None:
                self.resource_group.finish(user=user)
            return False
        if self.resource_groups is None:
            execution.state.add_listener(
                lambda s: self.resource_group.finish(user=user)
                if s in ("FINISHED", "FAILED", "CANCELED") else None)
        return True

    def get_query(self, query_id: str) -> Optional[QueryExecution]:
        with self._qlock:
            return self.queries.get(query_id)

    def query_state_counts(self):
        """Public metrics accessor: ``(queries-by-state counts, result rows
        held by FINISHED queries)`` — the exporter reads this instead of
        reaching into ``_qlock``/``queries`` privates."""
        by_state: Dict[str, int] = {}
        total_rows = 0
        with self._qlock:
            queries = list(self.queries.values())
        for q in queries:
            st = q.state.get()
            by_state[st] = by_state.get(st, 0) + 1
            if st == "FINISHED":
                total_rows += len(q.rows)
        return by_state, total_rows

    def query_trace(self, query_id: str,
                    include_recorder: bool = False) -> Optional[dict]:
        """Assemble the query's cross-process span tree: coordinator-side
        spans merge with each worker task's span dump (pulled on demand from
        ``GET /v1/task/{id}/spans`` — task-span collection is lazy, like the
        reference's trace export being independent of the query path).
        ``include_recorder`` attaches the flight-recorder postmortem: the
        one captured at FAILED, else a live merge of the rings
        (``?recorder=1``)."""
        q = self.get_query(query_id)
        if q is None:
            return None
        spans = (q.tracer.to_dicts() + list(q.extra_spans)
                 + q.worker_spans())
        from trino_tpu.obs.trace import build_tree

        trace = {
            "queryId": q.query_id,
            "traceId": q.tracer.trace_id,
            "state": q.state.get(),
            "spanCount": len(spans),
            # the phase ledger rides the trace payload once terminal —
            # the span tree is the evidence, the ledger the verdict
            "timeline": q.timeline_dict(),
            "root": build_tree(spans),
        }
        if include_recorder:
            # the stored postmortem exists only for FAILED queries (frozen
            # at failure time); any other state merges the LIVE rings on
            # every read — never cached, so repeated reads see fresh
            # process context
            trace["postmortem"] = (
                q.postmortem if q.postmortem is not None
                else q.capture_postmortem(store=False))
        return trace

    def _kill_query(self, query_id: str, reason: str) -> None:
        q = self.get_query(query_id)
        if q is not None and not q.state.is_terminal():
            q.kill(reason)


def _result_payload(server: CoordinatorServer, q: QueryExecution, token: int) -> dict:
    state = q.state.get()
    # summary stats ride EVERY statement response (reference: the
    # StatementStats block of the client protocol) so clients can render
    # live progress while polling nextUri
    payload: dict = {
        "id": q.query_id,
        "stats": {**q.query_stats(q.stage_stats(include_operators=False)),
                  "state": state},
    }
    if state == "FAILED":
        payload["error"] = {"message": q.failure or "query failed"}
        return payload
    if state == "CANCELED":
        payload["error"] = {"message": "query was canceled"}
        return payload
    if state != "FINISHED":
        payload["nextUri"] = f"{server.base_url}/v1/statement/executing/{q.query_id}/{token}"
        return payload
    if q.set_session:
        payload["setSessionProperties"] = {k: v for k, v in q.set_session.items()}
    if q.reset_session:
        payload["resetSessionProperties"] = list(q.reset_session)
    # PREPARE/DEALLOCATE round-trip (the X-Trino-Added-Prepare /
    # X-Trino-Deallocated-Prepare analog): clients track which names are
    # live so drivers (DBAPI) can skip re-PREPARE on reuse
    if q.add_prepared:
        payload["addedPreparedStatements"] = dict(q.add_prepared)
    if q.deallocated_prepared:
        payload["deallocatedPreparedStatements"] = list(q.deallocated_prepared)
    if q.result_segments is not None:
        # spooled protocol: the response carries the segment MANIFEST —
        # clients fetch the data from the producers' segment endpoints
        # in parallel; this coordinator never pages the rows
        q.last_drain_at = time.time()
        payload["columns"] = [{"name": c} for c in q.columns]
        payload["segments"] = [dict(e) for e in q.result_segments]
        payload["spooled"] = q.spooled
        return payload
    start = token * RESULT_PAGE_ROWS
    chunk = q.rows[start : start + RESULT_PAGE_ROWS]
    # client-drain bookkeeping for the phase ledger: the query's wall is
    # over, but the client is still fetching pages
    q.last_drain_at = time.time()
    payload["columns"] = [{"name": c} for c in q.columns]
    payload["data"] = [list(_jsonable(v) for v in row) for row in chunk]
    if start + RESULT_PAGE_ROWS < len(q.rows):
        payload["nextUri"] = f"{server.base_url}/v1/statement/executing/{q.query_id}/{token + 1}"
    return payload


def _drain_body(server: CoordinatorServer, q: QueryExecution,
                token: int) -> bytes:
    """Serialize one statement-protocol response and charge its bytes to
    the query's ``client-drain`` flow when it carries results (rows or a
    segment manifest). The serialize wall is the drain cost the
    coordinator can see — socket write time belongs to the client."""
    import time as _time

    t0 = _time.perf_counter()
    payload = _result_payload(server, q, token)
    body = json.dumps(payload).encode()
    if "data" in payload or "segments" in payload:
        try:
            from trino_tpu.obs.flowledger import FLOW_LEDGER

            FLOW_LEDGER.record_transfer(
                "client-drain", f"drain:{q.query_id}", len(body),
                _time.perf_counter() - t0,
                pages=len(payload.get("data") or payload.get("segments")
                          or ()),
                src=FLOW_LEDGER.node_id or "coordinator", dst="client",
                direction="send")
            if "nextUri" not in payload and "stats" in payload:
                # final page: refresh the stats flows block so the CLI
                # summary's drain tag counts THIS response's bytes (the
                # stats were built before the record above) — one extra
                # dumps of the last page buys a truthful summary
                payload["stats"]["flows"] = q.flow_stats_block()
                body = json.dumps(payload).encode()
        except Exception:  # noqa: BLE001 — accounting never fails serving
            pass
    return body


CACHE_HEADER = "X-Trino-Tpu-Cache"


def _cache_header(q: QueryExecution) -> Optional[dict]:
    """Result-cache disposition header (HIT|MISS|BYPASS), once the query
    has decided it (None while still queued/planning)."""
    return {CACHE_HEADER: q.cache_status} if q.cache_status else None


def _profiler_snapshot() -> dict:
    """The postmortem's device-profiler block: newest compile-ledger
    events + the monotonic utilization counters."""
    try:
        from trino_tpu.obs.devprofiler import DEVICE_PROFILER

        return {"compiles": DEVICE_PROFILER.compile_rows(limit=16),
                "counters": DEVICE_PROFILER.counters()}
    except Exception:  # noqa: BLE001 — best-effort forensics
        return {}


def _flows_snapshot() -> dict:
    """The postmortem's flow-ledger block: per-link rollups, net totals,
    the newest transfer records and the stall rollups."""
    try:
        from trino_tpu.obs.flowledger import FLOW_LEDGER

        return FLOW_LEDGER.flow_snapshot()
    except Exception:  # noqa: BLE001 — best-effort forensics
        return {}


def _int_property(properties: dict, name: str, default: int) -> int:
    """Integer session property from a raw (wire-string) property map —
    malformed values fall back like the typed registry's defaults."""
    try:
        return int(properties.get(name, default))
    except (TypeError, ValueError):
        return default


def _jsonable(v):
    import datetime
    import decimal

    if isinstance(v, (datetime.date, datetime.datetime)):
        return v.isoformat()
    if isinstance(v, decimal.Decimal):
        return str(v)
    return v


def _render_ui(server: CoordinatorServer) -> str:
    """Minimal cluster status page (reference role: core/trino-web-ui's
    query list + worker view, server-rendered instead of a React SPA)."""
    import html

    rows = []
    with server._qlock:
        queries = sorted(server.queries.items(), reverse=True)
    for qid, q in queries[:50]:
        state = q.state.get()
        stage_list = q.stage_stats(include_operators=False)
        qs = q.query_stats(stage_list)
        stages = " ".join(
            f"f{s['stageId']}: {s['outputRows']} rows/"
            f"{s['wallS'] * 1e3:.0f}ms"
            for s in stage_list) or "—"
        progress = (f"{qs['completedSplits']}/{qs['totalSplits']} splits, "
                    f"{qs['elapsedMs'] / 1e3:.1f}s")
        rows.append(
            f"<tr><td>{html.escape(qid)}</td><td class='s {state}'>{state}</td>"
            f"<td>{html.escape(q.user)}</td>"
            f"<td><code>{html.escape(q.sql.strip()[:120])}</code></td>"
            f"<td>{html.escape(progress)}</td>"
            f"<td>{html.escape(stages)}</td>"
            f"<td>{len(q.retried_tasks)}</td></tr>")
    nodes = "".join(
        f"<tr><td>{html.escape(n['nodeId'])}</td>"
        f"<td>{html.escape(n['url'])}</td></tr>"
        for n in server.registry.alive())
    # recent queries from the completed-query history ring (the durable
    # record: survives the live registry's pruning)
    recent = []
    for rec in server.history.snapshot()[:50]:
        recent.append(
            f"<tr><td>{html.escape(rec['queryId'])}</td>"
            f"<td class='s {rec['state']}'>{rec['state']}</td>"
            f"<td>{rec['elapsedMs'] / 1e3:.1f}s</td>"
            f"<td>{rec['resultRows']}</td>"
            f"<td>{html.escape(rec['cacheStatus'] or '—')}</td>"
            f"<td>{rec['adaptations']}</td>"
            f"<td><code>{html.escape((rec['query'] or '').strip()[:100])}"
            f"</code></td></tr>")
    recent_html = "".join(recent) or (
        "<tr><td colspan='7'>no completed queries yet</td></tr>")
    rg = server.resource_group.info()
    group_rows = ""
    for gname, g in sorted(rg.get("groups", {}).items()):
        group_rows += (
            f"<tr><td>{html.escape(gname)}</td><td>{g['state']}</td>"
            f"<td>{g['running']}</td><td>{g['queued']}</td>"
            f"<td>{g['served']}</td><td>{g['weight']}</td></tr>")
    groups_html = (
        "<h2>resource groups <small>(<code>select * from "
        "system.runtime.resource_groups</code>)</small></h2><table>"
        "<tr><th>group</th><th>state</th><th>running</th><th>queued</th>"
        f"<th>served</th><th>weight</th></tr>{group_rows}</table>"
        if group_rows else "")
    return f"""<!doctype html><html><head><meta http-equiv="refresh" content="3">
<title>trino-tpu</title><style>
body{{font-family:monospace;margin:2em;background:#111;color:#ddd}}
table{{border-collapse:collapse;margin:1em 0;width:100%}}
td,th{{border:1px solid #333;padding:4px 10px;text-align:left}}
.s.FINISHED{{color:#6c6}}.s.FAILED{{color:#e66}}.s.RUNNING{{color:#6ae}}
h1,h2{{color:#fff}}</style></head><body>
<h1>trino-tpu coordinator</h1>
<p>resource group "{rg['name']}": {rg['running']} running, {rg['queued']} queued
(limit {rg['hardConcurrencyLimit']})</p>
{groups_html}
<h2>workers</h2><table><tr><th>node</th><th>url</th></tr>{nodes}</table>
<h2>queries <small>(<a href="#recent" style="color:#6ae">recent
queries</a> · <code>select * from system.runtime.queries</code>)</small></h2>
<table>
<tr><th>query id</th><th>state</th><th>user</th><th>query</th>
<th>progress</th><th>stages (rows/wall)</th><th>retries</th></tr>
{''.join(rows)}</table>
<h2 id="recent">recent queries</h2><table>
<tr><th>query id</th><th>state</th><th>elapsed</th><th>rows</th>
<th>cache</th><th>adaptations</th><th>query</th></tr>
{recent_html}</table></body></html>"""


def _make_handler(server: CoordinatorServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # close keep-alive connections idle past this (the client pool's
        # idle TTL is shorter, so the client normally closes first)
        timeout = 30
        # TCP_NODELAY: headers and body flush as separate writes — with
        # Nagle on, the second write stalls behind the delayed ACK
        disable_nagle_algorithm = True

        def log_message(self, fmt, *args):
            pass

        def _send(self, status: int, body: bytes = b"",
                  content_type: str = "application/json",
                  headers: Optional[dict] = None):
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _read_body(self) -> bytes:
            n = int(self.headers.get("Content-Length", "0"))
            return self.rfile.read(n)

        def do_PUT(self):
            m = _ANNOUNCE_RE.match(self.path)
            if m:
                body = self._read_body()
                if not wire.verify(body, self.headers.get(wire.H_INTERNAL_AUTH)):
                    self._send(401, b'{"error": "bad internal signature"}')
                    return
                info = json.loads(body)
                server.registry.announce(m.group(1), info["url"], info)
                server.cluster_memory.update(m.group(1), info)
                self._send(200, b"{}")
                return
            self._send(404)

        def do_POST(self):
            if self.path == "/v1/statement":
                sql = self._read_body().decode()
                props = {}
                for header, value in self.headers.items():
                    if header.lower().startswith("x-trino-session-"):
                        props[header[len("x-trino-session-"):].lower()] = value
                user = self.headers.get("X-Trino-User", "anonymous")
                if server.authenticator is not None and server.authenticator.required:
                    from trino_tpu.server.auth import AuthenticationError

                    try:
                        identity = server.authenticator.authenticate_header(
                            self.headers.get("Authorization"))
                    except AuthenticationError as e:
                        self._send(401, json.dumps(
                            {"error": {"message": f"Authentication failed: {e}"}}
                        ).encode(), headers={
                            "WWW-Authenticate": 'Basic realm="trino-tpu", Bearer'})
                        return
                    # the authenticated principal wins over the client's
                    # claimed user header (no impersonation by default)
                    user = identity.user
                # the client-reported source (X-Trino-Source): a
                # resource-group selector routing dimension, like user
                source = self.headers.get("X-Trino-Source", "")
                from trino_tpu.server.dispatch import DispatchRejected

                try:
                    q = server.submit(sql, props, user=user, source=source)
                except DispatchRejected as e:
                    # typed overload: 429 + Retry-After with structured
                    # retry guidance — the client backs off and retries
                    # instead of piling a thread onto a saturated server
                    self._send(429, json.dumps(e.payload()).encode(),
                               headers={"Retry-After":
                                        f"{e.retry_after_s:g}"})
                    return
                # brief long-poll: short queries finish inside this
                # window, collapsing the protocol to ONE round trip
                # (submit response already carries the result page)
                if not q.state.is_terminal():
                    q.state.wait_for_terminal(0.5)
                self._send(200, _drain_body(server, q, 0),
                           headers=_cache_header(q))
                return
            self._send(404)

        def _authenticated(self, query=None):
            """Gate for query-scoped routes when an authenticator is
            configured: results, query info, and cancel carry user data and
            control — they are NOT open even though submission already
            authenticated (predictable query ids must not leak results).
            With ``query``, the authenticated principal must also OWN it
            (reference: AccessControl.checkCanViewQueryOwnedBy /
            checkCanKillQueryOwnedBy)."""
            if server.authenticator is None or not server.authenticator.required:
                return True
            from trino_tpu.server.auth import AuthenticationError

            try:
                identity = server.authenticator.authenticate_header(
                    self.headers.get("Authorization"))
            except AuthenticationError as e:
                self._send(401, json.dumps(
                    {"error": {"message": f"Authentication failed: {e}"}}
                ).encode(), headers={
                    "WWW-Authenticate": 'Basic realm="trino-tpu", Bearer'})
                return False
            if query is not None and query.user != identity.user:
                self._send(403, json.dumps(
                    {"error": {"message":
                               "Access Denied: query belongs to another user"}}
                ).encode())
                return False
            return True

        def do_GET(self):
            m = _RESULT_RE.match(self.path)
            if m:
                q = server.get_query(m.group(1))
                if not self._authenticated(query=q):
                    return
                if q is None:
                    self._send(404, b'{"error": "no such query"}')
                    return
                # long-poll briefly so clients don't busy-spin
                if not q.state.is_terminal():
                    q.state.wait_for_terminal(0.5)
                self._send(200, _drain_body(server, q, int(m.group(2))),
                           headers=_cache_header(q))
                return
            # the trace route accepts a query string (?recorder=1 attaches
            # the flight-recorder postmortem); other routes stay exact
            from urllib.parse import parse_qs, urlsplit

            url_parts = urlsplit(self.path)
            m = _TRACE_RE.match(url_parts.path)
            if m:
                q = server.get_query(m.group(1))
                if not self._authenticated(query=q):
                    return
                params = parse_qs(url_parts.query)
                with_recorder = params.get("recorder", ["0"])[0] not in (
                    "0", "", "false")
                trace = (server.query_trace(
                            m.group(1), include_recorder=with_recorder)
                         if q is not None else None)
                if trace is None:
                    # covers eviction between the two lookups too: never
                    # answer 200 with a null body
                    self._send(404, b'{"error": "no such query"}')
                    return
                self._send(200, json.dumps(trace).encode())
                return
            m = _PROFILE_RE.match(url_parts.path)
            if m:
                # the device-profiler read surface (obs/devprofiler.py):
                # merged coordinator+worker kernel rows, this query's
                # compile events, utilization samples, phase ledger
                q = server.get_query(m.group(1))
                if not self._authenticated(query=q):
                    return
                if q is None:
                    self._send(404, b'{"error": "no such query"}')
                    return
                profile = q.profile_dict()
                if profile is None:
                    self._send(404, json.dumps({"error": (
                        "profile aged out: the query's kernel rows have "
                        "left the device profiler's LRU "
                        "(devprofiler.MAX_QUERY_PROFILES)")}).encode())
                    return
                self._send(200, json.dumps(profile).encode())
                return
            m = _FLOWS_RE.match(url_parts.path)
            if m:
                # the flow-ledger read surface (obs/flowledger.py): this
                # query's cluster-merged per-link transfer rows, the
                # straggler verdicts, and the backpressure stall rollups
                q = server.get_query(m.group(1))
                if not self._authenticated(query=q):
                    return
                if q is None:
                    self._send(404, b'{"error": "no such query"}')
                    return
                self._send(200, json.dumps(q.flows_dict()).encode())
                return
            m = _QUERY_RE.match(self.path)
            if m:
                q = server.get_query(m.group(1))
                if not self._authenticated(query=q):
                    return
                if q is None:
                    self._send(404, b'{"error": "no such query"}')
                    return
                self._send(200, json.dumps(q.info()).encode())
                return
            m = _SEGMENT_RE.match(self.path)
            if m:
                # coordinator-hosted spooled result segments: the id is
                # an unguessable capability (the reference's pre-signed
                # segment URI model), so no further gate is applied —
                # range/ack semantics live in server/segments.py
                from trino_tpu.server.segments import segment_response

                sid = m.group(1)
                q = server.get_query(sid.split(".", 1)[0])
                if q is not None:
                    q.last_segment_fetch_at = time.time()
                status, body, seg_headers, ctype = segment_response(
                    server.segments, sid, self.headers.get("Range"))
                self._send(status, body, ctype, seg_headers)
                return
            if self.path == "/v1/node":
                self._send(200, json.dumps(server.registry.alive()).encode())
                return
            if self.path == "/v1/info":
                self._send(200, json.dumps(
                    {"coordinator": True, "state": "ACTIVE"}).encode())
                return
            if self.path == "/v1/metrics":
                from trino_tpu.server.events import render_metrics

                self._send(200, render_metrics(server).encode(),
                           "text/plain; version=0.0.4")
                return
            if self.path in ("/ui", "/ui/"):
                self._send(200, _render_ui(server).encode(), "text/html")
                return
            self._send(404)

        def do_DELETE(self):
            m = _RESULT_RE.match(self.path)
            if m:
                q = server.get_query(m.group(1))
                if not self._authenticated(query=q):
                    return
                if q is not None:
                    q.cancel()
                self._send(204)
                return
            m = _SEGMENT_RE.match(self.path)
            if m:
                # segment ACK: data fetches go straight to the owning
                # producer, but the tiny ack DELETE routes through the
                # coordinator — it forwards worker-hosted deletes and
                # stamps the query's segment-fetch clock either way
                sid = m.group(1)
                q = server.get_query(sid.split(".", 1)[0])
                if q is not None:
                    q.last_segment_fetch_at = time.time()
                    worker = q._segment_workers.get(sid)
                    if worker is not None:
                        try:
                            wire.http_request(
                                "DELETE", f"{worker}/v1/segment/{sid}",
                                timeout=10.0)
                        except Exception:  # noqa: BLE001 — TTL backstop
                            pass
                        self._send(204)
                        return
                server.segments.ack(sid)
                self._send(204)
                return
            self._send(404)

    return Handler


def main() -> None:
    """Entry point: ``python -m trino_tpu.server.coordinator --port N``."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()
    from trino_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    c = CoordinatorServer(args.port)
    c.start()
    print(json.dumps({"url": c.base_url}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        c.stop()


if __name__ == "__main__":
    main()
