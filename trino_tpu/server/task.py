"""Worker task engine: task lifecycle + fragment execution.

Reference: ``execution/SqlTaskManager.java:109`` (owns all tasks on a
worker), ``SqlTaskExecution.java:85`` (fragment → drivers), ``TaskState``
FSM. The driver loop's role is filled by whole-fragment execution over the
device (exec/executor.py) — one task = one fragment instance = one batch
program, not a page-at-a-time operator chain (SURVEY.md §7.1).

A ``TaskRequest`` ships the plan-fragment subtree (pickled — the analog of
the reference's JSON-serialized ``PlanFragment``), the splits assigned to
this task (``SOURCE_DISTRIBUTION`` placement, chosen by the coordinator),
and upstream task locations per RemoteSourceNode fragment id.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pickle
import threading
import time
import traceback
from typing import Dict, List, Optional

from trino_tpu.data.page import Page
from trino_tpu.data.serde import CODEC_NONE, CODEC_ZLIB, serialize_page
from trino_tpu.exec.executor import Executor, operator_kind, page_platform
from trino_tpu.exec.operator_stats import OperatorStats
from trino_tpu.obs import metrics as M
from trino_tpu.obs import trace as tracing
from trino_tpu.obs.devprofiler import (
    charge_to, copy_kernel_row, count_charged, host_read, merge_kernel_rows,
    new_kernel_row)
from trino_tpu.server.buffer import OutputBuffer, PartitionedOutputBuffer
from trino_tpu.server.statemachine import StateMachine, task_state_machine
from trino_tpu.sql.planner import plan as P
from trino_tpu.sql.planner.fragmenter import RemoteSourceNode


@dataclasses.dataclass
class TaskRequest:
    """Everything a worker needs to run one task (pickle wire format;
    reference: TaskUpdateRequest posted to POST /v1/task/{taskId})."""

    task_id: str
    query_id: str
    fragment_root: P.PlanNode
    splits: Dict[int, List]  # scan plan-node id -> [Split]
    upstream: Dict[int, List]  # fragment id -> [(base_url, task_id, buffer_id)]
    session_properties: Dict[str, object]
    # how many downstream consumers will pull this task's output (reference:
    # OutputBuffers — the consumer set is declared when the task is created)
    consumer_count: int = 1
    # when set, the task's output page is hash-partitioned by these channels
    # into consumer_count DISTINCT streams — consumer i pulls only partition
    # i (reference: PagePartitioner.java:134-149, FIXED_HASH_DISTRIBUTION's
    # producer half). None = every consumer reads the same stream.
    output_partition_channels: Optional[List[int]] = None
    # adaptive skew mitigation (trino_tpu/adaptive/): HOT partitions whose
    # rows this producer spreads round-robin across all partitions (probe
    # side of a salted repartition join) or replicates into every
    # partition (build side) — see parallel/exchange.spread_partition_ids
    # for the exactness argument
    skew_spread_partitions: Optional[List[int]] = None
    skew_replicate_partitions: Optional[List[int]] = None
    # spooled result protocol (server/segments.py): this task produces
    # the query's RESULT — its output writes size-bounded segments into
    # the worker's segment store instead of the output buffer, and the
    # statement response carries their URIs (the coordinator never pulls
    # the data). Set only on the root fragment's gather producers.
    spool_results: bool = False

    def to_bytes(self) -> bytes:
        return pickle.dumps(self)

    @staticmethod
    def from_bytes(data: bytes) -> "TaskRequest":
        return pickle.loads(data)


class FragmentExecutor(Executor):
    """Executes one plan fragment: scans read only the task's assigned
    splits; RemoteSourceNodes read pages pulled from upstream tasks."""

    def __init__(self, session, splits: Dict[int, List], remote_pages: Dict[int, List[Page]]):
        super().__init__(session)
        self._splits = splits
        self._remote_pages = remote_pages

    def _exec_TableScanNode(self, node: P.TableScanNode) -> Page:
        from trino_tpu import devcache
        from trino_tpu.exec import memory as _mem
        from trino_tpu.exec import staging

        conn = self.session.catalogs[node.catalog]
        splits = self._splits.get(node.id, [])
        # splits were assigned by the coordinator (static constraint already
        # applied); dynamic-filter domains collected in THIS fragment still
        # narrow the per-split scan
        constraint = self.scan_constraint(node)

        def load():
            # the pipelined engine (exec/staging.py): the task's assigned
            # splits scan in parallel on the shared pool, each consulting
            # the host-RAM tier, and the assembled columns cross in one
            # put an array, one wait a page. STAGING_SECONDS keeps its worker
            # semantics: the whole fresh scan+assemble+transfer wall
            # (device-cache hits never reach this loader).
            t0 = time.perf_counter()
            # a task's scans stage at a bucketed length: the splits of one
            # table differ by a few rows, and the split-at-a-time driver
            # would compile every operator program again for each
            page, rows, _prof = staging.staged_scan_page(
                self.session, node, conn, splits, constraint,
                bucket_rows=True)
            M.STAGED_ROWS.inc(rows)
            M.STAGING_SECONDS.inc(time.perf_counter() - t0)
            return page, rows, _mem.page_bytes(page), len(splits)

        with tracing.span("device/staging", table=node.table,
                          splits=len(splits)) as sp:
            # the worker-side buffer pool: this task's assigned split set
            # is the shard component, so a retried/speculative attempt of
            # the same splits — or the next query over them — stays warm
            ent, disposition = devcache.cached_stage(
                self.session, node, constraint, {},
                devcache.splits_shard(splits), load)
            page, rows = ent.value, ent.rows
            self.scan_stats[node.id] = rows
            self._pending_scan[node.id] = (len(splits), rows)
            self.scan_cache[node.id] = disposition
            # a warm scan transferred nothing: the span's staged_rows is
            # the zero-transfer proof signal (see trino_tpu/devcache/)
            sp.set("staged_rows", 0 if disposition == "hit" else rows)
            sp.set("cache", disposition)
        return page

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> Page:
        pages = self._remote_pages.get(node.fragment_id, [])
        pages = [p for p in pages if p.num_rows > 0]
        if not pages:
            return Page.all_dead(node.types)
        return Page.concat_all(pages)


class SqlTask:
    """One task: FSM + executor thread + output buffer.

    State flow PLANNED→RUNNING→FLUSHING→FINISHED mirrors TaskState.java:21;
    FLUSHING = body finished, buffer still draining to consumers.
    """

    def __init__(self, request: TaskRequest, session_factory,
                 traceparent: Optional[str] = None, recorder=None,
                 otlp=None, segment_store=None):
        self.request = request
        self.state: StateMachine[str] = task_state_machine()
        # worker half of the query's trace: same trace id, spans rooted
        # under the coordinator's propagated (schedule) span; a missing
        # header starts a detached local trace (direct task POSTs in tests)
        ctx = tracing.parse_traceparent(traceparent)
        self.tracer = tracing.Tracer(
            trace_id=ctx[0] if ctx else None,
            root_parent_id=ctx[1] if ctx else None)
        # worker-process flight recorder + OTLP exporter (both optional):
        # closed spans mirror into the ring; the finished task's span
        # dump ships to the collector under the propagated trace id
        self.tracer.recorder = recorder
        self._otlp = otlp
        from trino_tpu.server.buffer import DEFAULT_MAX_BUFFER_BYTES

        sink_max = int(request.session_properties.get(
            "sink_max_buffer_bytes") or DEFAULT_MAX_BUFFER_BYTES)
        # flow-ledger labels: full-wait stall samples carry this task's
        # stage (task ids are {query}.{fragment}.{worker}.a{attempt})
        self.stage_id = _task_stage_id(request.task_id)
        if request.output_partition_channels is not None:
            self.output = PartitionedOutputBuffer(
                request.consumer_count, max_buffer_bytes=sink_max,
                stall_stage=self.stage_id)
        else:
            self.output = OutputBuffer(
                request.consumer_count, max_buffer_bytes=sink_max,
                stall_key=(self.stage_id, None))
        # spooled result protocol: when this task produces the query's
        # result, its serialized output chunks roll into size-bounded
        # segments in the worker's segment store (server/segments.py)
        # instead of the output buffer — the coordinator collects the
        # segment metadata from task status and never pulls the data
        self._result_writer = None
        self.result_segments: List[dict] = []
        if (request.spool_results and segment_store is not None
                and request.output_partition_channels is None):
            props = request.session_properties
            from trino_tpu.server.segments import DEFAULT_SEGMENT_BYTES

            self._result_writer = segment_store.writer(
                request.query_id,
                target_bytes=int(props.get("spooled_results_segment_bytes")
                                 or DEFAULT_SEGMENT_BYTES),
                ttl_s=int(props.get("result_segment_ttl_ms")
                          or 300_000) / 1e3)
        self.failure: Optional[str] = None
        # peak device/host bytes observed by this task's executors — rolls
        # up into the worker announce for cluster memory management
        # (reference: QueryContext reservations -> ClusterMemoryPool)
        self.peak_memory_bytes = 0
        # --- task-level stats (reference: TaskStats + the OperatorStats it
        # aggregates): every retired executor folds its node_stats in here
        # under _stats_lock, and status responses snapshot the same way —
        # so a coordinator poll mid-execution reads a consistent rollup.
        self.operator_stats: Dict[int, "OperatorStats"] = {}
        # kernel-ledger rollup (obs/devprofiler.py): retired executors
        # fold their kernel_stats here; status snapshots ship the rows
        self.kernel_stats: Dict[tuple, dict] = {}
        self._stats_lock = threading.Lock()
        self.total_splits = sum(len(v) for v in request.splits.values())
        self.splits_completed = 0
        self.device_seconds = 0.0
        self.input_rows = 0  # connector/exchange rows entering the fragment
        self.output_rows = 0
        self.output_bytes = 0
        # per-partition LIVE output rows (hash-partitioned producers only):
        # the adaptive skew signal — counted pre-serialization because
        # serde compression flattens a constant hot key to almost no bytes
        self.partition_rows: Optional[List[int]] = None
        self.spill_count = 0
        # revocable-tier bytes shed on this task's behalf + yield-event
        # count (exec/memory.py spill path) — queryStats.memory inputs
        self.shed_bytes = 0
        self.yield_events = 0
        # device-cache dispositions of this task's scans (warm-serving
        # telemetry: rolls up task -> stage -> query and into the CLI)
        self.device_cache_hits = 0
        self.device_cache_misses = 0
        # exchange clients this task created (flow-ledger rollup: their
        # pull/stall seconds feed the transferS/stallS stats the straggler
        # detector attributes causes from)
        self._exchange_clients: List = []
        self.started_at = time.monotonic()
        self.ended_at: Optional[float] = None
        self._session_factory = session_factory
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _track_executor(self, ex) -> None:
        self._live_executor = ex
        if ex.memory.peak > self.peak_memory_bytes:
            # task-level reservation event: the TASK peak is max over its
            # (sequential) executors, so deltas here never double-count
            # the per-split/per-batch executor peaks the way summing
            # per-executor events would (exec/memory.py owner mode is for
            # the coordinator-local one-executor-per-query path)
            delta = ex.memory.peak - self.peak_memory_bytes
            self.peak_memory_bytes = ex.memory.peak
            from trino_tpu.obs.memledger import MEMORY_LEDGER, POOL_DEVICE

            MEMORY_LEDGER.record_event(
                "reserve", POOL_DEVICE,
                f"query:{self.request.query_id}", delta)

    def _retire_executor(self, ex, splits: int = 0, input_rows: int = 0,
                         device_s: float = 0.0) -> None:
        """Fold a finished executor's per-operator stats into the task's
        accumulated rollup (one executor per bulk body, per split, or per
        streaming batch — accumulation keeps stats additive across all
        three driver shapes)."""
        import dataclasses as _dc

        self._track_executor(ex)
        with self._stats_lock:
            for nid, st in ex.node_stats.items():
                have = self.operator_stats.get(nid)
                if have is None:
                    self.operator_stats[nid] = _dc.replace(st)
                else:
                    have.add(st)
            merge_kernel_rows(
                self.kernel_stats,
                list(getattr(ex, "kernel_stats", {}).values()))
            # the fragment body IS the device execution: charge its wall to
            # the fragment root's device-seconds
            root_st = self.operator_stats.get(self.request.fragment_root.id)
            if root_st is not None:
                root_st.device_s += device_s
            self.device_seconds += device_s
            self.splits_completed += splits
            self.input_rows += input_rows
            self.spill_count += len(ex.memory.spills)
            self.shed_bytes += ex.memory.shed_bytes
            self.yield_events += ex.memory.yields
            self.device_cache_hits += sum(
                1 for d in ex.scan_cache.values() if d == "hit")
            self.device_cache_misses += sum(
                1 for d in ex.scan_cache.values() if d == "miss")

    @contextlib.contextmanager
    def _charge_root(self, page: Page, host_copy: bool = False):
        """Charge this thread's device->host reads to the kernel row of
        the fragment's root node, which produced ``page``: work the task
        does on a page once the executor that made it has been retired
        (the output path, the streaming fold's state pages). The output
        path's own ``host_copy`` of a page says nothing of where the
        root's launches left it."""
        root = self.request.fragment_root
        row = new_kernel_row(str(root.id), operator_kind(root), "eager")
        row["platform"] = "" if host_copy else page_platform(page)
        try:
            with charge_to(row):
                yield
        finally:
            if (row["hostSyncs"] or row["compiles"] or row["aggPrograms"]
                    or row["aggEager"] or row["exchangedRows"]
                    or row["outputFetches"]):
                with self._stats_lock:
                    merge_kernel_rows(self.kernel_stats, [row])

    @contextlib.contextmanager
    def _output_path(self, page: Page, host_copy: bool = False):
        """The worker's output path after the fragment body, as one
        ``task/output`` span: fetch, compact, partition, chunk, serialise,
        enqueue (a wait at the buffer's watermark included) or segment
        write. The coordinator, or the consuming task, spends this time
        waiting. The live rows handed to the output buffer inside it are
        the root operator's ``exchangedRows``, the pages it fetched its
        ``outputFetches``."""
        with tracing.span("task/output"), self._charge_root(page, host_copy):
            yield

    @staticmethod
    def _host_compacted(page: Page) -> Page:
        """The output path's ONE host copy of ``page``, dead rows dropped:
        the page's whole tree leaves the device in one batched read (site
        ``output-fetch``, counted on the root's row as ``outputFetches``),
        and everything after it (the gather that compacts, the partition
        ids, the per-partition gathers, the chunk slices, the serde) is
        numpy on that copy; nothing goes back up and nothing is read
        twice. A page that is already such a copy is returned as it is."""
        host = page.to_host("output-fetch")
        if host is not page:
            count_charged("outputFetches")
        return host.compact(device=False)

    def stats_snapshot(self) -> dict:
        """Point-in-time task stats for ``GET /v1/task/{id}/status`` —
        the wire shape the coordinator's stage/query rollup consumes."""
        live = getattr(self, "_live_executor", None)
        peak = max(self.peak_memory_bytes,
                   live.memory.peak if live is not None else 0)
        # hash-partitioned producers break their output bytes down per
        # partition — the skew signal the adaptive re-planner reads
        part_bytes = (self.output.partition_enqueued_bytes
                      if isinstance(self.output, PartitionedOutputBuffer)
                      else None)
        # flow-ledger per-task seconds: exchange/spool pull wall and
        # backpressure stalls (producer full-waits + consumer empty
        # polls) — the straggler detector's cause inputs
        transfer_s = sum(c.pulled_seconds for c in self._exchange_clients)
        stall_s = (self.output.stalled_seconds
                   + sum(c.stalled_seconds for c in self._exchange_clients))
        with self._stats_lock:
            ops = [self.operator_stats[k].to_dict()
                   for k in sorted(self.operator_stats)]
            elapsed = (self.ended_at or time.monotonic()) - self.started_at
            snap = {
                "elapsedS": round(elapsed, 6),
                "deviceS": round(self.device_seconds, 6),
                "transferS": round(transfer_s, 6),
                "stallS": round(stall_s, 6),
                "completedSplits": self.splits_completed,
                "totalSplits": self.total_splits,
                "inputRows": self.input_rows,
                "outputRows": self.output_rows,
                "outputBytes": self.output_bytes,
                "peakBytes": peak,
                "spills": self.spill_count,
                "shedBytes": self.shed_bytes,
                "yieldEvents": self.yield_events,
                "deviceCacheHits": self.device_cache_hits,
                "deviceCacheMisses": self.device_cache_misses,
                "operatorStats": ops,
                "kernelStats": [copy_kernel_row(self.kernel_stats[k])
                                for k in sorted(self.kernel_stats)],
            }
            if part_bytes is not None:
                snap["partitionBytes"] = part_bytes
            if self.partition_rows is not None:
                snap["partitionRows"] = list(self.partition_rows)
            return snap

    @property
    def memory_bytes(self) -> int:
        """Reservation gauge for cluster memory management: the executor's
        peak while the body RUNS; once the body finished (FLUSHING) it
        decays to what the drain actually still holds — the result page
        being chunked out plus buffered frames — so a transient
        mid-execution peak does not outlive the body and starve admission
        (exact liveness would need per-page refcounts)."""
        state = self.state.get()
        if state in ("FINISHED", "FAILED", "CANCELED"):
            return 0
        if state not in ("PLANNED", "RUNNING"):
            return int(getattr(self, "flushing_bytes", 0)
                       + self.output.buffered_bytes)
        live = getattr(self, "_live_executor", None)
        peak = live.memory.peak if live is not None else 0
        return max(self.peak_memory_bytes, peak)

    def start(self) -> None:
        if self.state.compare_and_set("PLANNED", "RUNNING"):
            self._thread.start()

    def _run(self) -> None:
        task_span = self.tracer.start_span(
            "task", task_id=self.request.task_id,
            query_id=self.request.query_id)
        try:
            with tracing.activate(self.tracer, task_span.span_id):
                self._run_body()
        except Exception as e:  # noqa: BLE001 — reported through task status
            self.failure = f"{e}\n{traceback.format_exc()}"
            task_span.set("error", str(e).split("\n")[0][:300])
            if self._result_writer is not None:
                # no manifest will ever point at a failed attempt's
                # segments — reclaim them now, not at TTL
                self._result_writer.abandon()
            self.output.abort(str(e))
            self.state.set("FAILED")
        finally:
            self.ended_at = time.monotonic()
            # a terminal task stays in the manager's history (up to
            # MAX_TASK_HISTORY): it must not keep its last executor, and
            # with it the pulled pages on the device, alive that long
            self._live_executor = None
            self._observe_operator_metrics()
            if self.peak_memory_bytes:
                from trino_tpu.obs.memledger import (MEMORY_LEDGER,
                                                     POOL_DEVICE)

                MEMORY_LEDGER.record_event(
                    "release", POOL_DEVICE,
                    f"query:{self.request.query_id}",
                    self.peak_memory_bytes, reason="done")
            task_span.set("state", self.state.get())
            self.tracer.end_span(task_span)
            if self._otlp is not None:
                self._otlp.export_spans(
                    self.tracer.to_dicts(), self.tracer.trace_id,
                    {"query_id": self.request.query_id,
                     "task_id": self.request.task_id,
                     "task.state": self.state.get()})

    def _observe_operator_metrics(self) -> None:
        """Feed the per-operator-kind registry metrics from this task's
        accumulated stats, once, at task completion."""
        with self._stats_lock:
            snapshot = [(st.operator, st.wall_s, st.output_rows)
                        for st in self.operator_stats.values()]
        for operator, wall_s, rows in snapshot:
            M.OPERATOR_WALL_SECONDS.observe(wall_s, operator)
            if rows:
                M.OPERATOR_ROWS.inc(rows, operator)

    def _run_body(self) -> None:
        req = self.request
        # fault injection (reference: FailureInjector.java:41-69 —
        # keyed by trace/stage/partition/attempt; here by task-id match)
        inject = str(req.session_properties.get("failure_injection") or "")
        if inject and inject in req.task_id:
            raise RuntimeError(f"injected failure for {req.task_id}")
        # straggler injection ("substr:seconds") — exercises the FTE
        # scheduler's speculative execution (reference:
        # FailureInjector's sleep mode)
        slow = str(req.session_properties.get("slow_injection") or "")
        if slow:
            sub, _, secs = slow.partition(":")
            if sub and sub in req.task_id:
                time.sleep(float(secs or "5"))
        session = self._session_factory(req.session_properties)
        if self._try_streaming(req, session):
            return
        # pull all upstream fragments first (bulk-synchronous bodies:
        # joins/final aggs/sorts need their whole input; the pull itself
        # streams + backpressures)
        remote_pages: Dict[int, List[Page]] = {}
        for fid, locations in req.upstream.items():
            from trino_tpu.server.exchange_client import ExchangeClient, TaskLocation

            client = ExchangeClient(
                [TaskLocation(u, t, b) for u, t, b in locations],
                owner=f"task:{req.task_id}",
                stall_key=(self.stage_id, None))
            self._exchange_clients.append(client)
            client.start()
            remote_pages[fid] = client.pages()
        ex = FragmentExecutor(session, req.splits, remote_pages)
        self._track_executor(ex)
        with tracing.span("device/execute") as sp:
            t0 = time.perf_counter()
            page = ex.execute_checked(req.fragment_root)
            device_s = time.perf_counter() - t0
            sp.set("device_seconds", round(device_s, 6))
            sp.set("staged_rows", sum(ex.scan_stats.values()))
            sp.set("output_rows", int(page.num_rows))
        M.DEVICE_SECONDS.inc(device_s)
        remote_rows = sum(
            p.num_rows for pages in remote_pages.values() for p in pages)
        self._retire_executor(
            ex, splits=self.total_splits,
            input_rows=sum(ex.scan_stats.values()) + remote_rows,
            device_s=device_s)
        with self._output_path(page):
            self._write_output(page)
        self.state.set("FINISHED")

    def _write_output(self, page: Page) -> None:
        """The bulk body's output path: one host copy, compacted, then by
        the task's shape partition, spool or stream the chunks into the
        output buffer."""
        from trino_tpu.exec.memory import page_bytes

        req = self.request
        page = self._host_compacted(page)
        self.flushing_bytes = page_bytes(page)  # held through the drain
        with self._stats_lock:
            self.output_rows += page.num_rows
            self.output_bytes += self.flushing_bytes
        count_charged("exchangedRows", int(page.num_rows))
        self.state.set("FLUSHING")
        chunk_rows = self._chunk_rows(page)
        if req.output_partition_channels is not None:
            # hash-partitioned shuffle producer: split the output by
            # key hash (same splitmix64 combine as the device exchange,
            # so every producer places a key identically) and enqueue
            # each partition into its consumer's stream, a frame as soon
            # as it is encoded. Under FTE the per-partition streams spool
            # FIRST (durability before visibility — retried consumers
            # re-read partition files).
            parts = self._partition_pages(page)
            if spool_directory():
                part_frames = [
                    [serialize_page(c, CODEC_ZLIB)
                     for c in _chunk_pages(part, chunk_rows)]
                    for part in parts
                ]
                self._spool_partitioned(part_frames)
                for pid, frames in enumerate(part_frames):
                    for pb in frames:
                        self.output.enqueue_partition(pid, pb)
            else:
                self._enqueue_partitions(parts, chunk_rows)
            self.output.set_complete()
            return
        if self._result_writer is not None:
            # spooled result output: serialized chunks roll straight into
            # size-bounded segments in the worker's segment store —
            # nothing enters the output buffer, so this producer never
            # parks on a consumer that, by design, is not coming
            with tracing.span("segment/write") as sp:
                for c in _chunk_pages(page, chunk_rows):
                    self._result_writer.add(serialize_page(c, CODEC_ZLIB),
                                            int(c.num_rows))
                self._finish_result_spool()
                sp.set("segments", len(self.result_segments))
                sp.set("rows", int(page.live_count()))
            self.output.set_complete()
            return
        # STREAMING output: size-bounded chunks enqueue as they
        # serialize, so consumers pull chunk 0 while chunk 1 encodes,
        # and the bounded buffer's watermark gives real backpressure
        # (reference invariant SURVEY §A.6: incremental page flow).
        # Under FTE (spool configured) the whole output spools FIRST —
        # retried consumers must find the complete durable copy — which
        # trades pipelining for recoverability, as the reference's FTE
        # exchanges do.
        if spool_directory():
            page_frames = [
                serialize_page(c, CODEC_ZLIB)
                for c in _chunk_pages(page, chunk_rows)
            ]
            self._spool(page_frames)
            for pb in page_frames:
                self.output.enqueue(pb)
        else:
            for c in _chunk_pages(page, chunk_rows):
                # blocks at watermark
                self.output.enqueue(serialize_page(c, CODEC_NONE))
        self.output.set_complete()

    def _enqueue_partitions(self, parts: List[Page], chunk_rows: int) -> None:
        """Each partition's chunks into its consumer's stream, raw (the
        pipelined pull), each frame enqueued as soon as it is encoded."""
        for pid, part in enumerate(parts):
            for c in _chunk_pages(part, chunk_rows):
                self.output.enqueue_partition(
                    pid, serialize_page(c, CODEC_NONE))

    # ------------------------------------------------------- streaming loop
    @staticmethod
    def _streamable_leaf(root: P.PlanNode, leaf_type):
        """The single ``leaf_type`` leaf of a streamable fragment, else
        None. Streamable = every operator on the chain is row-local or a
        PARTIAL aggregation: executing it per arriving chunk/split and
        concatenating outputs is semantically identical to one bulk run
        (partial-agg outputs may legally contain multiple rows per group —
        the downstream FINAL merge makes them one). This is the
        WorkProcessor pull model (reference: operator/WorkProcessor.java:31,
        Driver.java:449's blocked futures) with the micro-batch as the unit
        instead of the page."""
        node = root
        while True:
            if isinstance(node, leaf_type):
                return node
            if isinstance(node, (P.FilterNode, P.ProjectNode, P.CompactNode)):
                node = node.source
                continue
            if isinstance(node, P.AggregationNode) and node.step == "partial":
                node = node.source
                continue
            return None

    def _streamable_source(self, root: P.PlanNode):
        return self._streamable_leaf(root, RemoteSourceNode)

    @staticmethod
    def _streaming_final_agg(root: P.PlanNode):
        """The (final-agg node, its RemoteSourceNode) when the fragment is a
        hash-distributed FINAL aggregation whose states the intermediate
        fold can merge — the streaming consumer then folds arriving partial
        states instead of buffering them all (reference:
        AggregationNode.Step.INTERMEDIATE)."""
        from trino_tpu.exec.executor import Executor

        if not (isinstance(root, P.AggregationNode) and root.step == "final"
                and isinstance(root.source, RemoteSourceNode)):
            return None
        for call in root.aggregates:
            if call.distinct or call.function not in Executor.MERGEABLE_STATE_FNS:
                return None
        return root, root.source

    # accumulate arriving pages to at least this many rows before running
    # the fragment body over the batch (tiny per-page dispatches would
    # dominate otherwise)
    STREAM_BATCH_ROWS = 65536

    def _streamable_scan(self, root: P.PlanNode):
        """The single TableScanNode leaf of a row-local/partial-agg chain,
        else None — the SPLIT-at-a-time driver shape (reference: the
        driver loop processing one split per quantum, SqlTaskExecution's
        per-split drivers)."""
        return self._streamable_leaf(root, P.TableScanNode)

    def _partition_pages(self, page: Page) -> List[Page]:
        """Hash-partition one output page (the output path's compacted
        host copy) into consumer_count per-partition pages, which stay on
        the host, applying the adaptive skew salting when the re-planner
        annotated this producer: hot partitions spread round-robin (probe
        side) or replicate into every partition (build side) — the
        producer half of the salted repartition join. A partition with no
        row is a page of no rows (``_chunk_pages`` yields nothing)."""
        from trino_tpu.data.page import host_take

        import numpy as np

        req = self.request
        # ONE hash pass per page: the pid array is computed once (with the
        # per-dictionary vocab hashes cached across a streaming producer's
        # pages) and reused by the salting spread, the partitioning
        # re-send, AND the skew-detection accounting below — previously
        # the accounting re-walked every partition page (N live_count
        # passes) after the hash pass
        if not hasattr(self, "_vocab_hash_cache"):
            self._vocab_hash_cache = {}
        pids = _canonical_partition_ids(
            page, req.output_partition_channels, req.consumer_count,
            vocab_cache=self._vocab_hash_cache)
        spread = getattr(req, "skew_spread_partitions", None)
        if spread:
            from trino_tpu.parallel.exchange import spread_partition_ids

            # the cursor rotates ACROSS pages so a streaming producer's
            # per-page hot rows don't all restart at partition 0
            pids, self._spread_cursor = spread_partition_ids(
                pids, spread, req.consumer_count,
                start=getattr(self, "_spread_cursor", 0))
        assert page.sel is None, "_partition_pages takes a compacted page"
        pids = np.asarray(pids)
        rows = [np.nonzero(pids == p)[0] for p in range(req.consumer_count)]
        # detection accounting straight off the (post-spread) pid array,
        # before replication: replicated hot-partition copies do not
        # inflate the skew signal the re-planner reads
        with self._stats_lock:
            if self.partition_rows is None:
                self.partition_rows = [0] * req.consumer_count
            for pid, idx in enumerate(rows):
                self.partition_rows[pid] += len(idx)
        replicate = getattr(req, "skew_replicate_partitions", None)
        if replicate:
            # a hot partition's rows follow every other partition's own
            hot = [h for h in dict.fromkeys(replicate) if 0 <= h < len(rows)]
            rows = [np.concatenate([own] + [rows[h] for h in hot if h != q])
                    for q, own in enumerate(rows)]
        return [Page([host_take(c, idx, device=False, site="partition")
                      for c in page.columns], None, page.replicated)
                for idx in rows]

    def _finish_result_spool(self) -> None:
        """Seal the result-segment writer: roll the last partial segment
        and publish the manifest metadata task status carries."""
        if self._result_writer is None:
            return
        metas = self._result_writer.finish()
        self.result_segments = [m.manifest_entry() for m in metas]

    def _complete_output(self) -> None:
        """Completion chokepoint for the streaming driver shapes: seal
        the result spool (if this task produces the query's result),
        then mark the buffer complete."""
        self._finish_result_spool()
        self.output.set_complete()

    def _enqueue_out(self, out: Page, part_channels, consumer_count) -> None:
        """Partition-aware enqueue of one output page (shared by the
        streaming paths: per-batch chains, per-split scans, and the fold
        path's finalization)."""
        with self._output_path(out, host_copy=True):
            self._enqueue_chunks(out, part_channels)

    def _enqueue_chunks(self, out: Page, part_channels) -> None:
        out = self._host_compacted(out)
        if out.num_rows == 0:
            return
        from trino_tpu.exec.memory import page_bytes

        live = int(out.num_rows)
        with self._stats_lock:
            self.output_rows += live
            self.output_bytes += page_bytes(out)
        count_charged("exchangedRows", live)
        chunk_rows = self._chunk_rows(out)
        if self._result_writer is not None and part_channels is None:
            # spooled result output (streaming shapes): chunks roll into
            # the segment store as they serialize — disk-bounded, so the
            # stream loop never blocks on an output-buffer watermark
            with tracing.span("segment/write") as sp:
                for c in _chunk_pages(out, chunk_rows):
                    self._result_writer.add(serialize_page(c, CODEC_ZLIB),
                                            int(c.num_rows))
                sp.set("rows", live)
            return
        if part_channels is not None:
            self._enqueue_partitions(self._partition_pages(out), chunk_rows)
        else:
            for c in _chunk_pages(out, chunk_rows):
                self.output.enqueue(serialize_page(c, CODEC_NONE))

    def _try_split_streaming(self, req: TaskRequest, session) -> bool:
        """Execute a scan-rooted streamable fragment ONE SPLIT AT A TIME,
        enqueueing each split's output as it completes: consumers pull
        split 0's rows while split 1 scans, and task memory is bounded by
        one split instead of the whole assignment (the per-driver split
        processing of the reference's task execution — splits are no
        longer an all-at-once bulk scan)."""
        scan = self._streamable_scan(req.fragment_root)
        if scan is None or scan.id not in req.splits:
            return False
        splits = req.splits[scan.id]
        if len(splits) <= 1:
            return False  # nothing to pipeline
        # the span covers the whole stage; device_seconds counts ONLY the
        # execute calls (enqueue blocks at the output watermark, and that
        # backpressure wait must not read as device time)
        with tracing.span("device/execute", mode="split-streaming") as sp:
            device_s = 0.0
            staged_rows = 0
            for split in splits:
                ex = FragmentExecutor(session, {scan.id: [split]}, {})
                self._track_executor(ex)
                t0 = time.perf_counter()
                page = ex.execute_checked(req.fragment_root)
                with self._output_path(page):
                    out = self._host_compacted(page)
                split_s = time.perf_counter() - t0
                device_s += split_s
                staged_rows += sum(ex.scan_stats.values())
                self._retire_executor(
                    ex, splits=1, input_rows=sum(ex.scan_stats.values()),
                    device_s=split_s)
                self._enqueue_out(out, req.output_partition_channels,
                                  req.consumer_count)
            sp.set("device_seconds", round(device_s, 6))
            sp.set("staged_rows", staged_rows)
            sp.set("splits", len(splits))
        M.DEVICE_SECONDS.inc(device_s)
        self.state.set("FLUSHING")
        self._complete_output()
        self.state.set("FINISHED")
        return True

    def _try_streaming(self, req: TaskRequest, session) -> bool:
        """Micro-batch driver loop for streamable consumer fragments: pull
        chunks from the ONE upstream, execute the fragment per batch, and
        enqueue each batch's output immediately — the consumer makes
        progress (and its output becomes pullable) while the producer is
        still FLUSHING, and holds only ~batch rows of input at a time.
        Returns False when the fragment shape or config requires the bulk
        path (joins/final aggs; FTE spooling needs the complete output
        durable before visibility, so it stays bulk)."""
        if spool_directory():
            return False
        if not req.upstream and len(req.splits) == 1:
            return self._try_split_streaming(req, session)
        final_agg = self._streaming_final_agg(req.fragment_root)
        src = (final_agg[1] if final_agg is not None
               else self._streamable_source(req.fragment_root))
        if src is None or len(req.upstream) != 1:
            return False
        if req.splits:  # mixed scan+remote shapes are not chain-shaped
            return False
        locations = req.upstream.get(src.fragment_id)
        if locations is None:
            return False
        from trino_tpu.server.exchange_client import ExchangeClient, TaskLocation

        client = ExchangeClient(
            [TaskLocation(u, t, b) for u, t, b in locations],
            owner=f"task:{req.task_id}", stall_key=(self.stage_id, None))
        self._exchange_clients.append(client)
        client.start()
        # device_clock accumulates ONLY the executor calls: the stream loop
        # also waits on upstream pulls and output backpressure, and that
        # wall time belongs to the exchange/pull spans, not device_seconds
        device_clock = [0.0]

        def enqueue_out(out: Page) -> None:
            self._enqueue_out(out, req.output_partition_channels,
                              req.consumer_count)

        def emit(batch: List[Page]) -> None:
            batch_rows = sum(p.num_rows for p in batch)
            page = Page.concat_all(batch)
            ex = FragmentExecutor(session, {}, {src.fragment_id: [page]})
            self._track_executor(ex)
            t0 = time.perf_counter()
            page = ex.execute_checked(req.fragment_root)
            with self._output_path(page):
                out = self._host_compacted(page)
            batch_s = time.perf_counter() - t0
            device_clock[0] += batch_s
            self._retire_executor(ex, input_rows=batch_rows, device_s=batch_s)
            enqueue_out(out)

        if final_agg is not None:
            # fold arriving partial-state pages into ONE running state page
            # (intermediate merge), finalize once the upstream is exhausted
            node = final_agg[0]
            running: Optional[Page] = None
            batch: List[Page] = []
            batch_rows = 0

            def record_agg_stats(ex, wall_s, in_rows, out_page,
                                 is_final=False):
                """aggregate_intermediate/final bypass the execute() stats
                wrapper — record the aggregation node's OperatorStats by
                hand so fold fragments still annotate EXPLAIN ANALYZE and
                feed the per-operator metrics. Only the finalization's rows
                count as operator OUTPUT (intermediate folds maintain
                internal state); every pass counts toward wall/input."""
                from trino_tpu.exec.memory import page_bytes

                st = ex.node_stats.setdefault(
                    node.id, OperatorStats(node.id, "Aggregation"))
                st.wall_s += wall_s
                st.input_rows += in_rows
                if is_final:
                    st.output_rows += int(out_page.num_rows)
                    st.output_bytes += page_bytes(out_page)
                st.invocations += 1

            def fold(running, batch):
                batch_rows = sum(p.num_rows for p in batch)
                page = Page.concat_all(
                    batch if running is None else [running] + batch)
                ex = FragmentExecutor(session, {}, {})
                self._track_executor(ex)
                t0 = time.perf_counter()
                with self._charge_root(page):
                    out = ex.aggregate_intermediate(node, page).compact()
                    ex.raise_errors()
                fold_s = time.perf_counter() - t0
                device_clock[0] += fold_s
                record_agg_stats(ex, fold_s, batch_rows, out)
                self._retire_executor(ex, input_rows=batch_rows,
                                      device_s=fold_s)
                return out

            with tracing.span("device/execute", mode="streaming-fold") as sp:
                in_rows = 0
                for page in client.iter_pages():
                    if page.num_rows == 0:
                        continue
                    batch.append(page)
                    batch_rows += page.num_rows
                    in_rows += page.num_rows
                    # fold once the batch has grown to the running state's
                    # size: every fold re-groups the whole state, so a
                    # fixed batch would re-sort a many-group state once per
                    # batch (and, each fold being a new shape, compile its
                    # programs again); doubling keeps folds logarithmic
                    # and the task at twice the state
                    if batch_rows >= max(
                            self.STREAM_BATCH_ROWS,
                            0 if running is None else running.num_rows):
                        running = fold(running, batch)
                        batch, batch_rows = [], 0
                if batch:
                    running = fold(running, batch)
                if running is None:
                    running = Page.all_dead(src.types)
                ex = FragmentExecutor(session, {}, {})
                t0 = time.perf_counter()
                with self._charge_root(running):
                    final = ex.aggregate_final(node, running)
                    ex.raise_errors()
                with self._output_path(final):
                    out = self._host_compacted(final)
                final_s = time.perf_counter() - t0
                device_clock[0] += final_s
                record_agg_stats(ex, final_s, int(running.num_rows), out,
                                 is_final=True)
                self._retire_executor(ex, device_s=final_s)
                sp.set("device_seconds", round(device_clock[0], 6))
                sp.set("input_rows", in_rows)
            M.DEVICE_SECONDS.inc(device_clock[0])
            self.state.set("FLUSHING")
            enqueue_out(out)
            self._complete_output()
            self.state.set("FINISHED")
            return True
        batch: List[Page] = []
        batch_rows = 0
        with tracing.span("device/execute", mode="streaming") as sp:
            in_rows = 0
            for page in client.iter_pages():
                if page.num_rows == 0:
                    continue
                batch.append(page)
                batch_rows += page.num_rows
                in_rows += page.num_rows
                if batch_rows >= self.STREAM_BATCH_ROWS:
                    emit(batch)
                    batch, batch_rows = [], 0
            if batch:
                emit(batch)
            sp.set("device_seconds", round(device_clock[0], 6))
            sp.set("input_rows", in_rows)
        M.DEVICE_SECONDS.inc(device_clock[0])
        self.state.set("FLUSHING")
        self._complete_output()
        self.state.set("FINISHED")
        return True

    # target serialized bytes per output chunk (reference: the page-size
    # targets of PartitionedOutputBuffer / PagesSerde)
    DEFAULT_CHUNK_BYTES = 4 << 20

    def _chunk_rows(self, page: Page) -> int:
        target = int(self.request.session_properties.get(
            "task_output_chunk_bytes") or self.DEFAULT_CHUNK_BYTES)
        return max(1, target // page.row_byte_estimate()) if page.num_rows else 1

    def _spool_partitioned(self, part_frames) -> None:
        """Spool each partition stream to its own durable file
        ({task}.p{pid}.pages) — the FTE contract for hash-distributed
        stages (reference: FileSystemExchange sink files per partition)."""
        spool_dir = spool_directory()
        if not spool_dir:
            return
        import os

        from trino_tpu.server import wire

        os.makedirs(spool_dir, exist_ok=True)
        for pid, frames in enumerate(part_frames):
            path = os.path.join(
                spool_dir, f"{self.request.task_id}.p{pid}.pages")
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(wire.frame_pages(frames))
            os.replace(tmp, path)

    def _spool(self, page_frames) -> None:
        """Persist the task's output to the shared spool directory
        (reference: the FTE tier's spooled exchange —
        spi/exchange/ExchangeManager.java:39 + FileSystemExchange.java:70):
        a finished task's pages survive the producing worker, so retried
        consumers re-read them instead of recomputing the stage."""
        spool_dir = spool_directory()
        if not spool_dir:
            return
        import os

        from trino_tpu.server import wire

        os.makedirs(spool_dir, exist_ok=True)
        path = os.path.join(spool_dir, f"{self.request.task_id}.pages")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(wire.frame_pages(page_frames))
        os.replace(tmp, path)  # atomic publish: readers never see partials

    def info(self) -> dict:
        return {
            "taskId": self.request.task_id,
            "state": self.state.get(),
            "failure": self.failure,
            "bufferedBytes": self.output.buffered_bytes,
            # at its watermark: the task goes no further until a consumer
            # pulls (the phased scheduler stops waiting for such a build)
            "outputFull": self.output.full,
            "memoryBytes": self.memory_bytes,
            # spooled result protocol: the segments this task wrote (the
            # coordinator assembles the statement manifest from these)
            "resultSegments": list(self.result_segments),
            # worker-reported stats ride every status response — the
            # coordinator's stage/query rollup reads them from its
            # status-polling loop (reference: TaskStatus carrying TaskStats)
            "stats": self.stats_snapshot(),
        }


def _task_stage_id(task_id: str):
    """The fragment (stage) id embedded in a coordinator task id
    ({query}.{fragment}.{worker}.a{attempt}); None for free-form ids
    (direct task POSTs in tests)."""
    parts = task_id.split(".")
    if len(parts) >= 4 and parts[-1].startswith("a"):
        try:
            return int(parts[-3])
        except ValueError:
            return None
    return None


def _chunk_pages(page: Page, chunk_rows: int):
    """Yield size-bounded row slices of a compacted page (empty pages yield
    nothing — downstream treats absence as zero rows)."""
    n = page.num_rows
    if n == 0 or page.live_count() == 0:
        return
    for lo in range(0, n, chunk_rows):
        yield page.slice_rows(lo, min(n, lo + chunk_rows))


_VOCAB_CACHE_MAX = 8  # distinct vocabularies a producer realistically shares


def _canonical_partition_ids(page: Page, channels, parts: int,
                             vocab_cache=None):
    """Per-row partition ids that agree ACROSS producer processes.

    partition_page_host's value hash is dictionary-scoped for varchar
    columns (int32 codes are page-local), which is fine for the spill path
    (one process, one dictionary) but would split equal string keys across
    FINAL tasks here. Varchar columns therefore hash their canonical UTF-8
    string per vocab entry (blake2b-8) and map codes through that table;
    other columns keep the shared splitmix64 value hash.

    ``vocab_cache`` (optional dict) memoizes the per-vocabulary hash
    table across a producer's pages — streaming producers share one
    dictionary across hundreds of pages, and re-blake2b-ing the whole
    vocabulary per page was the dominant per-call hash cost. Entries hold
    a strong reference to their Dictionary so the id key can never be
    reused by a different vocabulary; the cache is capped (FIFO) so
    producers whose pages carry PER-PAGE dictionaries cannot grow it or
    pin vocabularies unboundedly."""
    import hashlib

    import numpy as np

    from trino_tpu.exec.memory import _NULL_HASH, _mix64_np

    def _vocab_hashes(d):
        if vocab_cache is not None:
            hit = vocab_cache.get(id(d))
            if hit is not None and hit[0] is d:
                return hit[1]
        table = np.array(
            [
                int.from_bytes(
                    hashlib.blake2b(v.encode(), digest_size=8).digest(),
                    "little")
                for v in d.values
            ] or [0],
            dtype=np.uint64,
        )
        if vocab_cache is not None:
            while len(vocab_cache) >= _VOCAB_CACHE_MAX:
                vocab_cache.pop(next(iter(vocab_cache)))
            vocab_cache[id(d)] = (d, table)
        return table

    n = page.num_rows
    h = np.zeros(n, np.uint64)
    for ch in channels:
        col = page.columns[ch]
        if col.type.is_varchar and col.dictionary is not None:
            vocab_hash = _vocab_hashes(col.dictionary)
            codes = host_read(col.values, "partition")
            k = vocab_hash[np.clip(codes, 0, len(vocab_hash) - 1)]
            k = np.where(codes < 0, np.uint64(_NULL_HASH), k)
        else:
            # low limb only: equal values share it and hi-limb presence is
            # data-dependent per producer — mixing hi would break cross-
            # producer placement consistency (see exec/memory.py)
            k = _mix64_np(host_read(col.values, "partition").astype(np.int64))
        if col.nulls is not None:
            k = np.where(host_read(col.nulls, "partition"),
                         np.uint64(_NULL_HASH), k)
        h = _mix64_np(h ^ k)
    return (h % np.uint64(parts)).astype(np.int64)


def spool_directory() -> Optional[str]:
    """Cluster-shared spool location ('object storage' of the walking
    skeleton); unset disables spooling."""
    import os

    return os.environ.get("TRINO_TPU_SPOOL_DIR") or None


class TaskManager:
    """All tasks on this worker (reference: SqlTaskManager.java:109)."""

    # retained terminal tasks (status queries/late acks) — oldest evicted
    # (reference: SqlTaskManager's task info cache expiry)
    MAX_TASK_HISTORY = 200

    def __init__(self, session_factory, recorder=None, otlp=None,
                 segment_store=None):
        self._tasks: Dict[str, SqlTask] = {}
        self._lock = threading.Lock()
        self._session_factory = session_factory
        # worker-process observability hookups, threaded into every task
        # (obs/flightrecorder.FlightRecorder / obs/otlp.OtlpExporter)
        self._recorder = recorder
        self._otlp = otlp
        # spooled result protocol: the store result-producing tasks
        # (TaskRequest.spool_results) write their segments into
        self._segment_store = segment_store

    def create_task(self, request: TaskRequest,
                    traceparent: Optional[str] = None) -> SqlTask:
        with self._lock:
            terminal = [tid for tid, t in self._tasks.items() if t.state.is_terminal()]
            for tid in terminal[: max(0, len(terminal) - self.MAX_TASK_HISTORY)]:
                del self._tasks[tid]
            task = self._tasks.get(request.task_id)
            if task is None:
                task = SqlTask(request, self._session_factory,
                               traceparent=traceparent,
                               recorder=self._recorder, otlp=self._otlp,
                               segment_store=self._segment_store)
                self._tasks[request.task_id] = task
                created = True
            else:
                created = False
        if created:
            M.TASKS_TOTAL.inc()
            if self._recorder is not None:
                self._recorder.record(
                    "event", "task-created", taskId=request.task_id,
                    queryId=request.query_id,
                    splits=sum(len(v) for v in request.splits.values()))
        task.start()
        return task

    def get(self, task_id: str) -> Optional[SqlTask]:
        with self._lock:
            return self._tasks.get(task_id)

    def cancel(self, task_id: str) -> None:
        with self._lock:
            task = self._tasks.pop(task_id, None)
        if task is not None:
            task.output.abort("canceled")
            task.state.set("CANCELED")
            if self._recorder is not None:
                self._recorder.record("event", "task-canceled",
                                      taskId=task_id,
                                      queryId=task.request.query_id)

    def list_info(self) -> List[dict]:
        with self._lock:
            return [t.info() for t in self._tasks.values()]

    def query_memory(self) -> Dict[str, int]:
        """Reserved bytes per query on this worker (peak-while-running /
        buffered-while-flushing, see SqlTask.memory_bytes): the per-node
        half of the cluster memory pool (reference:
        memory/LocalMemoryManager feeding ClusterMemoryManager through
        node status)."""
        with self._lock:
            out: Dict[str, int] = {}
            for t in self._tasks.values():
                if t.state.is_terminal():
                    continue
                qid = t.request.query_id
                out[qid] = out.get(qid, 0) + t.memory_bytes
            return out
