"""Worker server: task CRUD + result streaming over HTTP.

Reference: ``server/TaskResource.java`` —
``POST /v1/task/{taskId}`` creates/updates a task (:140-145),
``GET /v1/task/{taskId}/results/{bufferId}/{token}`` streams pages
(:333-336), ``DELETE`` destroys; plus the worker side of discovery
(announce loop → coordinator, reference: airlift discovery announcer).

Built on the stdlib threading HTTP server — the control plane is
latency-bound, not throughput-bound (SURVEY.md §7.1 "control plane stays
host-side"); the data plane bodies are the serde's compressed columnar
pages.
"""
from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from trino_tpu.obs import trace as tracing
from trino_tpu.server import wire
from trino_tpu.server.task import TaskManager, TaskRequest

_RESULTS_RE = re.compile(r"^/v1/task/([^/]+)/results/(\d+)/(\d+)$")
_TASK_RE = re.compile(r"^/v1/task/([^/]+)$")
_STATUS_RE = re.compile(r"^/v1/task/([^/]+)/status$")
_SPANS_RE = re.compile(r"^/v1/task/([^/]+)/spans$")
_RECORDER_RE = re.compile(r"^/v1/task/([^/]+)/recorder$")
_SEGMENT_RE = re.compile(r"^/v1/segment/([^/]+)$")


def default_session_factory(properties):
    from trino_tpu.client.session import Session

    return Session(properties)


def shared_catalog_session_factory():
    """Session factory bound to ONE catalog map (and routine store) for the
    whole process, so stateful-connector writes and CREATE FUNCTION persist
    across tasks (see CoordinatorServer)."""
    from trino_tpu.connector.registry import default_catalogs

    catalogs = default_catalogs()
    udfs: dict = {}

    def factory(properties):
        from trino_tpu.client.session import Session

        return Session(properties, catalogs=catalogs, udfs=udfs)

    return factory


class WorkerServer:
    """One worker process: task manager + HTTP endpoint + announcer."""

    def __init__(self, port: int = 0, coordinator_url: Optional[str] = None,
                 node_id: Optional[str] = None, session_factory=None,
                 memory_limit_bytes: Optional[int] = None):
        import os

        self.node_id = node_id or f"worker-{time.time_ns() & 0xFFFFFF:x}"
        # this worker's failure flight recorder (obs/flightrecorder.py):
        # bounded ring of recent span/event records, pulled by the
        # coordinator into FAILED-query postmortems via
        # GET /v1/task/{id}/recorder
        from trino_tpu.obs.flightrecorder import FlightRecorder
        from trino_tpu.obs.memledger import MEMORY_LEDGER

        self.recorder = FlightRecorder(node_id=self.node_id)
        # the process memory ledger (obs/memledger.py): stamp this node's
        # identity (first server in the process wins — in-process test
        # clusters share one ledger exactly like they share the metrics
        # registry and the cache tiers) and mirror pressure sheds into
        # the flight recorder so OOM postmortems name the shed tier
        if not MEMORY_LEDGER.node_id:
            MEMORY_LEDGER.node_id = self.node_id
        MEMORY_LEDGER.attach_recorder(self.recorder)
        # the process device profiler (obs/devprofiler.py): same
        # first-server-wins identity stamp; compile-ledger events mirror
        # into the flight recorder so postmortems show recompile storms
        from trino_tpu.obs.devprofiler import (
            DEVICE_PROFILER, install_process_hooks)

        if not DEVICE_PROFILER.node_id:
            DEVICE_PROFILER.node_id = self.node_id
        DEVICE_PROFILER.attach_recorder(self.recorder)
        install_process_hooks()  # compile listener + GC pause recorder
        # the process flow ledger (obs/flowledger.py): same
        # first-server-wins identity stamp; retried transfers mirror into
        # the flight recorder so postmortems show flaky links
        from trino_tpu.obs.flowledger import FLOW_LEDGER

        if not FLOW_LEDGER.node_id:
            FLOW_LEDGER.node_id = self.node_id
        FLOW_LEDGER.attach_recorder(self.recorder)
        # OTLP export, on only when TRINO_TPU_OTLP_ENDPOINT is set: each
        # completed task ships its span dump under the query's PROPAGATED
        # trace id, so worker spans parent into the coordinator's trace
        # inside the collector too
        from trino_tpu.obs import otlp as _otlp

        self.otlp = _otlp.exporter_from_env(
            "trino-tpu-worker", instance_id=self.node_id)
        # spooled result segments (server/segments.py): result-producing
        # tasks write here; clients fetch via GET /v1/segment/{id} —
        # the worker IS the data plane, the coordinator never relays
        from trino_tpu.server.segments import SegmentStore

        self.segments = SegmentStore(node_id=self.node_id)
        self.tasks = TaskManager(
            session_factory or shared_catalog_session_factory(),
            recorder=self.recorder, otlp=self.otlp,
            segment_store=self.segments)
        self.coordinator_url = coordinator_url
        # per-worker memory pool size (reference: memory.heap-headroom /
        # query.max-memory-per-node config); None = unlimited
        env_limit = os.environ.get("TRINO_TPU_WORKER_MEMORY_BYTES")
        self.memory_limit_bytes = (
            memory_limit_bytes if memory_limit_bytes is not None
            else int(env_limit) if env_limit else None)
        # optional node host-RAM ceiling: process RSS over it sheds the
        # revocable cache tiers host-first (devcache.shed_revocable) on
        # the announce cadence; None = host RAM unmanaged
        env_host = os.environ.get("TRINO_TPU_HOST_MEMORY_LIMIT_BYTES")
        self.host_memory_limit_bytes = int(env_host) if env_host else None
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
        self.port = self.httpd.server_address[1]
        self.base_url = f"http://127.0.0.1:{self.port}"
        self._serve_thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._announce_thread = threading.Thread(target=self._announce_loop, daemon=True)
        self._stop = threading.Event()

    def start(self) -> None:
        self._serve_thread.start()
        if self.coordinator_url:
            self._announce_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.httpd.shutdown()
        self.httpd.server_close()
        self.segments.close()
        if self.otlp is not None:
            # flush + stop the exporter thread: a stopped instance must
            # not keep reporting metrics under its service.instance.id
            self.otlp.shutdown()

    def _announce_loop(self) -> None:
        """Periodic announce = discovery + liveness in one (reference:
        DiscoveryNodeManager polls announcements; HeartbeatFailureDetector
        pings — here the worker pushes, the coordinator ages entries out)."""
        while not self._stop.is_set():
            # piggyback the result-segment TTL sweep on the announce
            # cadence (rate-limited inside the store)
            try:
                self.segments.maybe_sweep()
            except Exception:  # noqa: BLE001 — lifecycle is best-effort
                pass
            try:
                from trino_tpu import __version__, devcache

                qmem = self.tasks.query_memory()
                if self.memory_limit_bytes is not None:
                    # the device table cache is this node's REVOCABLE
                    # tier: when queries + warm tables overflow the pool,
                    # shed cache FIRST — before the coordinator's
                    # low-memory killer would ever consider a query.
                    # DEVICE bytes only: the pool models query/device
                    # memory, and host-RAM cache bytes live in a
                    # different physical budget (counting them here
                    # would thrash the host tier on memory-tight
                    # workers while freeing nothing the pool needs).
                    # Scoped to the band where the cache IS the overflow
                    # (queries alone fit the pool): reservations are
                    # projected peaks, so a huge spilling join reports
                    # more than the pool while its partitioned passes
                    # stay under budget — eviction there cures nothing
                    # and the spill path's per-pass yield (exec/memory)
                    # already handles the real pressure.
                    q_total = sum(qmem.values())
                    over = (q_total
                            + devcache.DEVICE_CACHE.cached_bytes()
                            - self.memory_limit_bytes)
                    if over > 0 and q_total < self.memory_limit_bytes:
                        devcache.DEVICE_CACHE.yield_bytes(
                            over, reason="pool-overflow")
                # host-RAM pressure is the SEPARATE budget where the
                # two-tier shed order applies: when the process RSS
                # crosses the optional node limit, shed host pages
                # before warm-HBM entries (devcache.shed_revocable — a
                # lost host page costs one transfer to rebuild, a lost
                # HBM page costs the whole scan→decode→transfer path
                # once the host tier is gone too). CURRENT RSS only
                # (obs/metrics.current_rss_bytes): the gauge fallback
                # reports the lifetime PEAK on /proc-less platforms,
                # which would latch the shed on forever once crossed —
                # no reading, no shed.
                from trino_tpu.obs import metrics as M

                rss = M.current_rss_bytes()
                if self.host_memory_limit_bytes is not None and rss is not None:
                    over_host = rss - self.host_memory_limit_bytes
                    if over_host > 0:
                        devcache.shed_revocable(over_host)
                # sample the memory ledger on the announce cadence: live
                # per-owner bytes from ground-truth sources (the ledger's
                # event-driven live numbers never drift past one
                # heartbeat), per-pool watermarks + RSS + jax device
                # capacity into the per-node time series, and the
                # process gauges (RSS/fds/threads) so OTLP export and
                # system.metrics see LIVE values even when nobody
                # scrapes /v1/metrics
                mem_rows = self._sample_memory(qmem, rss)
                M.refresh_process_gauges()
                # device-profiler utilization tick (obs/devprofiler.py):
                # launches/sec + device-busy fraction since the last
                # heartbeat, and the newest compile-ledger events so
                # system.runtime.compiles merges cluster-wide
                from trino_tpu.obs.devprofiler import DEVICE_PROFILER

                util_sample = DEVICE_PROFILER.sample_utilization()
                compile_events = DEVICE_PROFILER.compile_rows(limit=64)
                # flow-ledger ride-alongs (obs/flowledger.py): per-link
                # rollups + stall timelines (system.runtime.transfers'
                # per-node source) and the NIC-level byte totals the
                # nodes table surfaces as net_bytes_sent/received
                from trino_tpu.obs.flowledger import FLOW_LEDGER

                flow_rows = FLOW_LEDGER.transfer_rows()
                flow_stalls = FLOW_LEDGER.stall_rows()
                net = FLOW_LEDGER.net_totals()
                wire.json_request(
                    "PUT",
                    f"{self.coordinator_url}/v1/announce/{self.node_id}",
                    {"url": self.base_url,
                     "tasks": len(self.tasks.list_info()),
                     # per-query live reservations + this worker's pool size:
                     # the coordinator's ClusterMemoryManager aggregates
                     # these (reference: node status -> ClusterMemoryPool)
                     "queryMemory": qmem,
                     "memoryBytes": sum(qmem.values()),
                     "memoryLimit": self.memory_limit_bytes,
                     # real accelerator capacity + warm-cache occupancy:
                     # admission sizes from hardware, the cache reads as
                     # revocable (server/cluster_memory.py)
                     "deviceMemoryBytes": devcache.device_memory_bytes(),
                     "deviceCacheBytes":
                         devcache.DEVICE_CACHE.cached_bytes(),
                     # host-RAM columnar tier occupancy + lifetime hits:
                     # the SECOND revocable tier (sheds first), surfaced
                     # by system.runtime.nodes (host_cache_* columns)
                     "hostCacheBytes":
                         devcache.HOST_CACHE.cached_bytes(),
                     "hostCacheHits": devcache.HOST_CACHE.hit_count(),
                     # per-pool, per-owner attribution rows (memory
                     # ledger): system.runtime.memory's per-node source
                     "memoryOwners": mem_rows,
                     # device-profiler ride-alongs: the latest utilization
                     # sample + newest compile-ledger events
                     # (system.runtime.compiles' per-node source)
                     "profiler": util_sample,
                     "compileEvents": compile_events,
                     # flow-ledger ride-alongs: per-link transfer rollups
                     # + backpressure stall rollups (the cluster-wide
                     # sources of system.runtime.transfers and the
                     # /flows surface) and NIC byte totals for the
                     # nodes table
                     "flows": flow_rows,
                     "flowStalls": flow_stalls,
                     "netBytesSent": net["sent"],
                     "netBytesReceived": net["received"],
                     "rssBytes": rss,
                     # surfaced by system.runtime.nodes (reference: the
                     # node version in NodeSystemTable rows)
                     "version": __version__},
                    timeout=5.0,
                )
            except Exception:  # noqa: BLE001 — coordinator may not be up yet
                pass
            self._stop.wait(0.5)

    def _sample_memory(self, qmem: dict, rss: Optional[int]) -> list:
        """One announce tick's memory-ledger sampling: sync live per-owner
        bytes from their ground-truth sources (task reservations, cache
        occupancy), sample the per-pool watermarks into the time-series
        ring, set the per-pool gauges, and return the per-owner rows the
        announce payload ships (``memoryOwners``)."""
        from trino_tpu import devcache
        from trino_tpu.obs import metrics as M
        from trino_tpu.obs.memledger import MEMORY_LEDGER, TOTAL_OWNER

        dev_owners = {f"query:{q}": int(b) for q, b in qmem.items()}
        dev_owners["device-cache"] = devcache.DEVICE_CACHE.cached_bytes()
        host_owners = {"host-cache": devcache.HOST_CACHE.cached_bytes()}
        # transient owners the sources above cannot see (staging scratch,
        # MV storage) ride in from the ledger's event-driven live bytes
        for row in MEMORY_LEDGER.owner_rows():
            owners = dev_owners if row["pool"] == "device" else host_owners
            if (row["owner"] != TOTAL_OWNER
                    and not row["owner"].startswith("query:")
                    and row["owner"] not in owners and row["bytes"] > 0):
                owners[row["owner"]] = row["bytes"]
        MEMORY_LEDGER.sync_pool("device", dev_owners, prefix="query:")
        MEMORY_LEDGER.sync_pool("host", host_owners)
        totals = {"device": sum(dev_owners.values()),
                  "host": sum(host_owners.values())}
        MEMORY_LEDGER.sample_watermarks(
            totals, rss_bytes=rss,
            device_total_bytes=devcache.device_memory_bytes())
        for pool, total in totals.items():
            M.MEMORY_POOL_BYTES.set(total, pool, self.node_id)
        ledger = {(r["pool"], r["owner"]): r
                  for r in MEMORY_LEDGER.owner_rows()}
        rows = []
        for pool, owners in (("device", dev_owners), ("host", host_owners)):
            for owner, nbytes in sorted(owners.items()):
                lr = ledger.get((pool, owner), {})
                rows.append({
                    "pool": pool, "owner": owner, "bytes": int(nbytes),
                    "peakBytes": max(int(lr.get("peakBytes", 0)),
                                     int(nbytes)),
                    "events": int(lr.get("events", 0)),
                })
            lr = ledger.get((pool, TOTAL_OWNER), {})
            rows.append({
                "pool": pool, "owner": TOTAL_OWNER,
                "bytes": int(totals[pool]),
                "peakBytes": max(int(lr.get("peakBytes", 0)),
                                 int(totals[pool])),
                "events": int(lr.get("events", 0)),
            })
        return rows


def _make_handler(server: WorkerServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # close keep-alive connections idle past this (the client pool's
        # idle TTL is shorter, so the client normally closes first)
        timeout = 30
        # TCP_NODELAY: headers and body flush as separate writes — with
        # Nagle on, the second write stalls behind the delayed ACK
        disable_nagle_algorithm = True

        def log_message(self, fmt, *args):  # quiet
            pass

        def _send(self, status: int, body: bytes = b"",
                  content_type: str = "application/json", headers: Optional[dict] = None):
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

        def do_POST(self):
            m = _TASK_RE.match(self.path)
            if m:
                body = self._read_body()
                if not wire.verify(body, self.headers.get(wire.H_INTERNAL_AUTH)):
                    self._send(401, b'{"error": "bad internal signature"}')
                    return
                request = TaskRequest.from_bytes(body)
                # trace-context propagation: the coordinator's schedule span
                # rides in on the traceparent header so this task's spans
                # parent into the query's trace tree
                task = server.tasks.create_task(
                    request, traceparent=self.headers.get(
                        tracing.TRACEPARENT_HEADER))
                self._send(200, json.dumps(task.info()).encode())
                return
            self._send(404)

        def _authorized(self) -> bool:
            """Every /v1/task route carries the cluster's HMAC (wire.sign of
            the body — empty for GET/DELETE), not just task creation: result
            pages and cancellation are control-plane surface too."""
            if wire.verify(b"", self.headers.get(wire.H_INTERNAL_AUTH)):
                return True
            self._send(401, b'{"error": "bad internal signature"}')
            return False

        def do_GET(self):
            m = _SEGMENT_RE.match(self.path)
            if m:
                # spooled result segments: NO cluster HMAC — the id is an
                # unguessable capability and the caller is an external
                # protocol client (the reference's pre-signed segment
                # URI model); range/ack semantics live in segments.py
                from trino_tpu.server.segments import segment_response

                status, body, headers, ctype = segment_response(
                    server.segments, m.group(1),
                    self.headers.get("Range"))
                self._send(status, body, ctype, headers)
                return
            m = _RESULTS_RE.match(self.path)
            if m:
                if not self._authorized():
                    return
                task = server.tasks.get(m.group(1))
                if task is None:
                    self._send(404, b'{"error": "no such task"}')
                    return
                pages, next_token, complete, failure = task.output.poll(
                    int(m.group(3)), buffer_id=int(m.group(2)))
                headers = {
                    wire.H_PAGE_TOKEN: m.group(3),
                    wire.H_NEXT_TOKEN: str(next_token),
                    wire.H_BUFFER_COMPLETE: "true" if complete else "false",
                }
                if failure:
                    headers[wire.H_TASK_FAILED] = failure.replace("\n", " ")[:900]
                self._send(200, wire.frame_pages(pages), wire.MEDIA_PAGES, headers)
                return
            m = _STATUS_RE.match(self.path)
            if m:
                if not self._authorized():
                    return
                task = server.tasks.get(m.group(1))
                if task is None:
                    self._send(404, b'{"error": "no such task"}')
                    return
                self._send(200, json.dumps(task.info()).encode())
                return
            m = _SPANS_RE.match(self.path)
            if m:
                if not self._authorized():
                    return
                task = server.tasks.get(m.group(1))
                if task is None:
                    self._send(404, b'{"error": "no such task"}')
                    return
                self._send(200, json.dumps({
                    "taskId": task.request.task_id,
                    "traceId": task.tracer.trace_id,
                    "spans": task.tracer.to_dicts(),
                }).encode())
                return
            m = _RECORDER_RE.match(self.path)
            if m:
                if not self._authorized():
                    return
                # the PROCESS ring, not a per-task record: a postmortem
                # wants the context AROUND the failure (what else ran,
                # which spans closed last) — and it still answers after
                # the task itself was pruned from the manager
                from trino_tpu.obs.flowledger import FLOW_LEDGER
                from trino_tpu.obs.memledger import MEMORY_LEDGER

                self._send(200, json.dumps({
                    "nodeId": server.node_id,
                    "taskId": m.group(1),
                    "taskKnown": server.tasks.get(m.group(1)) is not None,
                    "records": server.recorder.snapshot(),
                    # merged memory snapshot for OOM postmortems: pool
                    # watermarks + top consumers + recent sheds
                    "memory": MEMORY_LEDGER.memory_snapshot(),
                    # data-plane snapshot: per-link rollups + last
                    # transfers + stall timeline, so a FAILED postmortem
                    # shows what was moving when the query died
                    "flows": FLOW_LEDGER.flow_snapshot(),
                }).encode())
                return
            if self.path == "/v1/metrics":
                from trino_tpu.obs.metrics import render_registry

                self._send(200, render_registry().encode(),
                           "text/plain; version=0.0.4")
                return
            if self.path == "/v1/info":
                self._send(200, json.dumps(
                    {"nodeId": server.node_id, "state": "ACTIVE",
                     "tasks": server.tasks.list_info()}).encode())
                return
            self._send(404)

        def do_DELETE(self):
            m = _SEGMENT_RE.match(self.path)
            if m:
                # client ack: the segment was fetched — delete it now
                # instead of waiting out the TTL (idempotent: a repeated
                # ack of a gone segment is still a 204)
                server.segments.ack(m.group(1))
                self._send(204)
                return
            m = _RESULTS_RE.match(self.path)
            if m:
                if not self._authorized():
                    return
                # final ack: this consumer is done with the buffer
                task = server.tasks.get(m.group(1))
                if task is not None:
                    task.output.destroy_consumer(int(m.group(2)))
                self._send(204)
                return
            m = _TASK_RE.match(self.path)
            if m:
                if not self._authorized():
                    return
                server.tasks.cancel(m.group(1))
                self._send(204)
                return
            self._send(404)

    return Handler


def main() -> None:
    """Entry point: ``python -m trino_tpu.server.worker --port N
    --coordinator URL``."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--node-id", default=None)
    args = ap.parse_args()
    from trino_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    w = WorkerServer(args.port, args.coordinator, args.node_id)
    w.start()
    print(json.dumps({"nodeId": w.node_id, "url": w.base_url}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        w.stop()


if __name__ == "__main__":
    main()
