"""Typed metrics registry + Prometheus text exposition.

Reference role: airlift's ``@Managed`` counters exported through the
JMX-to-/metrics bridge (``trino-jmx`` + MetricsResource), replaced by an
explicit registry: every metric is DECLARED once, module-level, with a
type, help string, and label names — so the exporter, the docs checker
(``tools/check_metric_docs.py``), and the endpoint all read from one source
of truth and ad-hoc string rendering can't drift.

Three instrument types (the Prometheus core set the engine needs):

- ``Counter`` — monotonically increasing totals (bytes exchanged, retries);
- ``Gauge`` — point-in-time values (queries by state, worker count, uptime);
- ``Histogram`` — fixed-bucket latency distributions with ``_bucket`` /
  ``_sum`` / ``_count`` series (per-state query wall time).

The registry is process-global (``REGISTRY``): coordinator and worker are
separate processes, so each exports its own totals, exactly like the
reference's per-node JMX. Server-derived gauges are refreshed from the
owning server immediately before rendering, under ``RENDER_LOCK``
(server/events.render_metrics), and cleared afterwards so a same-process
worker render never re-exports another server's values.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

# fixed latency buckets (seconds) — chosen to straddle the engine's range:
# sub-10ms metadata statements through multi-minute sf100 scans
LATENCY_BUCKETS_S: Tuple[float, ...] = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    60.0, 120.0, 300.0)


def escape_label_value(value: str) -> str:
    """Prometheus text-format label escaping: backslash, double-quote, and
    newline must be escaped inside label values (exposition format spec)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _format_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(v) if isinstance(v, float) else str(v)


def _series(name: str, labels: Dict[str, str], value) -> str:
    if labels:
        inner = ",".join(
            f'{k}="{escape_label_value(v)}"' for k, v in labels.items())
        return f"{name}{{{inner}}} {_format_value(value)}"
    return f"{name} {_format_value(value)}"


class Metric:
    """Shared shape: name, help, label names, thread-safe child map keyed
    by label values."""

    type_name = "untyped"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = ()):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children: Dict[Tuple[str, ...], object] = {}
        self._lock = threading.Lock()

    def _labelkey(self, labelvalues: Sequence[str]) -> Tuple[str, ...]:
        if len(labelvalues) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {labelvalues!r}")
        return tuple(str(v) for v in labelvalues)

    def clear(self) -> None:
        with self._lock:
            self._children.clear()

    # -- rendering ---------------------------------------------------------
    def header(self) -> List[str]:
        return [f"# HELP {self.name} {self.help}",
                f"# TYPE {self.name} {self.type_name}"]

    def samples(self) -> List[tuple]:
        """Every touched series as ``(sample_name, labels_dict, value)``
        — the one expansion both the text rendering and the row view
        (``registry_samples``) consume, so they cannot diverge."""
        with self._lock:
            children = dict(self._children)
        return [(self.name, dict(zip(self.labelnames, key)), float(v))
                for key, v in sorted(children.items())]

    def render(self) -> List[str]:
        raise NotImplementedError


class Counter(Metric):
    type_name = "counter"

    def inc(self, amount: float = 1, *labelvalues) -> None:
        key = self._labelkey(labelvalues)
        with self._lock:
            self._children[key] = self._children.get(key, 0) + amount

    def value(self, *labelvalues) -> float:
        with self._lock:
            return self._children.get(self._labelkey(labelvalues), 0)

    def render(self) -> List[str]:
        return _render_flat(self)


class Gauge(Metric):
    type_name = "gauge"

    def set(self, value: float, *labelvalues) -> None:
        with self._lock:
            self._children[self._labelkey(labelvalues)] = value

    def inc(self, amount: float = 1, *labelvalues) -> None:
        key = self._labelkey(labelvalues)
        with self._lock:
            self._children[key] = self._children.get(key, 0) + amount

    def value(self, *labelvalues) -> float:
        with self._lock:
            return self._children.get(self._labelkey(labelvalues), 0)

    def render(self) -> List[str]:
        return _render_flat(self)


def _render_flat(metric: Metric) -> List[str]:
    """Counter/Gauge rendering: header always (the name is declared), a
    series per touched label set. Never-touched metrics emit NO series —
    a worker must not export the coordinator-derived gauges pinned at 0
    (which would read as 'this node has 0 uptime / 0 workers' on
    per-instance dashboards)."""
    lines = metric.header()
    for name, labels, v in metric.samples():
        lines.append(_series(name, labels, v))
    return lines


class Histogram(Metric):
    """Cumulative fixed-bucket histogram (``le`` buckets + sum + count)."""

    type_name = "histogram"

    def __init__(self, name: str, help: str, labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = LATENCY_BUCKETS_S):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(buckets))

    def observe(self, value: float, *labelvalues) -> None:
        key = self._labelkey(labelvalues)
        with self._lock:
            counts, total, n = self._children.get(
                key, ([0] * len(self.buckets), 0.0, 0))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            self._children[key] = (counts, total + value, n + 1)

    def snapshot(self, *labelvalues):
        """(bucket_counts, sum, count) for one label set (tests/listeners)."""
        with self._lock:
            counts, total, n = self._children.get(
                self._labelkey(labelvalues), ([0] * len(self.buckets), 0.0, 0))
            return list(counts), total, n

    def samples(self) -> List[tuple]:
        """Prometheus histogram expansion: one ``_bucket`` sample per
        ``le`` bound (cumulative, +Inf = observation count) plus ``_sum``
        and ``_count`` per label set."""
        with self._lock:
            children = {k: (list(c), t, n)
                        for k, (c, t, n) in self._children.items()}
        out: List[tuple] = []
        for key, (counts, total, n) in sorted(children.items()):
            base = dict(zip(self.labelnames, key))
            for b, c in zip(self.buckets, counts):
                out.append((f"{self.name}_bucket",
                            {**base, "le": _format_value(b)}, float(c)))
            out.append((f"{self.name}_bucket", {**base, "le": "+Inf"},
                        float(n)))
            out.append((f"{self.name}_sum", dict(base), float(total)))
            out.append((f"{self.name}_count", dict(base), float(n)))
        return out

    def render(self) -> List[str]:
        lines = self.header()
        for name, labels, v in self.samples():
            lines.append(_series(name, labels, v))
        return lines


# serializes refresh+render across ALL renderers in the process — the
# coordinator's gauge refresh (server/events.render_metrics) and any direct
# render_registry() caller (worker /v1/metrics) — so no scrape can observe
# a half-refreshed gauge. Reentrant: render_metrics holds it around its
# refresh window while calling render_registry.
RENDER_LOCK = threading.RLock()


class MetricsRegistry:
    """Ordered collection of declared metrics; renders the whole process's
    exposition page."""

    def __init__(self):
        self._metrics: Dict[str, Metric] = {}
        self._lock = threading.Lock()

    def _register(self, metric: Metric) -> Metric:
        with self._lock:
            existing = self._metrics.get(metric.name)
            if existing is not None:
                return existing  # module re-imports keep the same instance
            self._metrics[metric.name] = metric
            return metric

    def counter(self, name, help, labelnames=()) -> Counter:
        return self._register(Counter(name, help, labelnames))

    def gauge(self, name, help, labelnames=()) -> Gauge:
        return self._register(Gauge(name, help, labelnames))

    def histogram(self, name, help, labelnames=(),
                  buckets=LATENCY_BUCKETS_S) -> Histogram:
        return self._register(Histogram(name, help, labelnames, buckets))

    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return list(self._metrics)

    def render(self) -> str:
        with self._lock:
            metrics = list(self._metrics.values())
        with RENDER_LOCK:
            lines: List[str] = []
            for m in metrics:
                lines.extend(m.render())
        return "\n".join(lines) + "\n"


REGISTRY = MetricsRegistry()

# ----------------------------------------------------------- engine metrics
# Declared here (not at use sites) so every exported name is statically
# discoverable: tools/check_metric_docs.py imports this module and compares
# REGISTRY.names() against the README table.

# coordinator state gauges (refreshed per render via collect callbacks —
# see server/events.render_metrics). Names are byte-compatible with the
# seed's hand-rolled renderer.
QUERIES = REGISTRY.gauge(
    "trino_tpu_queries", "tracked queries by lifecycle state", ("state",))
QUERIES_TOTAL = REGISTRY.counter(
    "trino_tpu_queries_total", "queries submitted since server start")
QUERIES_TIME_LIMITED = REGISTRY.counter(
    "trino_tpu_queries_time_limited_total",
    "statements the coordinator ended for passing query_max_execution_time "
    "(EXCEEDED_TIME_LIMIT)")
RESULT_ROWS = REGISTRY.gauge(
    "trino_tpu_result_rows", "result rows held by FINISHED tracked queries")
WORKERS = REGISTRY.gauge(
    "trino_tpu_workers", "alive workers in the discovery registry")
UPTIME_SECONDS = REGISTRY.gauge(
    "trino_tpu_uptime_seconds", "seconds since server start")

# engine counters (process-global, incremented at the instrumented sites)
EXCHANGE_BYTES = REGISTRY.counter(
    "trino_tpu_exchange_bytes_total",
    "serialized page bytes pulled from upstream task buffers")
EXCHANGE_REQUESTS = REGISTRY.counter(
    "trino_tpu_exchange_requests_total",
    "exchange pull HTTP requests issued")
EXCHANGE_RETRIES = REGISTRY.counter(
    "trino_tpu_exchange_retries_total",
    "exchange pull attempts retried after transient failures")
SPOOL_READS = REGISTRY.counter(
    "trino_tpu_spool_reads_total",
    "task outputs served from the durable spool instead of a live buffer")
SPOOL_BYTES = REGISTRY.counter(
    "trino_tpu_spool_bytes_total",
    "page bytes read from durable spool files (kept separate from "
    "exchange bytes, which count network pulls from live buffers)")
# page serde (data/serde.py): per-column wire bytes by codec —
# zlib (blocks that actually shrank), none (incompressible blocks stored
# raw), logical (uncompressed column-block bytes, the denominator of the
# realized compression ratio)
SERDE_BYTES = REGISTRY.counter(
    "trino_tpu_serde_bytes_total",
    "page serde column-block bytes by direction and codec (codec = zlib "
    "compressed-wire | none raw-stored | logical uncompressed input/"
    "output; ratio = (zlib + none) / logical)", ("direction", "codec"))
# spooled result protocol (server/segments.py): result segments written
# by workers/the coordinator, served to clients, and reclaimed by the
# ack/TTL/orphan lifecycle
RESULT_SEGMENTS_WRITTEN = REGISTRY.counter(
    "trino_tpu_result_segments_written_total",
    "spooled result segments written to this process's segment store")
RESULT_SEGMENT_BYTES = REGISTRY.counter(
    "trino_tpu_result_segment_bytes_total",
    "spooled result segment bytes by direction (written = rolled into "
    "the segment store; served = read out by segment GETs)",
    ("direction",))
RESULT_SEGMENTS_RECLAIMED = REGISTRY.counter(
    "trino_tpu_result_segments_reclaimed_total",
    "result segments deleted, by reason (ack = client fetched and acked; "
    "ttl = expired un-acked, including failed queries' early drops; "
    "orphan = stale files swept at server start)", ("reason",))
RESULT_SEGMENT_RECLAIMED_BYTES = REGISTRY.counter(
    "trino_tpu_result_segment_reclaimed_bytes_total",
    "bytes reclaimed by result-segment deletion, by reason "
    "(ack | ttl | orphan)", ("reason",))
SPOOLED_RESULT_QUERIES = REGISTRY.counter(
    "trino_tpu_spooled_result_queries_total",
    "queries whose results were served as a spooled segment manifest, by "
    "mode (worker-direct = root-fragment producers wrote the segments "
    "and the coordinator never touched the data; coordinator = the "
    "coordinator spooled from its own segment store)", ("mode",))
INLINE_RESULT_REJECTIONS = REGISTRY.counter(
    "trino_tpu_inline_result_rejections_total",
    "queries failed by the inline-result memory guard "
    "(inline_result_max_bytes exceeded with spooled results disabled — "
    "the coordinator refuses to materialize, instead of OOMing the "
    "dispatch plane)")
COMPILE_CACHE_HITS = REGISTRY.counter(
    "trino_tpu_compile_cache_hits_total",
    "compiled-query runs reusing an already-built XLA executable")
COMPILE_CACHE_MISSES = REGISTRY.counter(
    "trino_tpu_compile_cache_misses_total",
    "compiled-query runs that traced+compiled (first run or capacity "
    "regrowth)")
COMPILE_SECONDS = REGISTRY.counter(
    "trino_tpu_compile_seconds_total",
    "wall seconds of compiled-query runs that traced+compiled (kept out "
    "of device seconds so one-time compiles don't skew throughput math)")
STAGING_SECONDS = REGISTRY.counter(
    "trino_tpu_staging_seconds_total",
    "host-side staging seconds charged to queries: the compiled tier "
    "charges dynamic-filter resolution + host domain application "
    "(bench's staging_df_s — the host work a run repeats without the "
    "device cache; CUMULATIVE across scan threads under the pipelined "
    "fan-out, so it can exceed the staging wall); the worker tier "
    "charges the per-split scan+assemble wall of FRESH stagings "
    "(device-cache hits charge nothing)")
DEVICE_SECONDS = REGISTRY.counter(
    "trino_tpu_device_seconds_total",
    "device execution wall seconds (fragment bodies / compiled runs)")
STAGED_ROWS = REGISTRY.counter(
    "trino_tpu_staged_rows_total", "rows staged from connectors into pages")
TASKS_TOTAL = REGISTRY.counter(
    "trino_tpu_tasks_total", "tasks created on this node")

# per-operator-kind rollups, fed from each task's accumulated OperatorStats
# at task completion (server/task.py) — the per-kernel attribution a
# serving stack needs ("which operator ate the rows/ms on this node")
OPERATOR_WALL_SECONDS = REGISTRY.histogram(
    "trino_tpu_operator_wall_seconds",
    "per-task operator wall time by operator kind, observed at task "
    "completion", ("operator",))
OPERATOR_ROWS = REGISTRY.counter(
    "trino_tpu_operator_rows_total",
    "rows output by operator kind, accumulated at task completion",
    ("operator",))

# device profiler (obs/devprofiler.py): per-operator launch + dispatch
# overhead counters bumped at query fold time (never per-dispatch), and
# the tiered compile-seconds histogram fed by every compile event
KERNEL_LAUNCHES = REGISTRY.counter(
    "trino_tpu_kernel_launches_total",
    "device dispatches by operator kind, folded from the kernel ledger "
    "at query completion", ("operator",))
KERNEL_DISPATCH_OVERHEAD = REGISTRY.counter(
    "trino_tpu_kernel_dispatch_overhead_seconds",
    "per-operator wall minus device seconds (host dispatch overhead — "
    "the number fragment megakernels must beat), folded from the kernel "
    "ledger at query completion", ("operator",))
# blocking device->host reads (obs/devprofiler.py host_read), by the static
# label of the call site; folded from the kernel ledger like the launches
HOST_SYNCS = REGISTRY.counter(
    "trino_tpu_host_syncs_total",
    "blocking device-to-host reads by call site, folded from the kernel "
    "ledger at query completion", ("site",))
HOST_SYNC_SECONDS = REGISTRY.counter(
    "trino_tpu_host_sync_seconds_total",
    "host seconds blocked in device-to-host reads by call site, folded "
    "from the kernel ledger at query completion", ("site",))
# process-wide collector pauses (obs/trace.py GcRecorder); read from the
# recorder's totals when the page renders, never per collection
GC_PAUSE_SECONDS = REGISTRY.counter(
    "trino_tpu_gc_pause_seconds_total",
    "seconds the Python garbage collector held the interpreter lock (no "
    "thread of this process ran Python meanwhile), by generation",
    ("generation",))
COMPILE_SECONDS_TIERED = REGISTRY.histogram(
    "trino_tpu_compile_seconds",
    "per-event jit/Pallas compile seconds by execution tier and "
    "compile-cache outcome (hit events observe ~0)", ("tier", "cache"))

# query caching subsystem (trino_tpu/cache/): coordinator result cache,
# logical-plan cache, and the connector-side datagen cache
RESULT_CACHE_HITS = REGISTRY.counter(
    "trino_tpu_result_cache_hits_total",
    "queries answered from the coordinator result cache (including "
    "single-flight followers served by a concurrent leader)")
RESULT_CACHE_MISSES = REGISTRY.counter(
    "trino_tpu_result_cache_misses_total",
    "cache-eligible queries that executed and (re)filled the result cache")
RESULT_CACHE_BYPASSES = REGISTRY.counter(
    "trino_tpu_result_cache_bypasses_total",
    "cache-enabled queries that bypassed the result cache (DML/DDL, "
    "non-deterministic functions, table functions, unversioned tables)")
RESULT_CACHE_EVICTIONS = REGISTRY.counter(
    "trino_tpu_result_cache_evictions_total",
    "result-cache entries evicted by the LRU byte budget")
RESULT_CACHE_BYTES = REGISTRY.gauge(
    "trino_tpu_result_cache_bytes",
    "estimated bytes of result pages held by the coordinator result cache")
RESULT_CACHE_SINGLE_FLIGHT_WAITS = REGISTRY.counter(
    "trino_tpu_result_cache_single_flight_waits_total",
    "queries that parked on a concurrent identical query's in-flight "
    "execution instead of executing themselves")
PLAN_CACHE_HITS = REGISTRY.counter(
    "trino_tpu_plan_cache_hits_total",
    "queries that reused a cached optimized logical plan (skipping "
    "parse/analyze/plan/optimize)")
PLAN_CACHE_MISSES = REGISTRY.counter(
    "trino_tpu_plan_cache_misses_total",
    "plan-cache lookups that planned from scratch (first sight, changed "
    "session properties, or a data-version mismatch)")
# materialized views (trino_tpu/matview/): the transparent planner
# substitution pass and the REFRESH swap
MV_SUBSTITUTIONS = REGISTRY.counter(
    "trino_tpu_mv_substitutions_total",
    "materialized-view substitution decisions by the planner pass "
    "(result = substituted | stale | access-denied | invalid): "
    "'substituted' rewrote a matched plan subtree into a storage-table "
    "scan; every other result fell back to the base plan", ("result",))
MV_REFRESH_SECONDS = REGISTRY.histogram(
    "trino_tpu_mv_refresh_seconds",
    "REFRESH MATERIALIZED VIEW wall time: plan + execute the definition "
    "+ atomic storage swap (+ the optional device-cache warm staging)")
GENCACHE_HITS = REGISTRY.counter(
    "trino_tpu_gencache_hits_total",
    "generator scan ranges served entirely from the datagen cache")
GENCACHE_MISSES = REGISTRY.counter(
    "trino_tpu_gencache_misses_total",
    "generator scan ranges that synthesized at least one column")
GENCACHE_EVICTIONS = REGISTRY.counter(
    "trino_tpu_gencache_evictions_total",
    "datagen cache entries evicted by the LRU byte budget")

# device table cache (trino_tpu/devcache/): warm-HBM buffer pool of staged
# scan artifacts, keyed by connector data_version — the repeat-traffic
# staging killer. Evictions count LRU budget pressure, revocable-tier
# yields to running queries, AND stale-version drops after DML.
DEVICE_CACHE_HITS = REGISTRY.counter(
    "trino_tpu_device_cache_hits_total",
    "table stagings served from the device cache (including single-flight "
    "followers served by a concurrent leader's transfer)")
DEVICE_CACHE_MISSES = REGISTRY.counter(
    "trino_tpu_device_cache_misses_total",
    "cache-eligible table stagings that transferred host pages to device "
    "and (budget permitting) filled the cache")
DEVICE_CACHE_BYPASS = REGISTRY.counter(
    "trino_tpu_device_cache_bypass_total",
    "table stagings the enabled device cache did not keep, by reason: "
    "over-cap (the staged table is larger than min(device_cache_max_bytes, "
    "the pool's budget)), unkeyed (unversioned connector, open "
    "transaction, unstable handle)", ("reason",))
DEVICE_CACHE_EVICTIONS = REGISTRY.counter(
    "trino_tpu_device_cache_evictions_total",
    "device-cache entries dropped (LRU byte budget, revocable-tier yield "
    "to a running query, or a stale data_version after DML)")
DEVICE_CACHE_BYTES = REGISTRY.gauge(
    "trino_tpu_device_cache_bytes",
    "device bytes held by the warm-HBM table cache (the revocable tier)")
DEVICE_CACHE_BUILD_HITS = REGISTRY.counter(
    "trino_tpu_device_cache_build_hits_total",
    "joins served a SORTED build-side artifact from the device cache (the "
    "warm repeated join skipped the build sort entirely; these also count "
    "in the general device-cache hit counter — the artifacts share the "
    "revocable-tier pool and byte budget)")
# host-RAM columnar page cache (trino_tpu/devcache/hostcache.py): the
# staging tier UNDER the warm-HBM pool — decoded per-split numpy column
# sets keyed by the same data_version signature, so an HBM eviction or a
# re-shard refills from host memory (transfer only) instead of re-running
# the connector scan and decode
HOST_CACHE_HITS = REGISTRY.counter(
    "trino_tpu_host_cache_hits_total",
    "split stagings served decoded columns from the host-RAM page cache "
    "(including single-flight followers served by a concurrent leader's "
    "scan) — the staging pipeline skipped the connector scan and decode")
HOST_CACHE_MISSES = REGISTRY.counter(
    "trino_tpu_host_cache_misses_total",
    "cache-eligible split stagings that ran the connector scan+decode and "
    "(budget permitting) filled the host-RAM page cache")
HOST_CACHE_EVICTIONS = REGISTRY.counter(
    "trino_tpu_host_cache_evictions_total",
    "host-cache entries dropped (LRU byte budget, revocable-tier shed, or "
    "a stale data_version after DML)")
HOST_CACHE_BYTES = REGISTRY.gauge(
    "trino_tpu_host_cache_bytes",
    "host RAM held by the columnar page cache (the second revocable tier "
    "— sheds before the warm-HBM tier under node pressure)")
# pipelined staging sub-phases (trino_tpu/exec/staging.py): the cold
# scan->decode->transfer path decomposed, so the trajectory can say WHICH
# stage of staging ate the wall. staging_seconds_total keeps its exact
# per-tier charging semantics (bench's staging_df_s identity); this
# counter is the finer-grained decomposition beside it.
STAGING_PHASE_SECONDS = REGISTRY.counter(
    "trino_tpu_staging_phase_seconds_total",
    "staging pipeline wall seconds by sub-phase: scan (parallel split "
    "read+decode fan-out), decode (host assembly: concat + dictionary "
    "merge), transfer (one host pass an array: narrowing + pad, one "
    "host->device put an array, one wait a page), host-cache (host-tier "
    "probe)", ("phase",))
# fused sort-merge join tier (ops/fused_join.py): kernel selections per
# join execution, labeled by the tier the cost gate chose
FUSED_JOIN_SELECTIONS = REGISTRY.counter(
    "trino_tpu_fused_join_selections_total",
    "join kernel selections by the fused-tier cost gate (tier = dense | "
    "fused | merge-sorted | merge-pallas | legacy); in the compiled/SPMD "
    "tiers a selection is counted per program TRACE, not per cached-"
    "executable run", ("tier",))
# overlapped ICI exchange (parallel/exchange.py): double-buffered send
# blocks pipelined against join compute in the SPMD tier
EXCHANGE_OVERLAPPED = REGISTRY.counter(
    "trino_tpu_exchange_overlapped_total",
    "probe-side exchanges compiled as double-buffered send-block pipelines "
    "(all-to-all of block k+1 overlapped with join compute on block k); "
    "counted per program trace, not per run", ("blocks",))

# adaptive execution (trino_tpu/adaptive/): runtime re-planning from the
# operator-stats spine, recorded per applied rule at the stage boundary
ADAPTIVE_ADAPTATIONS = REGISTRY.counter(
    "trino_tpu_adaptive_adaptations_total",
    "plan changes applied by the adaptive re-planner at stage boundaries",
    ("rule",))
ADAPTIVE_JOIN_FLIPS = REGISTRY.counter(
    "trino_tpu_adaptive_join_flips_total",
    "join-distribution switches (actual build rows contradicted the "
    "estimate across join_max_broadcast_rows)", ("direction",))
ADAPTIVE_RESEEDED_SOURCES = REGISTRY.counter(
    "trino_tpu_adaptive_reseeded_sources_total",
    "exchange sources stamped with actual upstream stage rows before "
    "their consumer fragment scheduled")
ADAPTIVE_SKEW_HOT_PARTITIONS = REGISTRY.counter(
    "trino_tpu_adaptive_skew_hot_partitions_total",
    "hot partitions salted by the adaptive skew mitigation (spread on "
    "the probe producer, replicated on the build producer)")

# serving fast path (server/prepared.py + server/fastpath.py): the
# high-QPS control-plane surface — prepared statements held by the
# coordinator registry, per-path execution counts, and EXECUTE bind time
# (the entire per-request planning cost once the parameterized plan is
# cached)
PREPARED_STATEMENTS = REGISTRY.gauge(
    "trino_tpu_prepared_statements",
    "prepared statements held by the coordinator registry (all users)")
FAST_PATH_QUERIES = REGISTRY.counter(
    "trino_tpu_fast_path_queries_total",
    "SELECT executions by control-plane path (fast-path = single-stage "
    "plan run coordinator-local, skipping task round-trips; distributed = "
    "fragment/schedule/execute across workers; local-catalog = forced "
    "coordinator-local by a process-local catalog)", ("path",))
EXECUTE_BIND_SECONDS = REGISTRY.histogram(
    "trino_tpu_execute_bind_seconds",
    "EXECUTE parameter bind time: constant-folding the USING expressions "
    "+ substituting them into the cached parameterized plan")

# dispatch plane / executor plane split (server/dispatch.py): the bounded
# dispatch queue between the HTTP front and the executor lanes, typed
# overload rejections, lane occupancy, and which plane ran each query
DISPATCH_QUEUE_DEPTH = REGISTRY.gauge(
    "trino_tpu_dispatch_queue_depth",
    "queries waiting in the bounded dispatch queue (between the HTTP "
    "front and the executor lanes)")
DISPATCH_REJECTED = REGISTRY.counter(
    "trino_tpu_dispatch_rejected_total",
    "statements rejected by the dispatch plane with the typed 429 + "
    "Retry-After overload response (reason = queue-full)", ("reason",))
DISPATCH_CACHE_SERVED = REGISTRY.counter(
    "trino_tpu_dispatch_cache_served_total",
    "queries answered entirely on the dispatch plane by the serving "
    "index (result-cache hit revalidated against connector data "
    "versions — no executor lane, no queue slot, no planning)")
EXECUTOR_LANES_BUSY = REGISTRY.gauge(
    "trino_tpu_executor_lanes_busy",
    "executor lanes currently running a query (the fixed lane pool "
    "replaced per-query thread creation)")
EXECUTOR_PLANE_QUERIES = REGISTRY.counter(
    "trino_tpu_executor_plane_queries_total",
    "dequeued queries by executing plane (inline = a dispatch-side "
    "executor lane; process = forwarded to an executor process; "
    "bounced = an executor process declined ownership and the query "
    "re-ran inline)", ("plane",))

# resource groups (server/resource_groups.py): hierarchical multi-tenant
# admission — per-group queue depth/occupancy gauges, queued-phase wait
# histogram, typed per-group rejections (queue-full = max_queued or
# global capacity at submit; queue-timeout = aged out of the group queue
# past queue_timeout_ms), and concurrency-free serving-index hits
# attributed to the group
RESOURCE_GROUP_QUEUED = REGISTRY.gauge(
    "trino_tpu_resource_group_queued",
    "queries parked in one resource group's queue", ("group",))
RESOURCE_GROUP_RUNNING = REGISTRY.gauge(
    "trino_tpu_resource_group_running",
    "queries running under one resource group (subtree rollup)",
    ("group",))
RESOURCE_GROUP_QUEUE_SECONDS = REGISTRY.histogram(
    "trino_tpu_resource_group_queue_seconds",
    "time a query waited in its resource group's queue before the "
    "weighted-fair drain admitted (or aged) it", ("group",))
RESOURCE_GROUP_REJECTED = REGISTRY.counter(
    "trino_tpu_resource_group_rejected_total",
    "queries a resource group said no to, by reason (queue-full = typed "
    "429 at submit; queue-timeout = typed EXCEEDED_QUEUE_TIMEOUT "
    "failure after aging out of the group queue)", ("group", "reason"))
RESOURCE_GROUP_SERVED = REGISTRY.counter(
    "trino_tpu_resource_group_served_total",
    "serving-index hits attributed to a resource group "
    "(concurrency-free: answered on the dispatch thread without "
    "occupying a group slot, counted so cached repeats stay auditable)",
    ("group",))

# HTTP keep-alive connection pool (server/wire.py): control-plane and
# client calls reuse pooled connections instead of a fresh TCP connect
# per request
HTTP_CONNECTIONS_OPENED = REGISTRY.counter(
    "trino_tpu_http_connections_opened_total",
    "fresh TCP connections opened by the keep-alive HTTP client pool")
HTTP_CONNECTION_REUSES = REGISTRY.counter(
    "trino_tpu_http_connection_reuses_total",
    "HTTP requests served over a pooled keep-alive connection (no TCP "
    "connect paid)")

# plan-IR sanity checking (sql/planner/sanity.py): invariant violations
# caught at plan time, labeled by the phase family that produced the bad
# plan (initial-plan | optimizer | fragmentation | adaptive). During
# adaptive re-planning a failure is CONTAINED (the pre-adaptation plan is
# kept, the query never fails), so this counter is the only loud signal.
PLAN_VALIDATION_FAILURES = REGISTRY.counter(
    "trino_tpu_plan_validation_failures_total",
    "plan invariant violations raised by the plan-IR sanity checker",
    ("phase",))

# latency distribution per terminal state (the per-state query histogram)
QUERY_SECONDS = REGISTRY.histogram(
    "trino_tpu_query_seconds",
    "query wall time by terminal state", ("state",))

# the query phase ledger (obs/timeline.py): exclusive wall per phase,
# observed once per terminal query for EVERY phase (zeros included) so
# bucket counts align across phases — the queued series is the
# queue-time histogram multi-tenant workload management reads, and the
# per-phase p99s are where a flat-p99 serving claim gets its attribution
QUERY_PHASE_SECONDS = REGISTRY.histogram(
    "trino_tpu_query_phase_seconds",
    "exclusive query wall seconds attributed to each phase by the "
    "completion-time phase ledger (queued | dispatch-queue | dispatch | "
    "parse-analyze | plan-optimize | prepare-bind | schedule | "
    "device-staging | device-execute | exchange-wait | "
    "result-serialization | segment-fetch | client-drain | "
    "unattributed)", ("phase",))

# tracing self-protection (obs/trace.py): per-tracer span cap — a
# pathological query stops RECORDING at the cap instead of growing
# coordinator/worker memory without bound
SPANS_DROPPED = REGISTRY.counter(
    "trino_tpu_spans_dropped_total",
    "spans dropped by the per-tracer span cap "
    "(TRINO_TPU_TRACE_MAX_SPANS, default 4096)")

# OTLP export (obs/otlp.py): the background batch exporter never blocks
# the query path — overflow of its bounded queue and failed sends DROP,
# counted here by reason
OTLP_DROPPED = REGISTRY.counter(
    "trino_tpu_otlp_dropped_total",
    "OTLP export spans/metric batches dropped instead of blocking "
    "(reason = overflow: bounded queue full; send-error: collector "
    "unreachable or non-2xx)", ("reason",))


# system catalog (trino_tpu/connector/system/): coordinator query-history
# ring occupancy + ring evictions (reference: QueryTracker's
# query.max-history expiry)
QUERY_HISTORY_SIZE = REGISTRY.gauge(
    "trino_tpu_query_history_size",
    "completed-query records held by the coordinator history ring "
    "(system.runtime.queries coverage of finished queries)")
QUERY_HISTORY_EVICTIONS = REGISTRY.counter(
    "trino_tpu_query_history_evictions_total",
    "completed-query records evicted from the coordinator history ring "
    "(query_max_history / query_min_expire_age_ms retention)")


# process self-metrics: the "host sick vs engine slow" discriminators
# (RSS, FDs, threads, GC) — refreshed immediately before every render so
# both coordinator and worker /v1/metrics (and system.metrics) carry a
# current reading without a background sampler thread
PROCESS_RSS_BYTES = REGISTRY.gauge(
    "trino_tpu_process_rss_bytes",
    "resident set size of this server process (VmRSS)")
PROCESS_OPEN_FDS = REGISTRY.gauge(
    "trino_tpu_process_open_fds",
    "open file descriptors held by this server process")
PROCESS_THREADS = REGISTRY.gauge(
    "trino_tpu_process_threads",
    "live Python threads in this server process")
PROCESS_GC_COLLECTIONS = REGISTRY.gauge(
    "trino_tpu_process_gc_collections",
    "Python GC collections per generation since process start "
    "(point-in-time read of gc.get_stats)", ("generation",))


# fixed byte buckets for memory-size histograms: 64KiB..64GiB in powers
# of four — straddles tiny test pages through sf100 working sets
MEMORY_BUCKETS_BYTES: Tuple[float, ...] = (
    64 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20, 64 << 20, 256 << 20,
    1 << 30, 4 << 30, 16 << 30, 64 << 30)

# cluster memory ledger (obs/memledger.py): per-pool occupancy sampled on
# the worker announce loop, pressure-shed events by reclaiming action,
# and the per-query peak distribution observed at query completion
MEMORY_POOL_BYTES = REGISTRY.gauge(
    "trino_tpu_memory_pool_bytes",
    "live memory-pool occupancy by pool and node, sampled on the worker "
    "announce loop (device = query reservations + warm-HBM cache [+ "
    "staging scratch]; host = host-RAM page cache [+ other tracked host "
    "owners])", ("pool", "node"))
MEMORY_PRESSURE_EVENTS = REGISTRY.counter(
    "trino_tpu_memory_pressure_events_total",
    "revocable-tier pressure sheds by reclaiming action (spill = a "
    "query's pre-spill cache yield; pool-overflow = device pool over its "
    "limit; host-pressure = process RSS over the node limit; "
    "rss-escalation = host pressure escalated into host-backed device "
    "entries; yield = direct cache yields)", ("action",))
QUERY_PEAK_MEMORY_BYTES = REGISTRY.histogram(
    "trino_tpu_query_peak_memory_bytes",
    "per-query peak device-pool bytes (max over tasks/stages), observed "
    "once per terminal query", ("state",),
    buckets=MEMORY_BUCKETS_BYTES)

# data-plane flow ledger (obs/flowledger.py): every cross-boundary byte
# typed by link class, the producers' backpressure stalls, and the
# straggler detector's terminal-query verdicts
TRANSFER_BYTES = REGISTRY.counter(
    "trino_tpu_transfer_bytes_total",
    "bytes moved across a data-plane link, by link class (exchange-pull "
    "| spool-write | segment-fetch | staging-transfer | client-drain | "
    "control) and direction (send | recv, from this process's "
    "viewpoint)", ("link", "direction"))
TRANSFER_SECONDS = REGISTRY.counter(
    "trino_tpu_transfer_seconds",
    "wall seconds spent moving bytes on a data-plane link (cumulative "
    "across concurrent transfers, so bytes/seconds is the per-stream "
    "effective rate, not the aggregate)", ("link",))
BACKPRESSURE_STALL_SECONDS = REGISTRY.counter(
    "trino_tpu_backpressure_stall_seconds_total",
    "seconds producers spent blocked on full output buffers plus "
    "consumers spent on empty exchange polls, by stage", ("stage",))
STRAGGLER_TASKS = REGISTRY.counter(
    "trino_tpu_straggler_tasks_total",
    "tasks flagged by the straggler detector at query completion, by "
    "dominant cause (transfer-bound | device-bound | queue-bound)",
    ("cause",))


def current_rss_bytes():
    """This process's CURRENT resident set (VmRSS), or None where /proc
    is unavailable — callers needing a live pressure signal (the worker
    host-RAM shed) must treat None as "unknown", never as 0 (the gauge
    fallback below reports the lifetime PEAK, which would latch any
    threshold forever)."""
    try:
        with open("/proc/self/status", encoding="ascii") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def refresh_process_gauges() -> None:
    """Sample the process self-metrics (Linux /proc where available,
    portable fallbacks otherwise); failures leave the previous reading."""
    import gc
    import threading as _threading

    rss = current_rss_bytes()
    if rss is not None:
        PROCESS_RSS_BYTES.set(rss)
    else:
        try:
            import resource
            import sys as _sys

            # ru_maxrss is the PEAK, in bytes on macOS and KiB elsewhere
            # (this branch only runs where /proc is absent) — coarse but
            # unit-correct fallback
            unit = 1 if _sys.platform == "darwin" else 1024
            PROCESS_RSS_BYTES.set(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * unit)
        except Exception:  # noqa: BLE001 — self-metrics are best-effort
            pass
    try:
        import os as _os

        PROCESS_OPEN_FDS.set(len(_os.listdir("/proc/self/fd")))
    except OSError:
        pass
    PROCESS_THREADS.set(_threading.active_count())
    for gen, st in enumerate(gc.get_stats()):
        PROCESS_GC_COLLECTIONS.set(int(st.get("collections", 0)), str(gen))
    _publish_gc_pauses()


_gc_published: Dict[int, float] = {}


def _publish_gc_pauses() -> None:
    """Move what the GC recorder has counted since the last render into
    ``trino_tpu_gc_pause_seconds_total``."""
    try:
        from trino_tpu.obs.trace import GC_RECORDER
    except ImportError:  # loaded as a standalone file by the doc gate
        return
    with RENDER_LOCK:
        for gen, total in list(GC_RECORDER.total_s.items()):
            delta = total - _gc_published.get(gen, 0.0)
            if delta > 0:
                GC_PAUSE_SECONDS.inc(delta, str(gen))
                _gc_published[gen] = total


def render_registry() -> str:
    """The whole process's exposition page (worker /v1/metrics, and the
    body of the coordinator's after its gauges refresh)."""
    refresh_process_gauges()
    return REGISTRY.render()


def registry_samples() -> List[tuple]:
    """Every touched series as ``(name, type, labels_dict, value, help)``
    tuples — the row-shaped view of the exposition page that feeds the
    ``system.metrics`` table (the jmx-connector role). Built from the
    same per-metric ``samples()`` expansion the text rendering consumes,
    so the table cannot diverge from ``/v1/metrics``."""
    refresh_process_gauges()
    with REGISTRY._lock:
        metrics = list(REGISTRY._metrics.values())
    out: List[tuple] = []
    with RENDER_LOCK:
        for m in metrics:
            out.extend((name, m.type_name, labels, value, m.help)
                       for name, labels, value in m.samples())
    return out
