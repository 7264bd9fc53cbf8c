"""Whole-query compilation: trace the executor once, jit, reuse.

Reference role: this is the moral equivalent of the reference's query-time
bytecode generation pipeline (``sql/gen/ExpressionCompiler`` + operator
factories baked per query by ``LocalExecutionPlanner``) — except the unit of
compilation is the *entire query body* (scan outputs -> final page), so XLA
fuses across operator boundaries (filter into scan into partial-agg, etc.),
which no per-operator engine can do.

The compiled artifact is reusable across runs with same-shaped inputs
(same splits).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.data.page import Page
from trino_tpu.exec.executor import Executor, QueryError
from trino_tpu.exec.page_tree import PageSpec, flatten_page, unflatten_page
from trino_tpu.obs import metrics as M
from trino_tpu.obs import trace as tracing
from trino_tpu.sql.planner import plan as P


# strong domains (|set|/NDV at or below this) prune rows HOST-SIDE at
# staging — a cheap numpy LUT pass that cuts the host->device transfer,
# the staging bottleneck at scale; weaker domains are enforced on device
HOST_APPLY_MAX_SEL = 0.25
# max probe-column value span for an in-program boolean LUT (bytes on the
# device = span); wider spans degrade to min/max range narrowing. 1<<28 =
# 256 MB worst case — big enough for sf100 orderkeys (150M span)
LUT_MAX_SPAN = 1 << 28


class StagingExecutor(Executor):
    """Stages scans for the compiled tier: constraint pushdown (including
    resolved dynamic domains — the connector can prune clustered key runs
    at the generator level) plus SELECTIVE host row filtering: strongly
    narrowing domains prune rows before the device transfer (fewer
    bytes cross host->device), while weak
    domains are left for PreloadedExecutor to enforce on device. The split
    is decided per domain by ``df_host_allow`` (set in
    CompiledQuery.build from NDV selectivity estimates)."""

    df_host_allow = None  # callable(node, column, domain) -> bool


class PreloadedExecutor(Executor):
    """Executor that reads table scans from pre-staged pages (the traced
    inputs) instead of calling the connector, with IN-PROGRAM dynamic
    filtering: when a join executes its build side, the traced key values
    ride into a boolean lookup table (one scatter, statically sized from
    the probe column's vrange) or a min/max range; probe scans deeper in
    the recursion mask against it and compact to a stats-sized capacity.
    The whole collect->apply dataflow lives inside the single compiled
    program — ZERO host work repeats per run (reference:
    DynamicFilterService.java:105 + DynamicFiltersCollector, redesigned as
    a pure dataflow instead of a coordinator round-trip)."""

    eager_tier = False  # runs under jax tracing: no host-side syncs
    enable_dynamic_filtering = True  # traced collection (see below)
    collect_stats = False  # tracing once; per-call timing is meaningless

    def __init__(self, session, staged: Dict[int, Page], capacity_hints=None,
                 device_df=None):
        super().__init__(session, capacity_hints)
        self.staged = staged
        # scan node_id -> [(channel, join_id, key_idx, spec)] where spec is
        # ("lut", lo, span) with STATIC bounds from the probe column's
        # vrange, or ("range",) for min/max-only narrowing
        self.device_df = device_df or {}
        # (join_id, key_idx) -> (traced key values, traced live mask),
        # registered by _collect_dynamic_filters during the build-side
        # visit, consumed by probe scans later in the same trace
        self.traced_domains: Dict[Tuple[int, int], tuple] = {}

    def _collect_dynamic_filters(self, node: P.JoinNode, build: Page,
                                 measured: Dict) -> None:
        """Traced collection: no host syncs, just remember the build-side
        key column (+liveness) for probe scans to mask against
        (``measured`` is the eager tier's host read: empty here)."""
        for i in node.dyn_filter_keys:
            ch = node.right_keys[i]
            col = build.columns[ch]
            if col.type.is_varchar or col.hi is not None:
                continue  # dictionary codes are page-local; two-limb later
            live = (build.sel if build.sel is not None
                    else jnp.ones(build.num_rows, bool))
            if col.nulls is not None:
                live = live & ~col.nulls
            self.traced_domains[(node.id, i)] = (col.values, live)

    def _exec_TableScanNode(self, node: P.TableScanNode) -> Page:
        page = self.staged[node.id]
        entries = self.device_df.get(node.id)
        if not entries:
            return page
        sel = page.sel if page.sel is not None else jnp.ones(page.num_rows, bool)
        applied = False
        for ch, join_id, key_idx, spec in entries:
            dom = self.traced_domains.get((join_id, key_idx))
            if dom is None:
                continue  # build side could not register (exotic key type)
            col = page.columns[ch]
            m = _traced_domain_mask(col.values, dom, spec)
            if col.nulls is not None:
                m = m & ~col.nulls
            sel = sel & m
            applied = True
        if not applied:
            return page
        page = Page(list(page.columns), sel, page.replicated)
        cap = self.capacity_hints.get(f"dfc:{node.id}")
        if cap is not None:
            page = self.compact_to(page, cap, f"dfc:{node.id}")
        return page


def _traced_domain_mask(values, dom, spec):
    """Membership of probe ``values`` in a traced build-side key set.
    LUT path: the dense boolean-table membership kernel shared with semi
    joins (ops/join.py dense_membership — one scatter, one bounded gather;
    NEVER jnp.searchsorted, whose log2(n) dependent random-gather passes
    cost ~2.5 s for 6M probes on v5e). Range path: masked min/max
    reductions — empty build sides yield an all-false mask (inner/semi
    join with an empty build emits nothing)."""
    from trino_tpu.ops import join as join_ops

    bvals, blive = dom
    if spec[0] == "lut":
        _, lo, span = spec
        return join_ops.dense_membership(
            (bvals, None), blive, (values, None), lo, span)
    bv = bvals.astype(jnp.int64)
    big = jnp.int64(1) << 62
    lo = jnp.min(jnp.where(blive, bv, big))
    hi = jnp.max(jnp.where(blive, bv, -big))
    v = values.astype(jnp.int64)
    return (v >= lo) & (v <= hi)


@dataclasses.dataclass
class CompiledQuery:
    session: object
    root: P.OutputNode
    input_arrays: List
    input_specs: Dict[int, PageSpec]
    fn: object  # jitted
    out_spec_cell: List
    error_codes_cell: List
    capacity_hints: Dict[str, int] = dataclasses.field(default_factory=dict)
    # two-phase execution profile: host phase-1 wall (dynamic-filter build
    # evaluation, exec/host_eval.py), host domain-application wall at the
    # scans, and per-scan staged row counts. Benchmarks charge
    # phase1_s + df_apply_s to every run: it is query work done off-device.
    phase1_s: float = 0.0
    df_apply_s: float = 0.0
    scan_rows: Dict[int, int] = dataclasses.field(default_factory=dict)
    # staging profile of the build: wall seconds of the staging loop, how
    # many scans the device cache served warm, and the rows that actually
    # crossed host->device (0 on a fully warm build — the warm-run proof)
    staging_s: float = 0.0
    cache_hits: int = 0
    fresh_staged_rows: int = 0
    # capacity-overflow regrowth recompiles this query has paid (the
    # double-and-recompile loop; 0 when hints were right the first time —
    # e.g. under adaptive_capacity_reseed)
    recompiles: int = 0
    # kernel-ledger rollup (obs/devprofiler.py): one "CompiledBody" row
    # accumulating this query's jitted-body dispatches
    kernel_stats: Dict[tuple, dict] = dataclasses.field(default_factory=dict)
    # compile-ledger identity, computed lazily once per instance
    _fingerprint: str = ""

    MAX_RECOMPILES = 16  # doubling buckets: 2^16x headroom over the estimate

    @classmethod
    def build(
        cls, session, root: P.OutputNode, capacity_hints: Dict[str, int] = None
    ) -> "CompiledQuery":
        """Two-phase compile (reference: DynamicFilterService +
        AdaptivePlanner): phase 1 host-evaluates DF build sides and narrows
        probe scans BEFORE staging; actual staged cardinalities then right-
        size capacities (stats start from truth). Phase 2 traces the query
        body once over the narrowed inputs. If a run still overflows a
        bucket, ``run()`` doubles it and recompiles."""
        from trino_tpu.exec import host_eval
        from trino_tpu.sql.planner import stats

        t0 = time.perf_counter()
        with tracing.span("staging/dynamic-filters"):
            dyn = host_eval.resolve_dynamic_filters(session, root)
        phase1_s = time.perf_counter() - t0
        scans = [n for n in P.walk_plan(root) if isinstance(n, P.TableScanNode)]

        def _dom_sel(node, col_name, dom):
            """|domain| / column NDV — the narrowing strength estimate."""
            if dom.values is None:
                return 1.0
            conn = session.catalogs[node.catalog]
            cs = conn.column_stats(node.schema, node.table, col_name)
            if cs is not None and cs.ndv:
                return min(1.0, len(dom.values) / cs.ndv)
            return 1.0

        def host_allow(node, col_name, dom):
            return dom.values is not None and \
                _dom_sel(node, col_name, dom) <= HOST_APPLY_MAX_SEL

        base = StagingExecutor(session)
        base.df_host_allow = host_allow
        base.dyn_domains.update(dyn)
        with tracing.span("device/staging") as stage_sp:
            t_stage = time.perf_counter()
            staged_pages = {n.id: base._exec_TableScanNode(n) for n in scans}
            staging_s = time.perf_counter() - t_stage
            # a device-cache HIT staged zero host->device bytes: the span's
            # staged_rows (the warm-run proof signal) and STAGED_ROWS count
            # only freshly transferred scans; cached rows report separately
            cache_hits = sum(
                1 for n in scans if base.scan_cache.get(n.id) == "hit")
            fresh_staged = sum(
                base.scan_stats.get(n.id, staged_pages[n.id].num_rows)
                for n in scans if base.scan_cache.get(n.id) != "hit")
            total_staged = sum(
                base.scan_stats.get(n.id, staged_pages[n.id].num_rows)
                for n in scans)
            stage_sp.set("staged_rows", int(fresh_staged))
            stage_sp.set("cached_rows", int(total_staged - fresh_staged))
            stage_sp.set("cache_hits", cache_hits)
            stage_sp.set("scans", len(scans))
        # phase1_s + df_apply_s: DF resolution plus host domain
        # application — the counter charges exactly that (asserted by
        # tests/test_device_cache.py::test_staging_seconds_accounting)
        M.STAGED_ROWS.inc(int(fresh_staged))
        M.STAGING_SECONDS.inc(phase1_s + base.df_apply_s)
        # in-program dynamic-filter specs + stats-sized compaction per scan.
        # Every (join, key) the optimizer annotated is applied ON DEVICE by
        # the traced collect->mask dataflow — including builds the host
        # evaluator cannot reproduce (host_eval's Unsupported shapes); the
        # host-resolved domains are used here only to (a) prune STAGING for
        # strong domains and (b) right-size the compaction capacities.
        df_hints: Dict[str, int] = {}
        device_df: Dict[int, List] = {}  # nid -> [(ch, join_id, key_idx, spec)]
        joins_by_id = {
            n.id: n for n in P.walk_plan(root) if isinstance(n, P.JoinNode)
        }
        for n in scans:
            staged_rows = base.scan_stats.get(n.id, staged_pages[n.id].num_rows)
            if not n.dynamic_filters:
                n.runtime_rows = staged_rows
                continue
            page = staged_pages[n.id]
            sel_frac = 1.0
            entries: List = []
            for join_id, key_idx, col_name in n.dynamic_filters:
                ch = n.column_names.index(col_name)
                col = page.columns[ch]
                join = joins_by_id.get(join_id)
                if col.type.is_varchar or col.hi is not None or join is None:
                    continue
                bcol_t = join.right.output_types[join.right_keys[key_idx]]
                if bcol_t.is_varchar:
                    continue  # build side cannot register this key
                dom_known = dyn.get((join_id, key_idx))
                if dom_known is not None and host_allow(n, col_name, dom_known):
                    # already physically applied at staging: an in-program
                    # mask would be provably all-true — skip the hot-path
                    # scatter+gather entirely
                    continue
                vr = col.vrange
                lut = vr is not None and (vr[1] - vr[0] + 1) <= LUT_MAX_SPAN
                if lut:
                    entries.append(
                        (ch, join_id, key_idx,
                         ("lut", int(vr[0]), int(vr[1] - vr[0] + 1))))
                else:
                    entries.append((ch, join_id, key_idx, ("range",)))
                if dom_known is not None and lut:
                    # discount only set domains the device enforces EXACTLY
                    # (the LUT); a range-degraded spec keeps far more rows
                    # than |set|/NDV, so it must not shrink the estimate,
                    # and host-applied domains already shrank staged_rows
                    sel_frac *= _dom_sel(n, col_name, dom_known)
            if not entries:
                n.runtime_rows = staged_rows
                continue
            device_df[n.id] = entries
            # base the estimate on the rows actually staged (host pruning
            # already happened); discount only the device-side narrowing
            est = max(int(staged_rows * sel_frac), 1)
            n.runtime_rows = est
            cap = 1 << max(int(est * 1.3), 1024).bit_length()
            if cap < staged_rows:
                df_hints[f"dfc:{n.id}"] = cap
        if capacity_hints is None:
            capacity_hints = stats.estimate_capacity_hints(session, root)
        from trino_tpu.adaptive.reseed import apply_reseed, reseed_enabled

        if reseed_enabled(session):
            # adaptive capacity reseeding (trino_tpu/adaptive/reseed.py):
            # the staged pages ARE the actual upstream rows — price
            # expansion-join capacities from their key histograms instead
            # of the static fudge-factor guesses, replacing over-allocation
            # AND the double-and-recompile loop in one move
            apply_reseed(session, root, staged_pages, 1, capacity_hints)
        capacity_hints.update(df_hints)
        flat_inputs: List = []
        specs: Dict[int, PageSpec] = {}
        layout: List[Tuple[int, int]] = []  # (node_id, num_arrays)
        for nid, page in staged_pages.items():
            arrays, spec = flatten_page(page)
            specs[nid] = spec
            layout.append((nid, len(arrays)))
            flat_inputs.extend(arrays)
        cq = cls(session, root, flat_inputs, specs, None, [None], [None], dict(capacity_hints))
        cq.phase1_s = phase1_s
        cq.df_apply_s = base.df_apply_s
        cq.scan_rows = dict(base.scan_stats)
        # device-cache disposition of this build's staging (warm-serving
        # telemetry: tests/test_device_cache.py reads these)
        cq.staging_s = staging_s
        cq.cache_hits = cache_hits
        cq.fresh_staged_rows = int(fresh_staged)
        cq._layout = layout
        cq._device_df = device_df
        cq._jit()
        return cq

    def _jit(self):
        session, root, specs = self.session, self.root, self.input_specs
        layout, hints = self._layout, self.capacity_hints
        device_df = getattr(self, "_device_df", {})
        out_spec_cell, error_codes_cell = self.out_spec_cell, self.error_codes_cell

        def run(flat):
            pages: Dict[int, Page] = {}
            i = 0
            for nid, count in layout:
                pages[nid] = unflatten_page(specs[nid], flat[i : i + count])
                i += count
            ex = PreloadedExecutor(session, pages, dict(hints), device_df)
            out_page = ex.execute(root)
            out_arrays, out_spec = flatten_page(out_page)
            out_spec_cell[0] = out_spec
            error_codes_cell[0] = [c for c, _ in ex.errors]
            return out_arrays, [f for _, f in ex.errors]

        self.raw_fn = run  # unjitted closure (for AOT/compile-check harnesses)
        self.fn = jax.jit(run)
        # compile-cache state: the jitted callable IS the cache (reused
        # executable across runs); a fresh _jit means the next call traces
        # + compiles (a miss), later calls reuse the executable (hits)
        self._executable_fresh = True

    def _profile_run(self, fresh: bool, dispatch_wall_s: float,
                     body_device_s: float, estimated: bool) -> None:
        """Feed the device profiler: one compile-ledger event per run
        (miss on fresh executables, hit on reuse) + a ``CompiledBody``
        kernel row. Best-effort — accounting never fails work."""
        try:
            from trino_tpu.cache.plan_key import plan_fingerprint
            from trino_tpu.obs.devprofiler import (
                DEVICE_PROFILER, shape_signature)

            if not self._fingerprint:
                self._fingerprint = plan_fingerprint(self.root)
            DEVICE_PROFILER.record_compile(
                "compiled", self._fingerprint,
                shape_signature(self.input_arrays),
                dispatch_wall_s if fresh else 0.0,
                "miss" if fresh else "hit", started=fresh)
            # a fresh run's dispatch wall is dominated by trace+compile —
            # charged to the compile ledger above, NOT to the kernel row,
            # so dispatch overhead stays a steady-state signal
            wall = (body_device_s if fresh
                    else dispatch_wall_s + (0.0 if estimated
                                            else body_device_s))
            key = (str(self.root.id), "CompiledBody", "compiled")
            ks = self.kernel_stats.get(key)
            if ks is None:
                ks = self.kernel_stats[key] = {
                    "planNodeId": key[0], "operator": key[1],
                    "tier": "compiled", "launches": 0, "wallS": 0.0,
                    "deviceS": 0.0, "inputBytes": 0, "outputBytes": 0,
                    "estimated": estimated}
            ks["launches"] += 1
            ks["wallS"] += wall
            ks["deviceS"] += body_device_s
            ks["estimated"] = bool(ks["estimated"] or estimated)
            DEVICE_PROFILER.count_launch(wall, body_device_s
                                         if not estimated else 0.0)
        except Exception:  # noqa: BLE001 — accounting never fails work
            pass

    def run(self) -> Page:
        """Execute; on a capacity overflow, double the offending join's
        bucket and recompile (reference analog: the spill/partition FSM of
        HashBuilderOperator — growth instead of spill)."""
        from trino_tpu.exec.executor import QueryError, raise_query_errors
        from trino_tpu.sql.planner import stats

        for _ in range(self.MAX_RECOMPILES):
            # first call on a fresh executable traces + compiles (a compile-
            # cache miss); subsequent calls reuse the jitted executable
            fresh = self._executable_fresh
            if fresh:
                try:
                    from trino_tpu.obs.devprofiler import DEVICE_PROFILER

                    DEVICE_PROFILER.compile_started()
                except Exception:  # noqa: BLE001 — accounting only
                    pass
            with tracing.span(
                    "device/compile" if fresh else "device/execute") as sp:
                t0 = time.perf_counter()
                out_arrays, error_flags = self.fn(self.input_arrays)
                device_s = time.perf_counter() - t0
                sp.set("device_seconds", round(device_s, 6))
                sp.set("staged_rows", int(sum(self.scan_rows.values())))
            # kernel/compile ledger (obs/devprofiler.py): with
            # device_profiling on, bracket the post-dispatch wait so
            # device seconds are measured, not dispatch wall
            props = getattr(self.session, "properties", None) or {}
            sync = bool(props.get("device_profiling", False))
            # estimated (no-sync) mode: a fresh run's wall is compile, not
            # kernel time — estimate the body's device share as 0 there
            body_device_s = 0.0 if fresh else device_s
            estimated = True
            if sync:
                t_sync = time.perf_counter()
                try:
                    jax.block_until_ready(out_arrays)
                except Exception:  # noqa: BLE001 — profiling never fails
                    pass
                body_device_s = time.perf_counter() - t_sync
                estimated = False
            self._profile_run(fresh, device_s, body_device_s, estimated)
            (M.COMPILE_CACHE_MISSES if fresh else M.COMPILE_CACHE_HITS).inc()
            self._executable_fresh = False
            # a fresh run's wall is dominated by trace+XLA-compile; charge
            # it to compile seconds so device_seconds stays a steady-state
            # throughput signal (mirrors the device/compile span split)
            (M.COMPILE_SECONDS if fresh else M.DEVICE_SECONDS).inc(device_s)
            codes = self.error_codes_cell[0]
            # capacity overflows first: any other flag fired on the same run
            # may be an artifact of the truncated join output
            grown = stats.grow_overflowed_hints(self.capacity_hints, codes, error_flags)
            if grown is not None:
                self.capacity_hints = grown
                self.recompiles += 1
                self._jit()
                continue
            raise_query_errors(codes, error_flags)
            return unflatten_page(self.out_spec_cell[0], out_arrays)
        raise QueryError("capacity still exceeded after recompiles (join or exchange bucket)")
