"""Plan executor: fully traceable array program over device Pages.

Reference: the worker execution engine — ``LocalExecutionPlanner.java:532``
turning plan nodes into operator pipelines + ``Driver.java:372``'s page loop.
TPU-first difference (SURVEY.md §7.1): no page-at-a-time pull loop — each
plan node is a whole-column array transformation with *static shapes*:
filters keep selection masks instead of compacting, aggregations emit
padded outputs with a live-group prefix, sorts move dead rows last. Because
every step is shape-static and host-sync-free, the entire query body can be
traced once and compiled by XLA (``exec.compiled``), and the same recursion
runs under ``shard_map`` for multi-chip SPMD (``parallel.spmd``).

Data-dependent runtime errors (division by zero, multi-row scalar subquery)
are collected as boolean flags and checked once after execution — the
deferred-error contract of ops/expr_lower.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import types
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.data.page import Column, Page
from trino_tpu.exec import memory as _mem
from trino_tpu.exec.operator_stats import OperatorStats
from trino_tpu.exec.page_tree import (
    StaticSpec, attach_dictionaries, flatten_page, static_spec,
    unflatten_page,
)
from trino_tpu.obs import metrics as M
from trino_tpu.obs import trace as tracing
from trino_tpu.obs.devprofiler import (
    charge_to, count_charged, host_read, host_read_all, merge_platforms,
    new_kernel_row)
from trino_tpu.ops import aggregate as agg_ops
from trino_tpu.ops import expr_lower as L
from trino_tpu.ops import fused_join as fused_ops
from trino_tpu.ops import groupby as gb
from trino_tpu.ops import join as join_ops
from trino_tpu.ops import ranks as ranks_ops
from trino_tpu.ops import scans
from trino_tpu.ops import segments as seg
from trino_tpu.ops import sort as sort_ops
from trino_tpu.sql import ir
from trino_tpu.sql.planner import plan as P


class QueryError(RuntimeError):
    def __init__(self, message: str, code: str = ""):
        super().__init__(message)
        self.code = code


def raise_query_errors(codes, flags):
    """Raise the first deferred runtime error whose flag fired. Shared by
    the eager, compiled, and SPMD paths."""
    for code, flag in zip(codes, flags):
        if bool(host_read(flag, "error-flags").any()):
            raise QueryError(code.replace("_", " ").capitalize(), code=code)


def _col_from_lowered(t: T.Type, lv: L.LoweredVal) -> Column:
    nulls = None if lv.valid is None else ~lv.valid
    children = None
    if lv.children is not None:
        children = [
            _col_from_lowered(ct, k) for ct, k in zip(T.type_children(t), lv.children)
        ]
        return Column(t, lv.vals, nulls, None, children=children)
    # a static |value| bound proven by the lowering becomes the column's
    # vrange, so downstream consumers (sum's int64-vs-limb choice, physical
    # narrowing) keep their fast paths for projected expressions
    vrange = (-lv.bound, lv.bound) if lv.bound is not None and lv.hi is None else None
    return Column(t, lv.vals, nulls, lv.dictionary, vrange, hi=lv.hi)


def operator_kind(node: P.PlanNode) -> str:
    """The operator name of a plan node, as the stats, the kernel ledger
    and the ``operator/<kind>`` spans spell it."""
    return type(node).__name__.replace("Node", "")


def page_platform(page: Page) -> str:
    """The device platform holding ``page``'s first column (``"host"`` for
    a numpy array): the kernel ledger's proof of where a launch ran."""
    if not page.columns:
        return ""
    values = page.columns[0].values
    if not isinstance(values, jax.Array):
        return "host"
    return next(iter(values.devices())).platform


def _col_to_lowered(c: Column) -> join_ops.Lowered:
    return (c.values, None if c.nulls is None else ~c.nulls)


def _key_lowereds(c: Column, force_two_limb: bool = False) -> List[join_ops.Lowered]:
    """Key operands for grouping/joining/sorting one column. Two-limb long
    decimals (Column.hi) contribute TWO lexicographic key operands:
    (hi, lo-with-flipped-sign-bit) — the flip makes the unsigned low word
    order correctly as a signed int64, and equality is flip-invariant, so
    the same pair serves hash, merge, and order comparisons (reference:
    Int128.compareTo = compare hi, then unsigned lo). ``force_two_limb``
    expands a single-limb column the same way (sign-extended hi) so the
    two sides of a join stay symmetric."""
    if c.hi is None and not force_two_limb:
        return [_col_to_lowered(c)]
    valid = None if c.nulls is None else ~c.nulls
    lo = c.values.astype(jnp.int64)
    hi = c.hi if c.hi is not None else (lo >> 63)
    return [(hi, valid), (lo ^ jnp.int64(-(2**63)), valid)]


def scan_constraint_with(node: "P.TableScanNode", dyn_domains):
    """Effective TupleDomain for a scan: static pushdown ∩ available
    dynamic-filter domains (reference: DynamicFilter.getCurrentPredicate).
    Shared by the eager executor and the staged tiers (compiled/SPMD)."""
    from trino_tpu.connector.predicate import TupleDomain

    td = node.constraint
    for join_id, key_idx, column in node.dynamic_filters or ():
        dom = dyn_domains.get((join_id, key_idx))
        if dom is None:
            continue
        extra = TupleDomain({column: dom})
        td = extra if td is None else td.intersect(extra)
    return td


def dynamic_domain_map(node, dyn_domains):
    """column -> available dynamic-filter Domain for a scan (intersecting
    when several joins filter the same column). Shared by the phase-1 host
    evaluator and the scan-time enforcer so both always agree on which rows
    survive."""
    dyn = {}
    for join_id, key_idx, column in node.dynamic_filters or ():
        dom = dyn_domains.get((join_id, key_idx))
        if dom is None or dom.is_all():
            continue
        dyn[column] = dom.intersect(dyn[column]) if column in dyn else dom
    return dyn


def apply_dynamic_domains(node, dyn_domains, datas, allow=None):
    """Engine-side enforcement of a scan's available dynamic-filter domains
    on host-side scanned data: connectors treat constraints as ADVISORY (the
    tpch generator prunes only via its monotone key), so the scan operator
    itself drops rows outside the domain before device transfer — the
    reference's ScanFilterAndProjectOperator applying
    DynamicFilter.getCurrentPredicate. Varchar domains are skipped
    (dictionary codes are page-local). ``allow(column, domain)`` restricts
    which domains apply here (the compiled tier splits strong domains —
    host row pruning cuts the device transfer — from weak ones it enforces
    on device)."""
    import dataclasses as _dc

    from trino_tpu.exec.host_eval import domain_mask

    dyn = dynamic_domain_map(node, dyn_domains)
    if allow is not None:
        dyn = {c: d for c, d in dyn.items() if allow(node, c, d)}
    if not dyn:
        return datas
    out = []
    for d in datas:
        if not d:
            out.append(d)
            continue
        n = len(next(iter(d.values())).values)
        keep = np.ones(n, dtype=bool)
        for column, dom in dyn.items():
            cd = d.get(column)
            if cd is None or cd.dictionary is not None:
                continue
            keep &= domain_mask(
                dom,
                np.asarray(cd.values),
                np.asarray(cd.nulls) if cd.nulls is not None else None,
            )
        if keep.all():
            out.append(d)
            continue
        from trino_tpu.connector.spi import column_data_take

        out.append({name: column_data_take(cd, keep) for name, cd in d.items()})
    return out


class Executor:
    """Traceable plan interpreter. ``execute_checked`` runs eagerly and
    raises deferred errors; the recursion itself (``execute``) is pure and
    jit-safe."""

    # Eager tier: host-side recursion over concrete arrays (the local path
    # and worker fragments). Traced subclasses (PreloadedExecutor,
    # SpmdExecutor) run under jax tracing where host-side syncs (stats,
    # dynamic-filter domains, spill partitioning) are impossible.
    eager_tier = True
    enable_dynamic_filtering = True  # AND-ed with the session property
    collect_stats = True  # per-operator wall/rows (traced subclasses: False)
    # Row-level dynamic-domain enforcement at the scan: host-side numpy here
    # (concrete arrays); the compiled tier stages full pages and enforces ON
    # DEVICE instead (searchsorted membership + compact ride HBM bandwidth,
    # ~40x the host's — exec/compiled.py StagingExecutor)
    apply_df_host = True

    def __init__(self, session, capacity_hints: Optional[Dict[str, int]] = None):
        self.session = session
        self.errors: List[Tuple[str, jnp.ndarray]] = []
        # M:N join output capacities by plan-node id. Eager runs compute the
        # exact total (one device sync) and record a padded power-of-two here;
        # traced runs (compiled/SPMD) require the hint to pre-exist — the
        # bucketed-recompile strategy of SURVEY.md §7.3 (dynamic shapes).
        self.capacity_hints: Dict[str, int] = capacity_hints if capacity_hints is not None else {}
        # Dynamic filtering (reference: DynamicFilterService): build-side key
        # domains by (join_id, key_index), produced when joins execute their
        # build side, consumed by probe-side scans. Eager execution only —
        # traced subclasses (PreloadedExecutor/SpmdExecutor) stage scans
        # before tracing and override the class flag (Tracers have no
        # concrete min/max).
        self.dyn_domains: Dict[Tuple[int, int], object] = {}
        # host seconds spent applying dynamic domains at scans (benchmarks
        # charge this to the query: it is join work moved off-device)
        self.df_apply_s = 0.0
        # rows materialized per scan plan-node id (EXPLAIN/pushdown tests)
        self.scan_stats: Dict[int, int] = {}
        # device-cache disposition per scan plan-node id ("hit" | "miss" |
        # "bypass"): a "hit" staged ZERO host->device bytes — callers use
        # this to keep staged-rows accounting honest (trino_tpu/devcache/)
        self.scan_cache: Dict[int, str] = {}
        # per-operator stats by plan-node id (EXPLAIN ANALYZE, task status):
        # typed OperatorStats ACCUMULATED across repeated node executions
        # (reference: OperatorContext/OperatorStats — SURVEY.md §5.1)
        self.node_stats: Dict[int, OperatorStats] = {}
        # rows a node produced on its LATEST execution — parents read their
        # children's entries to charge input_rows per invocation
        self._last_output_rows: Dict[int, int] = {}
        # stack of accumulated child wall time: operators recursively
        # execute their sources inside method(node), so per-operator wall
        # must subtract the subtree's time to be EXCLUSIVE (the reference's
        # OperatorStats semantics — summing operators then equals the query)
        self._child_wall: List[float] = [0.0]
        # (splits, scanned_rows) staged by the scan method that just ran,
        # consumed by the execute() wrapper into the scan's OperatorStats
        self._pending_scan: Dict[int, Tuple[int, int]] = {}
        # bytes a node produced on its LATEST execution — parents charge
        # input bytes per invocation (kernel-ledger input side)
        self._last_output_bytes: Dict[int, int] = {}
        # device profiler (obs/devprofiler.py): per-(node, operator)
        # kernel rollups — launches, wall vs device seconds, bytes.
        # Accumulated here, folded ONCE at task/query completion.
        self.kernel_stats: Dict[Tuple[int, str], dict] = {}
        # device-memory budget + spill decisions (exec/memory.py; reference:
        # lib/trino-memory-context + the spill FSMs). Property name mirrors
        # the reference's query_max_memory_per_node.
        from trino_tpu.exec.memory import MemoryContext

        props = (
            session.properties
            if session is not None and hasattr(session, "properties")
            else {}
        ) or {}
        self.memory = MemoryContext(props.get("query_max_device_memory"))
        if not props.get("dynamic_filtering_enabled", True):
            self.enable_dynamic_filtering = False
        self.spill_enabled = bool(props.get("spill_enabled", True))
        # device_profiling session property: when on, each dispatch is
        # block_until_ready-bracketed so device seconds are measured;
        # when off (default) NO sync is added — device seconds are
        # estimated from wall and only zero-sync counting happens
        self.profile_sync = bool(props.get("device_profiling", False))

    # ------------------------------------------------------------------ api
    def execute_checked(self, node: P.PlanNode) -> Page:
        page = self.execute(node)
        self.raise_errors()
        return page

    def raise_errors(self):
        raise_query_errors([c for c, _ in self.errors], [f for _, f in self.errors])

    def execute(self, node: P.PlanNode, **how) -> Page:
        """Run ``node`` (and, recursively, its sources) to a page. ``how``
        is handed to the node's own method: what a parent that has seen
        the plan shape asks of this child (a Compact asking its join to
        squeeze the match first, ``_exec_CompactNode``)."""
        method = getattr(self, f"_exec_{type(node).__name__}", None)
        if method is None:
            raise NotImplementedError(f"executor: {type(node).__name__}")
        if not self.collect_stats:
            return method(node, **how)
        # per-operator profiling, always on in the eager tier (reference:
        # OperatorContext/OperatorStats via OperationTimer — SURVEY.md §5.1)
        kind = operator_kind(node)
        # what the node's blocking device->host reads and compiles are
        # charged to while it executes (obs/devprofiler.py host_read), its
        # children charging their own rows
        ks = self._kernel_row(node)
        self._child_wall.append(0.0)
        # kernel ledger: device seconds per dispatch. profile_sync ON:
        # eager jax dispatch returns before the math finishes — the
        # block_until_ready wait IS the device time, and excl_wall
        # (dispatch + host glue) minus it is the overhead. OFF: zero-sync
        # estimate — device ≈ exclusive wall, flagged.
        sync_s = 0.0
        t0 = time.perf_counter()
        try:
            with tracing.span(f"operator/{kind}",
                              planNodeId=node.id) as sp, charge_to(ks):
                page = method(node, **how)
                if self.profile_sync:
                    t_sync = time.perf_counter()
                    try:
                        jax.block_until_ready(
                            [c.values for c in page.columns])
                    except Exception:  # noqa: BLE001 — never fails work
                        pass
                    sync_s = time.perf_counter() - t_sync
                # live rows, not padded slots: a blocking read of the
                # selection mask, inside the clock and the row of the node
                # that produced the page (the adaptive planner and EXPLAIN
                # ANALYZE read output_rows)
                live = page.live_count("operator-stats")
                sp.set("rows", live)
        finally:
            # keep the stack balanced on error paths: the parent is still
            # charged the subtree's time. The measured sync wait is
            # elapsed time inside THIS node's subtree too, so the parent's
            # exclusive wall stays exclusive of it
            elapsed = time.perf_counter() - t0
            wall = elapsed - sync_s
            child_wall = self._child_wall.pop()
            self._child_wall[-1] += elapsed
        excl_wall = max(0.0, wall - child_wall)
        estimated = not self.profile_sync
        device_s = excl_wall if estimated else sync_s
        nbytes = _mem.page_bytes(page)
        st = self.node_stats.get(node.id)
        if st is None:
            st = self.node_stats[node.id] = OperatorStats(node.id, kind)
        # accumulate, never overwrite: a node re-executed (per probe batch,
        # per split) ADDS its rows/bytes/time, so rollups stay additive.
        # Wall is EXCLUSIVE (children's recursive time subtracted), so the
        # per-operator-kind metrics and rollups sum to the fragment body.
        st.wall_s += excl_wall
        st.output_rows += live
        st.output_bytes += nbytes
        st.invocations += 1
        st.peak_bytes = max(st.peak_bytes, nbytes)
        st.input_rows += sum(
            self._last_output_rows.get(s.id, 0) for s in node.sources)
        splits, scanned = self._pending_scan.pop(node.id, (0, 0))
        st.splits += splits
        st.input_rows += scanned  # scans: connector rows are the input side
        # kernel-ledger rollup: one "launch" per node execution in the
        # eager tier (each _exec_ dispatches this node's device ops).
        # Wall here is EXCLUSIVE (matches st.wall_s), with the measured
        # sync wait added back when profiling — wall − device = the
        # per-operator dispatch overhead megakernels must beat.
        in_bytes = sum(
            self._last_output_bytes.get(s.id, 0) for s in node.sources)
        kwall = excl_wall + (device_s if not estimated else 0.0)
        ks["launches"] += 1
        ks["platform"] = merge_platforms(ks["platform"], page_platform(page))
        ks["wallS"] += kwall
        ks["deviceS"] += device_s
        ks["inputBytes"] += in_bytes
        ks["outputBytes"] += nbytes
        ks["estimated"] = bool(ks["estimated"] or estimated)
        try:
            from trino_tpu.obs.devprofiler import DEVICE_PROFILER

            DEVICE_PROFILER.count_launch(kwall, device_s
                                         if not estimated else 0.0)
        except Exception:  # noqa: BLE001 — accounting never fails work
            pass
        self._last_output_bytes[node.id] = nbytes
        self._last_output_rows[node.id] = live
        # operator-output reservation rolls into the query's peak (the
        # LocalMemoryContext -> query-pool rollup, exact from static shapes)
        self.memory.observe(nbytes)
        return page

    def _kernel_row(self, node: P.PlanNode) -> dict:
        """The kernel-ledger row of this (node, operator), made on first
        use."""
        key = (node.id, operator_kind(node))
        row = self.kernel_stats.get(key)
        if row is None:
            row = self.kernel_stats[key] = new_kernel_row(
                str(node.id), key[1], "eager",
                estimated=not self.profile_sync)
        return row

    def charging(self, node: P.PlanNode):
        """Charge this thread's device->host reads to ``node``'s kernel
        row: for work on a page once the node that produced it has
        returned (result rows on the coordinator)."""
        return charge_to(self._kernel_row(node))

    def _narrowed_or_flag(self, col: Column, sel=None) -> Column:
        """Degrade a two-limb long-decimal column to its low word for
        consumers without limb support (window args, map-building aggregate
        keys, ...): LIVE rows whose value does not fit int64 raise the
        deferred DECIMAL_OVERFLOW error — exactly the pre-limb-storage
        contract, so in-range data keeps working and out-of-range data
        fails loudly instead of silently truncating."""
        if col.hi is None:
            return col
        fits = col.hi == (col.values.astype(jnp.int64) >> 63)
        if col.nulls is not None:
            fits = fits | col.nulls
        if sel is not None:
            fits = fits | ~sel
        self.errors.append((L.DECIMAL_OVERFLOW, jnp.any(~fits)))
        return Column(col.type, col.values, col.nulls, col.dictionary)

    def _narrow_lowered_or_flag(self, arg, hi_l, sel_l=None):
        """The layout-space analog of _narrowed_or_flag for payload pairs."""
        if hi_l is None:
            return arg
        vals_l, valid_l = arg
        fits = hi_l == (vals_l.astype(jnp.int64) >> 63)
        if valid_l is not None:
            fits = fits | ~valid_l
        if sel_l is not None:
            fits = fits | ~sel_l
        self.errors.append((L.DECIMAL_OVERFLOW, jnp.any(~fits)))
        return arg

    def _lower(self, e: ir.Expr, page: Page) -> L.LoweredVal:
        ctx = L.LowerCtx(page.columns, page.num_rows, page.sel)
        out = L.lower(e, ctx)
        for code, flag in ctx.errors:
            self.errors.append((code, flag))
        return out

    # ----------------------------------------------------------------- scan
    def scan_constraint(self, node: P.TableScanNode):
        return scan_constraint_with(node, self.dyn_domains)

    def _host_applied_domains(self, node: P.TableScanNode) -> Dict:
        """The dynamic domains this executor will physically apply at the
        scan (the host-pruning subset) — part of the cache signature: two
        executors with the same constraint but different applied sets
        stage DIFFERENT pages (trino_tpu/devcache/keys.py)."""
        if not self.apply_df_host:
            return {}
        dyn = dynamic_domain_map(node, self.dyn_domains)
        allow = getattr(self, "df_host_allow", None)
        if allow is not None:
            dyn = {c: d for c, d in dyn.items() if allow(node, c, d)}
        return dyn

    def _exec_TableScanNode(self, node: P.TableScanNode) -> Page:
        from trino_tpu import devcache
        from trino_tpu.exec import staging

        conn = self.session.catalogs[node.catalog]
        constraint = self.scan_constraint(node)
        applied = self._host_applied_domains(node)

        def load():
            # adaptive split sizing: fan big tables out over the staging
            # pool (pushdown handles stay single-split — the guard is
            # inside target_split_count)
            target = staging.target_split_count(
                self.session, conn, node.schema, node.table,
                handle=node.table_handle)
            splits = conn.get_splits(
                node.schema, node.table, target, constraint=constraint,
                handle=node.table_handle)
            prune = None
            if self.apply_df_host:
                allow = getattr(self, "df_host_allow", None)

                def prune(datas):
                    return apply_dynamic_domains(
                        node, self.dyn_domains, datas, allow=allow)

            page, scanned, prof = staging.staged_scan_page(
                self.session, node, conn, splits, constraint,
                prune=prune, applied_domains=applied)
            if self.apply_df_host:
                # CUMULATIVE host domain-application seconds across the
                # scan threads (StageProfile.prune_s): under a parallel
                # fan-out this is CPU-seconds of host work, which can
                # exceed the staging wall — the honest measure of "work
                # a run repeats", but not a wall clock. The PR 7
                # accounting identity (STAGING_SECONDS charges exactly
                # phase1_s + df_apply_s) holds by construction either
                # way; at parallelism 1 it equals the old serial wall.
                self.df_apply_s += prof.prune_s
            return page, scanned, _mem.page_bytes(page), len(splits)

        ent, disposition = devcache.cached_stage(
            self.session, node, constraint, applied, "table", load)
        self.scan_cache[node.id] = disposition
        self.scan_stats[node.id] = ent.rows
        self._pending_scan[node.id] = (ent.splits, ent.rows)
        return ent.value

    def _exec_ValuesNode(self, node: P.ValuesNode) -> Page:
        cols = [
            Column.from_python(t, [r[i] for r in node.rows])
            for i, t in enumerate(node.types)
        ]
        # identical on every device under SPMD -> replicated
        if not cols:
            # zero-column single row (SELECT without FROM)
            return Page(
                [Column(T.BIGINT, jnp.zeros(len(node.rows), dtype=jnp.int64))],
                replicated=True,
            )
        return Page(cols, replicated=True)

    # -------------------------------------------------------------- set ops
    def _exec_UnionNode(self, node: P.UnionNode) -> Page:
        """UNION ALL: row-wise page concatenation (static shapes: total =
        sum of branch capacities; dead rows stay dead)."""
        return Page.concat_all([self.execute(s) for s in node.sources_])

    def _exec_SetOpNode(self, node: P.SetOpNode) -> Page:
        left = self.execute(node.left)
        right = self.execute(node.right)
        return self.set_op_pages(node, left, right)

    def set_op_pages(self, node: P.SetOpNode, left: Page, right: Page) -> Page:
        """INTERSECT/EXCEPT DISTINCT via the grouping machinery: concat both
        sides with a side tag, group by ALL columns (grouping equality makes
        NULLs compare equal — the set-operation semantics), then keep groups
        by per-side presence counts. Reference: SetOperationNodeTranslator's
        aggregation-based lowering."""
        both = Page.concat_pages(left, right)
        n_l = left.num_rows
        side_right = jnp.arange(both.num_rows) >= n_l
        return self._set_op_grouped(node, both, side_right)

    def _set_op_grouped(self, node: P.SetOpNode, both: Page, side_right) -> Page:
        """The grouping half of a set operation over a combined page with an
        explicit per-row side tag — reused by the SPMD tier after a
        whole-row hash exchange (where positional tagging is impossible)."""
        n = both.num_rows
        layout, out_sel, (side_right_l,), sel_l = self.group_structure(
            list(range(both.channel_count)), both, [side_right]
        )
        l_cnt = seg.seg_sum(layout, (~side_right_l).astype(jnp.int64), sel_l, jnp.int64)
        r_cnt = seg.seg_sum(layout, side_right_l.astype(jnp.int64), sel_l, jnp.int64)
        if node.op == "intersect":
            keep = (l_cnt > 0) & (r_cnt > 0)
        else:  # except
            keep = (l_cnt > 0) & (r_cnt == 0)
        out_cols = self._gathered_key_cols(
            both, list(range(both.channel_count)), layout
        )
        return Page(out_cols, out_sel & keep, both.replicated)

    # --------------------------------------------------------------- filter
    def _exec_FilterNode(self, node: P.FilterNode) -> Page:
        page = self.execute(node.source)
        lv = self._lower(node.predicate, page)
        passed = lv.vals if lv.valid is None else (lv.vals & lv.valid)
        sel = passed if page.sel is None else (page.sel & passed)
        return Page(page.columns, sel, page.replicated)

    def _exec_CompactNode(self, node: P.CompactNode) -> Page:
        """Squeeze live rows into a smaller static-capacity page
        (``compact_to``: live rows first, original order kept). Skipped
        when it cannot help (no selection mask, or capacity >= the page's
        rows — e.g. an SPMD shard already smaller than the global
        estimate). Overflow raises CAPACITY_EXCEEDED:cmp:<id> for the
        recompile-growth loop.

        Directly on a join that compacts its match (the shape
        optimizer.insert_compactions plans for q3's two joins) the node
        hands the join its own id: the join squeezes the probe's match to
        this node's capacity BEFORE it gathers a build payload
        (``compacted_lookup_join``), and the page that comes back is
        already the one ``compact_to`` would make, so nothing is left to
        do here."""
        if self._join_compacts_match(node.source):
            page = self.execute(node.source, compact_into=node)
        else:
            page = self.execute(node.source)
        if page.sel is None:
            return page
        capacity = self.hint_capacity(f"cmp:{node.id}", page.sel.astype(jnp.int32))
        return self.compact_to(page, capacity, f"cmp:{node.id}")

    def _join_compacts_match(self, source: P.PlanNode) -> bool:
        """Whether a Compact on ``source`` is run by the join itself. The
        plan shape decides (no property); a tier whose lookup join is not
        ``Executor.lookup_join`` overrides this (parallel/spmd.py)."""
        return P.compacts_its_match(source)

    def compact_to(self, page: Page, capacity: int, key: str) -> Page:
        """Squeeze live rows into a ``capacity``-slot page: the positions
        of the first ``capacity`` live rows from prefix counts of the mask
        (``ranks.true_positions``: no sort, nothing n-sized touched at
        random), then ONE batched row-gather per dtype group at them —
        gathering only the KEPT rows (capacity), not all n. Original row
        order is kept (positions ascend); slots past the live count hold
        row 0's values under a False ``sel``. Overflow raises
        CAPACITY_EXCEEDED:<key> for the recompile-growth loop. Shared by
        CompactNode and the device-side dynamic-filter scans; a lookup
        join under a CompactNode makes the same page itself
        (``compacted_lookup_join``)."""

        n = page.num_rows
        if page.sel is None or capacity >= n:
            return page
        if any(c.type.is_nested for c in page.columns):
            # device row-gathers cannot re-flatten variable-length children
            # (data-dependent shapes); keep the selection mask instead —
            # semantically identical, just uncompacted
            return page
        idx, sel = self._kept_positions(
            page.sel, jnp.sum(page.sel.astype(jnp.int32)), capacity, key)
        if self.eager_tier:  # a traced tier would count its trace, not its runs
            count_charged("prefixCompactions")
        cols, _ = self._columns_at(page.columns, idx)
        return Page(cols, sel, page.replicated, live_prefix=True)

    def _kept_positions(self, live, total, capacity: int, key: str):
        """(positions of the first ``capacity`` of ``live``'s ``total``
        set rows, the squeezed page's mask); more than ``capacity`` of
        them flags CAPACITY_EXCEEDED:<key>."""
        self.errors.append((f"CAPACITY_EXCEEDED:{key}", total > capacity))
        idx = ranks_ops.true_positions(live, capacity)
        kept = jnp.arange(capacity, dtype=jnp.int32) < jnp.minimum(total, capacity)
        return idx, kept

    @staticmethod
    def _columns_at(columns, idx, extra=()):
        """(``columns`` at the ASCENDING row ids ``idx``, ``extra`` arrays
        at them): values, null masks and hi limbs in ONE batched
        row-gather per dtype group."""
        arrays = list(extra)
        for c in columns:
            arrays.append(c.values)
            if c.nulls is not None:
                arrays.append(c.nulls)
            if c.hi is not None:
                arrays.append(c.hi)
        gathered = ranks_ops.batched_gather(arrays, idx)
        cols = []
        i = len(extra)
        for c in columns:
            v = gathered[i]
            i += 1
            nulls = None
            if c.nulls is not None:
                nulls = gathered[i]
                i += 1
            chi = None
            if c.hi is not None:
                chi = gathered[i]
                i += 1
            # stable: live rows keep their relative order -> ascending holds
            cols.append(Column(c.type, v, nulls, c.dictionary, c.vrange,
                               ascending=c.ascending, hi=chi))
        return cols, gathered[:len(extra)]

    def _exec_ProjectNode(self, node: P.ProjectNode) -> Page:
        page = self.execute(node.source)
        cols = []
        for e in node.expressions:
            if isinstance(e, ir.ColumnRef):
                # pass-through: reuse the column wholesale (keeps vrange,
                # dictionary, and sort-order metadata; skips re-lowering)
                cols.append(page.columns[e.index])
                continue
            lv = self._lower(e, page)
            cols.append(_col_from_lowered(e.type, lv))
        return Page(cols, page.sel, page.replicated,
                    live_prefix=page.live_prefix)

    # -------------------------------------------------------------- unnest
    def _exec_UnnestNode(self, node: P.UnnestNode) -> Page:
        page = self.execute(node.source)
        return self.unnest_page(node, page)

    def unnest_page(self, node: P.UnnestNode, page: Page) -> Page:
        """Static-shape UNNEST expansion (plan.py UnnestNode docstring).

        Output capacity = total flat element count across the unnested
        expressions (the exact row count for the single-array case; an upper
        bound when zipping several). Per-output-slot parent rows come from
        one searchsorted over the output offsets; every produced column is
        either a parent-row gather (replicated channels) or a flat-child
        gather at ``child_offset[parent] + position`` (unnested channels)."""
        from trino_tpu.ops import array_ops as A

        n = page.num_rows
        lows = [self._lower(e, page) for e in node.unnest_exprs]
        for lv in lows:
            if lv.children is None:
                raise NotImplementedError("UNNEST argument must be array/map-typed")
        for c in node.replicate_channels:
            if page.columns[c].type.is_nested:
                raise NotImplementedError(
                    "replicating an array/map column through UNNEST "
                    "(project it before/after instead)"
                )
        raw_lens = [lv.vals.astype(jnp.int32) for lv in lows]
        eff_lens = [
            jnp.where(lv.valid, ln, 0) if lv.valid is not None else ln
            for lv, ln in zip(lows, raw_lens)
        ]
        out_len = eff_lens[0]
        for ln in eff_lens[1:]:
            out_len = jnp.maximum(out_len, ln)
        if page.sel is not None:
            out_len = jnp.where(page.sel, out_len, 0)
        out_offsets = A.offsets_from_lengths(out_len)
        capacity = max(
            1, sum(int(lv.children[0].vals.shape[0]) for lv in lows)
        )
        slot = jnp.arange(capacity, dtype=jnp.int32)
        rowid_raw = jnp.searchsorted(out_offsets, slot, side="right").astype(jnp.int32) - 1
        rowid = jnp.clip(rowid_raw, 0, n - 1)
        pos = slot - out_offsets[rowid]  # 0-based position within the parent row
        sel = slot < out_offsets[-1]
        cols: List[Column] = []
        for ci in node.replicate_channels:
            c = page.columns[ci]
            cols.append(
                Column(
                    c.type,
                    c.values[rowid],
                    c.nulls[rowid] if c.nulls is not None else None,
                    c.dictionary,
                    c.vrange,
                )
            )
        child_types = iter(node.output_types[len(node.replicate_channels):])
        for lv, raw_ln in zip(lows, raw_lens):
            child_off = A.offsets_from_lengths(raw_ln)
            in_range = pos < raw_ln[rowid]
            if lv.valid is not None:
                in_range = in_range & lv.valid[rowid]
            for child in lv.children:
                flat = child.vals
                flat_n = int(flat.shape[0])
                safe = flat if flat_n else jnp.zeros((1,), flat.dtype)
                idx = jnp.clip(child_off[rowid] + pos, 0, max(flat_n - 1, 0))
                vals = safe[idx]
                valid = in_range
                if child.valid is not None:
                    cvalid = child.valid if flat_n else jnp.zeros((1,), bool)
                    valid = valid & cvalid[idx]
                cols.append(Column(next(child_types), vals, ~valid, child.dictionary))
        if node.ordinality:
            cols.append(Column(T.BIGINT, (pos + 1).astype(jnp.int64)))
        return Page(cols, sel)

    # ---------------------------------------------------------- aggregation
    def _exec_AggregationNode(self, node: P.AggregationNode) -> Page:
        page = self.execute(node.source)
        if node.step == "partial":
            return self.aggregate_partial(node, page)
        if node.step == "final":
            return self.aggregate_final(node, page)
        if node.distribution == "colocated":
            # finished where its table is scanned: nothing was cut under it
            count_charged("colocatedAggs")
        return self.aggregate_page(node, page)

    def aggregate_partial(self, node: P.AggregationNode, page: Page) -> Page:
        """Partial aggregation: emit group keys + accumulator-state columns
        (reference: HashAggregationOperator(PARTIAL) shipping
        AccumulatorCompiler intermediate states through an exchange).
        State column types follow plan._acc_types so the page can cross the
        wire (serde needs faithful dtypes)."""
        fused = self._as_one_program("aggregate_partial", node, page)
        if fused is not None:
            return fused
        payload_arrays, slots = self._agg_payloads(node.aggregates, page.columns)
        layout, part_sel, payloads_l, sel_l = self.group_structure(
            node.group_channels, page, payload_arrays
        )
        out_cols: List[Column] = []
        if node.group_channels:
            out_cols.extend(
                self._gathered_key_cols(page, node.group_channels, layout)
            )
        src_types = node.source.output_types
        for call, slot in zip(node.aggregates, slots):
            s1 = slot[0] if slot is not None else None
            hi_l = self._slot_hi(payloads_l, s1)
            arg1 = self._slot_arg(payloads_l, s1)
            if hi_l is not None and call.function not in ("sum", "count"):
                arg1 = self._narrow_lowered_or_flag(arg1, hi_l, sel_l)
                hi_l = None
            states = self._partial_states(
                call, page, layout, arg1, sel_l, hi_l=hi_l,
            )
            state_types = P._acc_types(call, src_types)
            for (sv, valid), st in zip(states, state_types):
                out_cols.append(
                    Column(st, sv, None if valid is None else ~valid, None)
                )
        return Page(out_cols, part_sel, page.replicated)

    def aggregate_final(self, node: P.AggregationNode, page: Page) -> Page:
        """Final aggregation over gathered partial-state pages."""
        fused = self._as_one_program("aggregate_final", node, page)
        if fused is not None:
            return fused
        k = len(node.group_channels)
        # state columns ride the grouping sort as payloads (layout space)
        payload_arrays: List = []
        state_slots: List = []
        for c in page.columns[k:]:
            if c.hi is not None:
                raise NotImplementedError(
                    "distributed final aggregation over long-decimal states "
                    "beyond int64 (single-process paths support them)"
                )
            vi = len(payload_arrays)
            payload_arrays.append(c.values)
            hv = c.nulls is not None
            if hv:
                payload_arrays.append(~c.nulls)
            state_slots.append((vi, hv, None))
        layout, out_sel, payloads_l, sel_l = self.group_structure(
            list(range(k)), page, payload_arrays
        )
        out_cols: List[Column] = []
        if k:
            out_cols.extend(
                self._gathered_key_cols(page, list(range(k)), layout)
            )
        ci = 0
        for call in node.aggregates:
            # state layout must match what aggregate_partial emitted
            n_states = P._acc_state_count(call)
            states = [
                self._slot_arg(payloads_l, state_slots[ci + j]) for j in range(n_states)
            ]
            ci += n_states
            out_cols.append(self._combine_state(call, states, sel_l, layout))
        return Page(out_cols, out_sel, page.replicated)

    # aggregate functions whose partial STATES merge into states of the
    # same dtypes with plain sum/min/max reductions — the set the streaming
    # consumer's intermediate fold supports (reference:
    # AggregationNode.Step.INTERMEDIATE)
    MERGEABLE_STATE_FNS = {"count", "sum", "avg", "min", "max", "count_if"}

    def aggregate_intermediate(self, node: P.AggregationNode, page: Page) -> Page:
        """Merge partial-state pages into a COMBINED partial-state page of
        the same schema (reference: AggregationNode.Step.INTERMEDIATE —
        the reference inserts these between partial and final exchanges;
        here they are the fold step of the streaming consumer loop: state
        pages accumulate per arriving micro-batch, memory stays
        O(groups + batch) no matter how much the producer emits)."""
        fused = self._as_one_program("aggregate_intermediate", node, page)
        if fused is not None:
            return fused
        k = len(node.group_channels)
        payload_arrays: List = []
        state_slots: List = []
        for c in page.columns[k:]:
            if c.hi is not None:
                raise NotImplementedError(
                    "intermediate merge over long-decimal two-limb states")
            vi = len(payload_arrays)
            payload_arrays.append(c.values)
            hv = c.nulls is not None
            if hv:
                payload_arrays.append(~c.nulls)
            state_slots.append((vi, hv, None))
        layout, out_sel, payloads_l, sel_l = self.group_structure(
            list(range(k)), page, payload_arrays
        )
        out_cols: List[Column] = []
        if k:
            out_cols.extend(
                self._gathered_key_cols(page, list(range(k)), layout)
            )
        ci = 0
        for call in node.aggregates:
            n_states = P._acc_state_count(call)
            states = [
                self._slot_arg(payloads_l, state_slots[ci + j])
                for j in range(n_states)
            ]
            types = [page.columns[k + ci + j].type for j in range(n_states)]
            ci += n_states
            fn = call.function
            if fn not in self.MERGEABLE_STATE_FNS or call.distinct:
                raise NotImplementedError(f"intermediate merge of {fn}")
            if fn in ("count", "count_if"):
                merged = [agg_ops.agg_sum(layout, states[0], sel_l,
                                          np.dtype(np.int64))]
            elif fn == "sum" and n_states == 2:
                # long-decimal running sum: (lo, hi) limb-pair states merge
                # through the same exact int128 grouped sum the partial used
                lo_vals, lo_valid = states[0]
                hi_vals, _ = states[1]
                (m_hi, m_lo), nonempty = agg_ops.agg_sum_128(
                    layout, lo_vals, hi_vals, lo_valid, sel_l)
                merged = [(m_lo, nonempty), (m_hi, None)]
            elif fn == "sum":
                merged = [agg_ops.agg_sum(layout, states[0], sel_l,
                                          types[0].np_dtype)]
            elif fn == "avg":
                merged = [
                    agg_ops.agg_sum(layout, states[0], sel_l, types[0].np_dtype),
                    agg_ops.agg_sum(layout, states[1], sel_l, np.dtype(np.int64)),
                ]
            elif fn == "min":
                merged = [agg_ops.agg_min(layout, states[0], sel_l)]
            else:  # max
                merged = [agg_ops.agg_max(layout, states[0], sel_l)]
            for (sv, valid), st in zip(merged, types):
                out_cols.append(
                    Column(st, sv, None if valid is None else ~valid, None)
                )
        return Page(out_cols, out_sel, page.replicated)

    def _partial_states(self, call: P.AggregateCall, page, layout, arg_l, sel_l,
                        hi_l=None):
        """State arrays per aggregate: [(values, valid)], layout matching
        plan._acc_types. ``arg_l``/``sel_l`` are in layout space
        (group_structure payloads)."""
        if call.distinct:
            raise NotImplementedError(
                "DISTINCT aggregates cannot be split partial/final (the "
                "planner routes them through a gather exchange instead)"
            )
        sel = sel_l
        if call.function == "count" and call.arg_channel is None:
            v, _ = agg_ops.agg_count_star(layout, sel)
            return [(v, None)]
        arg = arg_l
        if call.function == "count":
            v, _ = agg_ops.agg_count(layout, arg, sel)
            return [(v, None)]
        if call.function == "sum":
            if P._is_long_decimal(call.output_type):
                # two-limb running state (plan._acc_types): exact across the
                # partial/final split for the full p38 range
                vals_l, valid_l = arg
                (s_hi, s_lo), nonempty = agg_ops.agg_sum_128(
                    layout, vals_l, hi_l, valid_l, sel
                )
                return [(s_lo, nonempty), (s_hi, None)]
            return [agg_ops.agg_sum(layout, arg, sel, call.output_type.np_dtype)]
        if call.function == "avg":
            base = (
                call.output_type.np_dtype
                if call.output_type.is_decimal
                else np.dtype(np.float64)
            )
            s, s_valid = agg_ops.agg_sum(layout, arg, sel, base)
            cnt, _ = agg_ops.agg_count(layout, arg, sel)
            return [(s, s_valid), (cnt, None)]
        if call.function == "min":
            return [agg_ops.agg_min(layout, arg, sel)]
        if call.function == "max":
            return [agg_ops.agg_max(layout, arg, sel)]
        if call.function in P._VAR_FAMILY:
            t = page.columns[call.arg_channel].type
            cnt, mean, m2 = agg_ops.var_states(
                layout, arg, sel, t.scale if t.is_decimal else 0
            )
            return [(cnt, None), (mean, None), (m2, None)]
        if call.function == "approx_percentile":
            from trino_tpu.ops import hll

            vals_l, valid_l = arg
            m_l = valid_l if sel is None else (
                sel if valid_l is None else (valid_l & sel))
            return hll.percentile_states(layout, vals_l, m_l)
        if call.function in ("bool_and", "bool_or"):
            fn = agg_ops.agg_min if call.function == "bool_and" else agg_ops.agg_max
            v, valid = fn(layout, arg, sel)
            return [(v.astype(bool), valid)]
        if call.function == "count_if":
            vals_l, valid_l = arg
            m = vals_l if valid_l is None else (vals_l & valid_l)
            v, _ = agg_ops.agg_count_star(layout, m if sel is None else m & sel)
            return [(v, None)]
        raise NotImplementedError(call.function)

    def _combine_state(self, call: P.AggregateCall, states, sel, layout) -> Column:
        """``states``: per-state (values, valid) pairs in layout space; sel
        likewise (see group_structure)."""
        if call.function == "count":
            v, _ = agg_ops.agg_sum(layout, states[0], sel, np.dtype(np.int64))
            return Column(T.BIGINT, v, None, None)
        if call.function == "sum":
            if P._is_long_decimal(call.output_type):
                lo_v, lo_valid = states[0]
                hi_v, _ = states[1]
                (s_hi, s_lo), nonempty = agg_ops.agg_sum_128(
                    layout, lo_v, hi_v, lo_valid, sel
                )
                return Column(call.output_type, s_lo, ~nonempty, None, hi=s_hi)
            v, valid = agg_ops.agg_sum(
                layout, states[0], sel, call.output_type.np_dtype
            )
            return Column(call.output_type, v, None if valid is None else ~valid, None)
        if call.function == "avg":
            base = (
                call.output_type.np_dtype
                if call.output_type.is_decimal
                else np.dtype(np.float64)
            )
            s, _sv = agg_ops.agg_sum(layout, states[0], sel, base)
            cnt, _ = agg_ops.agg_sum(layout, states[1], sel, np.dtype(np.int64))
            v, valid = agg_ops.finish_avg(s, cnt, call.output_type)
            return Column(call.output_type, v, None if valid is None else ~valid, None)
        if call.function == "min":
            v, valid = agg_ops.agg_min(layout, states[0], sel)
            return Column(call.output_type, v, None if valid is None else ~valid, None)
        if call.function == "max":
            v, valid = agg_ops.agg_max(layout, states[0], sel)
            return Column(call.output_type, v, None if valid is None else ~valid, None)
        if call.function in P._VAR_FAMILY:
            cnt_i, m = states[0]
            if sel is not None:
                m = sel if m is None else (m & sel)
            cnt, mean, m2 = agg_ops.combine_var_states(
                layout, cnt_i, states[1][0], states[2][0], m
            )
            v, valid = agg_ops.finish_var(cnt, mean, m2, call.function)
            return Column(call.output_type, v, None if valid is None else ~valid, None)
        if call.function == "approx_percentile":
            from trino_tpu.ops import hll

            cnt_state = states[-1]
            if sel is not None:
                cv, cm = cnt_state
                cnt_state = (jnp.where(sel, cv, jnp.zeros((), cv.dtype)), cm)
            v, valid = hll.percentile_merge(
                layout, states[:-1], cnt_state, call.param)
            return Column(call.output_type, v, None if valid is None else ~valid, None)
        if call.function in ("bool_and", "bool_or"):
            fn = agg_ops.agg_min if call.function == "bool_and" else agg_ops.agg_max
            v, valid = fn(layout, states[0], sel)
            return Column(T.BOOLEAN, v.astype(bool),
                          None if valid is None else ~valid, None)
        if call.function == "count_if":
            v, _ = agg_ops.agg_sum(layout, states[0], sel, np.dtype(np.int64))
            return Column(T.BIGINT, v, None, None)
        raise NotImplementedError(call.function)

    def group_structure(
        self, group_channels: List[int], page: Page, payloads=(), force_sort=False,
        in_place=False,
    ):
        """(GroupLayout, out_sel, payloads_l, sel_l): group assignment.

        Two strategies (the FlatHash vs BigintGroupByHash specialization
        split in the reference, re-chosen for TPU — see ops/segments.py):
        - direct-mapped: all keys are null-free dictionary codes (or
          booleans) with a small cardinality product -> gid is a perfect
          index, NO sort, aggregation via unrolled masked reductions
          (the Q1-shape fast path; out_sel is the occupancy mask, in key
          order).
        - sort-based: exact comparison grouping for arbitrary keys
          (ops/groupby.py); capacity == input length, out_sel a prefix.

        ``payloads`` (e.g. aggregate argument columns) come back in LAYOUT
        SPACE: permuted group-contiguous by the sort for the sorted
        strategy (free payload operands of the one fused lax.sort),
        unchanged for direct layouts. ``sel_l`` is the page's selection in
        that same space (a live-prefix mask after sorting dead rows last).
        """
        n = page.num_rows
        keys = [kl for c in group_channels for kl in _key_lowereds(page.columns[c])]
        sel = page.sel
        if not group_channels:
            gids = jnp.zeros((n,), dtype=jnp.int32)
            layout = seg.direct_layout(gids, 1, sel)
            return layout, jnp.arange(1) < 1, list(payloads), sel
        direct = None if force_sort else self._direct_strides(group_channels, page)
        if direct is not None:
            strides, capacity = direct
            gids = jnp.zeros((n,), dtype=jnp.int32)
            for (vals, _), stride in zip(keys, strides):
                gids = gids + vals.astype(jnp.int32) * stride
            layout = seg.direct_layout(gids, capacity, sel)
            return layout, seg.occupancy(layout, sel), list(payloads), sel
        presorted = self._presorted_group(group_channels, page)
        if presorted is not None:
            # input already group-contiguous (single ascending key, dead
            # rows a tail): boundaries are one elementwise compare — the
            # n·log²n lax.sort, the engine's dominant cost at scale, never
            # runs. Layout space == original row order, so payloads and
            # sel pass through unchanged.
            vals = presorted
            dead = jnp.zeros((n,), bool) if sel is None else ~sel
            neq = vals[1:] != vals[:-1]
            boundary = jnp.concatenate(
                [jnp.ones((1,), bool), neq | (dead[1:] != dead[:-1])])
            if in_place:
                # the caller's aggregates are prefix scans over the runs
                # (sum, count, avg of integers): no group is listed, no
                # slot gathered; each group's row is its run's last
                layout = seg.run_layout(boundary)
                return layout, seg.run_ends(layout) & ~dead, list(payloads), sel
            gid_sorted = scans.cumsum(boundary.astype(jnp.int32)) - 1
            num_groups = jnp.sum(boundary & ~dead)
            layout = seg.sorted_layout(
                jnp.arange(n, dtype=jnp.int32), gid_sorted, num_groups)
            return layout, jnp.arange(n) < num_groups, list(payloads), sel
        order, gid_sorted, num_groups, payloads_l = gb.group_plan(keys, sel, payloads)
        layout = seg.sorted_layout(order, gid_sorted, num_groups)
        if sel is None:
            sel_l = None
        else:
            n_live = jnp.sum(sel).astype(jnp.int32)
            sel_l = jnp.arange(n, dtype=jnp.int32) < n_live
        return layout, jnp.arange(n) < num_groups, payloads_l, sel_l

    @staticmethod
    def _agg_payloads(aggregates, columns):
        """(payload_arrays, slots): flatten every non-distinct aggregate
        argument (values + validity) into sort-payload operands; slots maps
        each call to its (index, has_valid) or None (count(*)/DISTINCT)."""
        payload_arrays: List = []
        slots: List = []
        for call in aggregates:
            if call.arg_channel is None or call.distinct:
                slots.append(None)
                continue
            def add(col):
                vi = len(payload_arrays)
                payload_arrays.append(col.values)
                hv = col.nulls is not None
                if hv:
                    payload_arrays.append(~col.nulls)
                hii = None
                if col.hi is not None:  # long-decimal high limb rides along
                    hii = len(payload_arrays)
                    payload_arrays.append(col.hi)
                return (vi, hv, hii)

            s1 = add(columns[call.arg_channel])
            s2 = (
                add(columns[call.arg2_channel])
                if call.arg2_channel is not None
                else None
            )
            slots.append((s1, s2))
        return payload_arrays, slots

    @staticmethod
    def _slot_arg(payloads_l, slot):
        if slot is None:
            return None
        vi, hv, _ = slot
        return (payloads_l[vi], payloads_l[vi + 1] if hv else None)

    @staticmethod
    def _slot_hi(payloads_l, slot):
        """Layout-space high-limb array of the aggregate argument, if any."""
        if slot is None or slot[2] is None:
            return None
        return payloads_l[slot[2]]

    @staticmethod
    def _presorted_group(group_channels: List[int], page: Page):
        """The single group-key column when the page is already
        group-contiguous: key ascending, null-free, dead rows a tail
        (sel None or live-prefix). Returns its values array or None."""
        if len(group_channels) != 1:
            return None
        col = page.columns[group_channels[0]]
        if not col.ascending or col.nulls is not None:
            return None
        if page.sel is not None and not page.live_prefix:
            return None
        return col.values

    @staticmethod
    def _scans_alone(node: P.AggregationNode, page: Page) -> bool:
        """Whether every aggregate of ``node`` is a prefix scan over
        presorted runs (``seg.run_layout``): sum, count and avg of integer
        (or decimal) arguments held in one limb. Anything else reads the
        sorted layout's group list."""
        for call in node.aggregates:
            if call.distinct or call.function not in ("sum", "count", "avg"):
                return False
            if call.arg_channel is not None:
                col = page.columns[call.arg_channel]
                if col.hi is not None or not jnp.issubdtype(
                        col.values.dtype, jnp.integer):
                    return False
        return True

    @staticmethod
    def _direct_strides(group_channels: List[int], page: Page):
        sizes = []
        for c in group_channels:
            col = page.columns[c]
            if col.nulls is not None:
                return None
            if col.type.is_varchar and col.dictionary is not None:
                sizes.append(max(len(col.dictionary), 1))
            elif col.type == T.BOOLEAN:
                sizes.append(2)
            else:
                return None
        capacity = 1
        for s in sizes:
            capacity *= s
        if not 1 <= capacity <= seg.DIRECT_CAPACITY_MAX:
            return None
        strides = []
        acc = 1
        for s in reversed(sizes):
            strides.append(acc)
            acc *= s
        return list(reversed(strides)), capacity

    def aggregate_page(self, node: P.AggregationNode, page: Page) -> Page:
        """Group and aggregate; output has `capacity` rows, sel marking live
        groups (prefix for the sort path, occupancy mask for the direct
        path — both in group-key order)."""
        if node.group_channels and self.eager_tier:
            spilled = self._maybe_spill_aggregation(node, page)
            if spilled is not None:
                return spilled
        fused = self._as_one_program("aggregate_page", node, page)
        if fused is not None:
            return fused
        n = page.num_rows
        sel = page.sel
        if n == 0:
            page = Page(
                [
                    Column(c.type, jnp.zeros((1,), dtype=c.values.dtype), None, c.dictionary)
                    for c in page.columns
                ],
                jnp.zeros((1,), dtype=bool),
            )
            n = 1
            sel = page.sel
        payload_arrays, slots = self._agg_payloads(node.aggregates, page.columns)
        # array_agg/histogram/map_agg need group-contiguous rows in layout
        # space (their outputs ARE the per-group row runs); the direct
        # masked-loop layout never permutes, so force the sort strategy
        force_sort = any(
            c.function in ("array_agg", "histogram", "map_agg")
            for c in node.aggregates
        )
        layout, out_sel, payloads_l, sel_l = self.group_structure(
            node.group_channels, page, payload_arrays, force_sort=force_sort,
            in_place=self._scans_alone(node, page),
        )
        out_cols: List[Column] = []
        if layout.run_start is not None:
            # slot i is row i: the key column is its own output
            out_cols.append(page.columns[node.group_channels[0]])
        elif node.group_channels:
            out_cols.extend(
                self._gathered_key_cols(page, node.group_channels, layout)
            )
        for call, slot in zip(node.aggregates, slots):
            s1, s2 = slot if slot is not None else (None, None)
            if call.function in ("array_agg", "histogram", "map_agg"):
                if call.distinct:
                    raise NotImplementedError(
                        f"{call.function}(DISTINCT): not yet supported")
                out_cols.append(
                    self._nested_agg_column(
                        call, page, layout,
                        self._slot_arg(payloads_l, s1),
                        self._slot_arg(payloads_l, s2) if s2 is not None else None,
                        sel_l,
                        hi_l=self._slot_hi(payloads_l, s1),
                    )
                )
                continue
            res = self._exec_aggregate(
                call, page, sel, layout, self._slot_arg(payloads_l, s1), sel_l,
                hi_l=self._slot_hi(payloads_l, s1),
                arg2_l=self._slot_arg(payloads_l, s2) if s2 is not None else None,
                hi2_l=self._slot_hi(payloads_l, s2) if s2 is not None else None,
            )
            vals, valid = res[0], res[1]
            hi_out = res[2] if len(res) > 2 else None
            # value-carrying aggregates keep the argument's dictionary
            dictionary = None
            if call.function in ("min", "max", "arbitrary", "any_value",
                                 "min_by", "max_by") and call.arg_channel is not None:
                dictionary = page.columns[call.arg_channel].dictionary
            out_cols.append(
                Column(
                    call.output_type,
                    vals,
                    (~valid) if valid is not None else None,
                    dictionary,
                    hi=hi_out,
                )
            )
        return Page(out_cols, out_sel, page.replicated)

    @staticmethod
    def _sum_fits_int64(page: Page, channel: int, n: int) -> bool:
        """Whether connector stats bound a sum of ``n`` values of the column
        inside int64 (with headroom)."""
        vrange = page.columns[channel].vrange
        if vrange is None:
            return False
        b = max(abs(int(vrange[0])), abs(int(vrange[1])))
        return b * max(n, 1) < 2**62

    def _as_one_program(self, entry: str, node: P.AggregationNode,
                        page: Page) -> Optional[Page]:
        """The eager tier's direct-layout aggregation as ONE compiled
        program per page (``direct_aggregation``), in place of some
        hundred eagerly dispatched reductions; None where the body has to
        run as it stands: a traced tier (already inside a program), the
        sorted and presorted layouts (a data-dependent sort in the
        middle), and aggregates that regroup or read a dictionary's
        content. Either way the executing operator's kernel row counts
        the body (``aggPrograms`` / ``aggEager``)."""
        if not self.eager_tier:
            return None
        # final and intermediate pages carry their keys first
        channels = (node.group_channels if entry in
                    ("aggregate_page", "aggregate_partial")
                    else list(range(len(node.group_channels))))
        columns = None
        if ((not channels or self._direct_strides(channels, page) is not None)
                and not any(c.distinct or c.function in _UNFUSED_AGGREGATES
                            for c in node.aggregates)):
            arrays, page_spec = flatten_page(page)
            columns = static_spec(page_spec)  # None: nested columns
        if columns is None:
            count_charged("aggEager")
            return None
        spec = _AggregationSpec(
            entry, tuple(node.group_channels), tuple(node.aggregates),
            tuple(node.source.output_types)
            if entry == "aggregate_partial" else (),
            columns,
            tuple(c.arg_channel for c in node.aggregates
                  if c.function == "sum" and entry == "aggregate_page"
                  and P._is_long_decimal(c.output_type)
                  and self._sum_fits_int64(page, c.arg_channel,
                                           page.num_rows)))
        out_arrays, out_spec, flags = direct_aggregation(spec, arrays)
        count_charged("aggPrograms")
        self.errors.extend(zip(out_spec.notes, flags))
        out = attach_dictionaries(
            unflatten_page(out_spec.page_spec(), out_arrays), page)
        for i, c in enumerate(channels):
            out.columns[i].vrange = page.columns[c].vrange
        return out

    def _gathered_key_cols(self, page: Page, channels, layout) -> List[Column]:
        """Output group-key columns gathered at each slot's representative
        row, rebuilding two-limb long decimals from their (hi, lo-flipped)
        key operand pairs (_key_lowereds)."""
        keys, spans = [], []
        for c in channels:
            parts = _key_lowereds(page.columns[c])
            spans.append((len(keys), len(parts)))
            keys.extend(parts)
        key_cols = gb.gather_group_keys(keys, layout.rep)
        out = []
        for (start, cnt), c in zip(spans, channels):
            src = page.columns[c]
            if cnt == 2:
                hi_v, valid = key_cols[start]
                lo_flip, _ = key_cols[start + 1]
                lo = lo_flip ^ jnp.int64(-(2**63))
                out.append(
                    Column(src.type, lo, None if valid is None else ~valid,
                           None, hi=hi_v)
                )
            else:
                v, valid = key_cols[start]
                out.append(
                    Column(src.type, v, None if valid is None else ~valid,
                           src.dictionary, src.vrange)
                )
        return out

    def _nested_agg_column(self, call, page, layout, arg_l, arg2_l, sel_l,
                           hi_l=None) -> Column:
        """Aggregates with nested (array/map) outputs.

        array_agg: the output array column IS the group-contiguous row runs
        of the grouping sort — per-slot lengths are the group ranges, the
        flat child is the (layout-space) argument column itself. NULL inputs
        are kept as NULL elements (reference: ArrayAggregationFunction).
        Sorted layouts put live rows first, group-contiguous from position
        0, so cumsum(lengths) == starts for every live slot and the flat
        child aligns with no extra gather. The global (no GROUP BY) case
        rides the direct single-slot layout: live rows compact to a prefix
        at their positions listed in order (``ranks.true_positions``).

        histogram / map_agg re-group on (group, key) pairs (ops/aggregate.py
        grouped_pairs): each distinct pair is one map entry; histogram's
        values are the run counts, map_agg's the representative row's value
        (duplicate keys keep an arbitrary one, matching the reference)."""
        if call.function in ("histogram", "map_agg"):
            return self._map_agg_column(call, page, layout, sel_l)
        vals_l, valid_l = arg_l
        src = page.columns[call.arg_channel]
        elem_t = call.output_type.element
        if layout.is_direct:
            assert layout.capacity == 1, "grouped array_agg must use a sorted layout"
            n = layout.n
            if sel_l is None:
                flat, flat_valid, flat_hi = vals_l, valid_l, hi_l
                count = jnp.int32(n)
            else:
                order = ranks_ops.true_positions(sel_l, n)
                flat = vals_l[order]
                flat_valid = valid_l[order] if valid_l is not None else None
                flat_hi = hi_l[order] if hi_l is not None else None
                count = jnp.sum(sel_l.astype(jnp.int32))
            lengths = count[None].astype(jnp.int32)
        else:
            lengths = (layout.ends - layout.starts).astype(jnp.int32)
            flat, flat_valid, flat_hi = vals_l, valid_l, hi_l
        child = Column(
            elem_t, flat, None if flat_valid is None else ~flat_valid, src.dictionary,
            hi=flat_hi,
        )
        # SQL: an aggregate over zero rows is NULL (a zero-length group can
        # only arise from an empty input set)
        return Column(call.output_type, lengths, lengths == 0, children=[child])

    def _map_agg_column(self, call, page, layout, sel_l) -> Column:
        """histogram(x) / map_agg(k, v) over original-order page columns
        (grouped_pairs re-sorts internally; null keys drop per SQL)."""
        # keys/values without limb kernels degrade to the low word with a
        # deferred overflow check (see _narrowed_or_flag)
        key_col = self._narrowed_or_flag(page.columns[call.arg_channel], page.sel)
        key = _col_to_lowered(key_col)
        # sel must be in ORIGINAL row order here (grouped_pairs resorts)
        entry_counts, rep, run_counts, entry_live = agg_ops.grouped_pairs(
            layout, key, page.sel
        )
        keys_flat = Column(
            call.output_type.key, key_col.values[rep], None, key_col.dictionary
        )
        if call.function == "histogram":
            vals_flat = Column(T.BIGINT, run_counts)
        else:
            vcol = page.columns[call.arg2_channel]
            vvals = vcol.values[rep]
            vnulls = vcol.nulls[rep] if vcol.nulls is not None else None
            vhi = vcol.hi[rep] if vcol.hi is not None else None
            vals_flat = Column(call.output_type.value, vvals, vnulls,
                               vcol.dictionary, hi=vhi)
        # SQL: null for groups whose input set is empty after null-key drops
        return Column(
            call.output_type, entry_counts, entry_counts == 0,
            children=[keys_flat, vals_flat],
        )

    _in_spill_pass = False  # reentrancy guard for partitioned passes

    def _maybe_spill_aggregation(self, node: P.AggregationNode, page: Page):
        """Over-budget group-by: hash-partition rows by group key host-side,
        aggregate each partition fully on device, concatenate. Partitions
        hold disjoint group-key sets, so per-partition results are exact
        (reference: SpillableHashAggregationBuilder, host RAM as the tier)."""
        from trino_tpu.exec import memory as mem

        if self._in_spill_pass or not self.spill_enabled:
            return None
        projected = mem.page_bytes(page)
        parts = self.memory.spill_partitions(projected)
        if parts <= 1:
            return None
        self.memory.record_spill(node.id, "aggregation", parts, projected)
        out = None
        self._in_spill_pass = True
        try:
            for part in mem.partition_page_host(page, node.group_channels, parts):
                res = self.aggregate_page(node, part).compact()
                out = res if out is None else Page.concat_pages(out, res)
        finally:
            self._in_spill_pass = False
        return out

    def _exec_aggregate(
        self, call: P.AggregateCall, page, sel, layout, arg_l, sel_l,
        hi_l=None, arg2_l=None, hi2_l=None,
    ):
        """``arg_l``/``sel_l``/``hi_l`` are in layout space (group_structure
        payloads); the DISTINCT path re-groups and takes the original-order
        page column instead. Returns (vals, valid) — or (lo, valid, hi) for
        two-limb long-decimal results."""
        if hi_l is not None and call.function not in ("sum", "count"):
            # no limb kernel for this aggregate: degrade to the low word
            # with a deferred overflow check (the pre-limb contract)
            arg_l = self._narrow_lowered_or_flag(arg_l, hi_l, sel_l)
            hi_l = None
        if hi2_l is not None:
            arg2_l = self._narrow_lowered_or_flag(arg2_l, hi2_l, sel_l)
        if call.function == "approx_percentile":
            if call.distinct:
                raise NotImplementedError(
                    "approx_percentile(DISTINCT): not yet supported")
            from trino_tpu.ops import hll

            vals_l, valid_l = arg_l
            m_l = valid_l if sel_l is None else (
                sel_l if valid_l is None else (sel_l & valid_l))
            return hll.approx_percentile(layout, vals_l, m_l, call.param)
        if call.distinct:
            if call.function not in ("count", "approx_distinct"):
                raise NotImplementedError(f"{call.function}(DISTINCT): not yet supported")
            arg = _col_to_lowered(page.columns[call.arg_channel])
            if call.function == "approx_distinct":
                # real HyperLogLog sketch (reference: airlift HLL via
                # ApproximateCountDistinctAggregation) — m=2048, ~2.3%
                # standard error, at sorted-segment cost (ops/hll.py)
                from trino_tpu.ops import hll

                return hll.approx_distinct(layout, arg, sel)
            return agg_ops.agg_count_distinct(layout, arg, sel)
        sel = sel_l
        if call.function == "count" and call.arg_channel is None:
            return agg_ops.agg_count_star(layout, sel)
        arg = arg_l
        if call.function == "count":
            return agg_ops.agg_count(layout, arg, sel)
        if call.function == "sum":
            vals_l, valid_l = arg
            out_t = call.output_type
            need128 = hi_l is not None
            if (not need128 and isinstance(out_t, T.DecimalType)
                    and out_t.precision > 18):
                # int64 accumulation is exact only when stats bound the
                # total; otherwise take the limb path (correct for the full
                # p38 range instead of silently wrapping)
                need128 = not self._sum_fits_int64(
                    page, call.arg_channel, layout.n)
            if need128:
                (s_hi, s_lo), nonempty = agg_ops.agg_sum_128(
                    layout, vals_l, hi_l, valid_l, sel
                )
                return s_lo, nonempty, s_hi
            return agg_ops.agg_sum(layout, arg, sel, call.output_type.np_dtype)
        if call.function == "avg":
            base = (
                call.output_type.np_dtype
                if call.output_type.is_decimal
                else np.dtype(np.float64)
            )
            s, _ = agg_ops.agg_sum(layout, arg, sel, base)
            cnt, _ = agg_ops.agg_count(layout, arg, sel)
            return agg_ops.finish_avg(s, cnt, call.output_type)
        if call.function == "min":
            return agg_ops.agg_min(layout, arg, sel)
        if call.function == "max":
            return agg_ops.agg_max(layout, arg, sel)
        if call.function in P._VAR_FAMILY:
            t = page.columns[call.arg_channel].type
            return agg_ops.agg_var(
                layout, arg, sel, call.function, t.scale if t.is_decimal else 0
            )
        if call.function in ("bool_and", "bool_or"):
            # boolean min/max (reference: BooleanAndAggregation/BooleanOr)
            vals_l, valid_l = arg
            fn = agg_ops.agg_min if call.function == "bool_and" else agg_ops.agg_max
            v, valid = fn(layout, (vals_l, valid_l), sel)
            return v.astype(bool), valid
        if call.function == "count_if":
            vals_l, valid_l = arg
            m = vals_l if valid_l is None else (vals_l & valid_l)
            return agg_ops.agg_count_star(layout, m if sel is None else m & sel)
        if call.function in ("arbitrary", "any_value"):
            return agg_ops.agg_first(layout, arg, sel)
        if call.function == "geometric_mean":
            vals_l, valid_l = arg
            t = page.columns[call.arg_channel].type
            x = vals_l.astype(jnp.float64)
            if t.is_decimal:
                x = x / (10.0 ** t.scale)
            ln = jnp.log(jnp.maximum(x, 1e-300))  # non-positive -> NaN domain
            ln = jnp.where(x > 0, ln, jnp.nan)
            s, nonempty = agg_ops.agg_sum(layout, (ln, valid_l), sel, np.dtype(np.float64))
            cnt, _ = agg_ops.agg_count(layout, arg, sel)
            v = jnp.exp(s / jnp.maximum(cnt, 1))
            return v, nonempty
        if call.function == "checksum":
            # order-independent 64-bit checksum: sum (mod 2^64) of per-row
            # CONTENT hashes (reference ChecksumAggregation is xor-of-hash;
            # same properties, engine-specific constant). Varchar hashes the
            # UTF-8 string per vocab entry (dictionary codes are ranks and
            # would collide across datasets); floats hash their bit pattern.
            from trino_tpu.parallel.exchange import _mix64 as mix64

            vals_l, valid_l = arg
            src = page.columns[call.arg_channel]
            if src.dictionary is not None:
                import hashlib

                lut = np.array(
                    [
                        int.from_bytes(
                            hashlib.blake2b(v.encode(), digest_size=8).digest(),
                            "little", signed=True)
                        for v in src.dictionary.values
                    ] or [0],
                    dtype=np.int64,
                )
                h = jnp.asarray(lut)[jnp.clip(vals_l, 0, len(lut) - 1)]
            else:
                x = vals_l
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = jax.lax.bitcast_convert_type(
                        x.astype(jnp.float64), jnp.int64)
                h = mix64(x.astype(jnp.int64).astype(jnp.uint64)).astype(jnp.int64)
            if valid_l is not None:
                h = jnp.where(valid_l, h, jnp.int64(-7046029254386353131))
            v, _ = agg_ops.agg_sum(layout, (h, None), sel, np.dtype(np.int64))
            return v, None
        if call.function in ("min_by", "max_by"):
            return agg_ops.agg_minmax_by(
                layout, arg, arg2_l, sel, call.function == "min_by"
            )
        if call.function in ("corr", "covar_samp", "covar_pop",
                             "regr_slope", "regr_intercept"):
            tx = page.columns[call.arg_channel].type
            ty = page.columns[call.arg2_channel].type
            return agg_ops.agg_bivariate(
                layout, arg, arg2_l, sel, call.function,
                tx.scale if tx.is_decimal else 0,
                ty.scale if ty.is_decimal else 0,
            )
        raise NotImplementedError(call.function)

    # -------------------------------------------------------------- window
    def _exec_WindowNode(self, node: P.WindowNode) -> Page:
        return self.window_over_page(node, self.execute(node.source))

    def window_over_page(self, node: P.WindowNode, page: Page) -> Page:
        from trino_tpu.ops import window as win_ops

        n = page.num_rows
        pkeys = [
            kl for c in node.partition_channels
            for kl in _key_lowereds(page.columns[c])
        ]
        okeys = [
            (kl, asc, nf)
            for c, asc, nf in node.order_channels
            for kl in _key_lowereds(page.columns[c])
        ]
        layout = win_ops.build_layout(pkeys, okeys, page.sel, n)
        out_cols = list(page.columns)
        for call, name in zip(node.calls, node.names):
            arg = (
                _col_to_lowered(
                    self._narrowed_or_flag(page.columns[call.arg_channel],
                                           page.sel))
                if call.arg_channel is not None
                else None
            )
            fn = call.function
            flo, fhi = call.frame_lo, call.frame_hi
            if fn == "row_number":
                v, valid = win_ops.row_number(layout)
            elif fn == "rank":
                v, valid = win_ops.rank(layout)
            elif fn == "dense_rank":
                v, valid = win_ops.dense_rank(layout)
            elif fn == "ntile":
                v, valid = win_ops.ntile(layout, call.offset)
            elif fn == "percent_rank":
                v, valid = win_ops.percent_rank(layout)
            elif fn == "cume_dist":
                v, valid = win_ops.cume_dist(layout)
            elif fn == "sum":
                v, valid = win_ops.agg_sum(
                    layout, arg, call.frame, call.output_type.np_dtype, flo, fhi)
            elif fn == "avg":
                s, s_valid = win_ops.agg_sum(
                    layout, arg, call.frame,
                    call.output_type.np_dtype if call.output_type.is_decimal
                    else np.dtype(np.float64),
                    flo, fhi,
                )
                cnt, _ = win_ops.agg_count(layout, arg, call.frame, flo, fhi)
                v, dvalid = agg_ops.finish_avg(s, cnt, call.output_type)
                valid = s_valid if dvalid is None else (
                    dvalid if s_valid is None else (s_valid & dvalid)
                )
            elif fn in ("count", "count_star"):
                v, valid = win_ops.agg_count(layout, arg, call.frame, flo, fhi)
            elif fn in ("min", "max"):
                v, valid = win_ops.agg_minmax(layout, arg, call.frame, fn == "min")
            elif fn in ("lag", "lead"):
                v, valid = win_ops.shifted_value(layout, arg, call.offset, fn == "lead")
            elif fn == "nth_value":
                v, valid = win_ops.nth_value(
                    layout, arg, call.offset, call.frame, flo, fhi)
            elif fn in ("first_value", "last_value"):
                v, valid = win_ops.edge_value(
                    layout, arg, call.frame, fn == "first_value", flo, fhi)
            else:
                raise NotImplementedError(f"window function {fn}")
            # value-carrying functions keep the source column's dictionary
            dictionary = None
            if fn in ("min", "max", "lag", "lead", "first_value", "last_value",
                      "nth_value"):
                dictionary = page.columns[call.arg_channel].dictionary
            out_cols.append(
                Column(call.output_type, v, None if valid is None else ~valid, dictionary)
            )
        return Page(out_cols, page.sel, page.replicated)

    # -------------------------------------------------------------- joins
    def _exec_JoinNode(self, node: P.JoinNode,
                       compact_into: Optional[P.CompactNode] = None) -> Page:
        # Build side FIRST (the reference's phased build-before-probe
        # ordering) so its key domains can dynamically narrow probe scans.
        right = self.execute(node.right)
        measured = {}  # a traced tier reads nothing: its domains are arrays
        if self.eager_tier and node.left_keys:
            right, measured = self._measure_build_keys(node, right)
        if self.enable_dynamic_filtering and node.dyn_filter_keys:
            self._collect_dynamic_filters(node, right, measured)
        left = self.execute(node.left)
        return self._dispatch_join(node, left, right, compact_into)

    def _dispatch_join(self, node: P.JoinNode, left: Page, right: Page,
                       compact_into: Optional[P.CompactNode] = None) -> Page:
        if self.eager_tier:
            # the slots this join's place in the ORDER makes it carry,
            # whatever the kernels then do with them (static shapes)
            count_charged("joinProbeSlots", left.num_rows)
            count_charged("joinBuildSlots", right.num_rows)
        if node.left_keys and self.eager_tier:
            # eager tier: spill-partition when the working set exceeds the
            # device budget (traced tiers bound memory via capacity hints)
            spilled = self._maybe_spill_join(node, left, right)
            if spilled is not None:
                return spilled  # the passes' own path: each compacts on the host
        if compact_into is not None:
            return self.compacted_lookup_join(node, left, right, compact_into)
        return self._run_join_kernel(node, left, right)

    def _maybe_spill_join(self, node: P.JoinNode, left: Page, right: Page):
        """Host-offload spill (exec/memory.py): when probe+build exceed the
        device budget, hash-partition BOTH sides by join key host-side and
        run the join as P independent on-device passes (equal keys
        co-locate, so the union of pass outputs is the exact join). The
        reference's partitioned-spill design (HashBuilderOperator FSM +
        GenericPartitioningSpiller) with host RAM as the spill tier."""
        from trino_tpu.exec import memory as mem

        if not self.spill_enabled:
            return None
        projected = mem.page_bytes(left) + mem.page_bytes(right)
        parts = self.memory.spill_partitions(projected)
        if parts <= 1:
            return None
        self.memory.record_spill(node.id, "join", parts, projected)
        lparts = mem.partition_page_host(left, node.left_keys, parts)
        rparts = mem.partition_page_host(right, node.right_keys, parts)
        out = None
        hint_key = f"join:{node.id}"
        for lp, rp in zip(lparts, rparts):
            # per-pass expansion capacity: each pass sizes its own bucket
            self.capacity_hints.pop(hint_key, None)
            res = self._run_join_kernel(node, lp, rp)
            res = res.compact()  # spill the pass result to host-sized rows
            out = res if out is None else Page.concat_pages(out, res)
        self.capacity_hints.pop(hint_key, None)
        return out

    def _run_join_kernel(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        """The single join-kernel dispatch, shared by the direct path and
        the spilled per-partition passes."""
        if node.join_type in ("semi", "anti"):
            if node.filter is not None:
                return self.semi_join_filtered(node, left, right)
            return self.semi_join(node, left, right)
        if not node.left_keys:
            if node.singleton:
                return self.singleton_cross(node, left, right)
            return self.expand_join(node, left, right)  # true cross join
        if node.right_unique:
            return self.lookup_join(node, left, right)
        return self.expand_join(node, left, right)

    DYNAMIC_FILTER_MAX_SET = 1024  # in-set domain cap (reference: the
    # small/large domain-compaction thresholds of DynamicFilterConfig)

    def _measure_build_keys(self, node: P.JoinNode, build: Page):
        """(build, key index -> (live count, min, max)) of its integer key
        columns, reduced on the device and fetched in ONE read of three
        scalars a key (site ``dynamic-filter-domain``): what a dynamic
        filter's domain starts from, and, for a key column whose static
        range did not survive an exchange, the range the direct-address
        join tier needs (``_dense_join_cols``; a 42,779-row build of part
        keys would otherwise send 62.9 M probe slots through a sort).
        Only keys one of the two will use are measured; a range measured
        for a column that had none is set on a copy of the page."""
        wanted = {}
        dense = len(node.right_keys) == 1 and (
            node.right_unique or node.join_type in ("semi", "anti"))
        for i, ch in enumerate(node.right_keys):
            col = build.columns[ch]
            if (col.type.is_varchar or col.hi is not None
                    or col.children is not None or not build.num_rows
                    or not jnp.issubdtype(col.values.dtype, jnp.integer)):
                continue
            df = (self.enable_dynamic_filtering
                  and i in (node.dyn_filter_keys or ()))
            if df or (dense and col.vrange is None):
                wanted[i] = col
        if not wanted:
            return build, {}
        scalars = []
        for col in wanted.values():
            live = build.sel
            if col.nulls is not None:
                live = ~col.nulls if live is None else live & ~col.nulls
            vals = jnp.asarray(col.values)
            info = jnp.iinfo(vals.dtype)
            if live is None:
                scalars += [vals.shape[0], vals.min(), vals.max()]
            else:
                scalars += [jnp.sum(live.astype(jnp.int32)),
                            jnp.where(live, vals, info.max).min(),
                            jnp.where(live, vals, info.min).max()]
        got = [int(x) for x in host_read_all(scalars, "dynamic-filter-domain")]
        measured, columns = {}, list(build.columns)
        for n, (i, col) in enumerate(wanted.items()):
            count, lo, hi = got[3 * n:3 * n + 3]
            measured[i] = (count, lo, hi)
            if col.vrange is None and count:
                columns[node.right_keys[i]] = dataclasses.replace(
                    col, vrange=(lo, hi))
        return dataclasses.replace(build, columns=columns), measured

    def _collect_dynamic_filters(self, node: P.JoinNode, build: Page,
                                 measured: Dict) -> None:
        """Build-side key domains for the probe scans the optimizer
        annotated. A build of at most ``DYNAMIC_FILTER_MAX_SET`` live keys
        gives its key set (the column read to the host); a larger one its
        range, from ``measured`` alone, and NO domain where that range
        narrows nothing (``_range_narrows``)."""
        from trino_tpu.connector.predicate import Domain

        for i in node.dyn_filter_keys:
            ch = node.right_keys[i]
            col = build.columns[ch]
            if col.type.is_varchar:
                continue  # dictionary codes are page-local, not portable
            if i in measured and measured[i][0] > self.DYNAMIC_FILTER_MAX_SET:
                _count, lo, hi = measured[i]
                if self._range_narrows(node, i, lo, hi):
                    self.dyn_domains[(node.id, i)] = Domain.range(
                        low=lo, high=hi)
                continue
            site = "dynamic-filter-domain"
            vals = host_read(col.values, site)
            live = (
                np.ones(len(vals), bool)
                if build.sel is None
                else host_read(build.sel, site).copy()
            )
            if col.nulls is not None:
                live &= ~host_read(col.nulls, site)
            lv = vals[live]
            if len(lv) == 0:
                dom = Domain(values=frozenset())  # provably empty probe
            elif len(lv) <= self.DYNAMIC_FILTER_MAX_SET:
                dom = Domain.from_values(np.unique(lv).tolist())
            else:
                dom = Domain.range(low=lv.min().item(), high=lv.max().item())
            self.dyn_domains[(node.id, i)] = dom

    def _range_narrows(self, node: P.JoinNode, i: int, lo: int, hi: int) -> bool:
        """False where [lo, hi] keeps as much of the probe column's own
        range (the connector's column statistics) as the planner assumes
        of a predicate it knows nothing about
        (``stats.UNKNOWN_FILTER_COEFFICIENT``) or more: 42,779 green part
        keys span 1 .. 2,000,000 of ``l_partkey``'s 1 .. 2,000,000. Such
        a domain prunes no split, costs a pass over every scanned row to
        apply, and would key the scan's cached artifact by binding. True
        where the probe column's range is not known."""
        from trino_tpu.sql.planner import stats
        from trino_tpu.sql.planner.optimizer import _trace_to_scan

        traced = _trace_to_scan(node.left, node.left_keys[i])
        if traced is None:
            return True
        scan, column = traced
        conn = self.session.catalogs.get(scan.catalog)
        cs = conn.column_stats(scan.schema, scan.table, column) if conn else None
        if cs is None or cs.vrange is None:
            return True
        kept = min(hi, cs.high) - max(lo, cs.low) + 1
        return kept < stats.UNKNOWN_FILTER_COEFFICIENT * (cs.high - cs.low + 1)

    def hint_capacity(self, key: str, emit_counts) -> int:
        """Static output capacity for an expansion join or exchange, by hint
        key ("join:<id>" / "xchg*:<id>", see sql/planner/stats.py)."""
        cap = self.capacity_hints.get(key)
        if cap is not None:
            return cap
        if emit_counts is None:  # exchanges have no eager fallback
            raise RuntimeError(
                f"{key} has no capacity hint — estimate_exchange_hints and "
                "the executor's dispatch disagree (sql/planner/stats.py)"
            )
        try:
            total = int(host_read(jnp.sum(emit_counts), "join-emit-count"))
        except jax.errors.ConcretizationTypeError:
            raise RuntimeError(
                f"{key} traced without a capacity hint — compiled paths "
                "estimate hints from stats (sql/planner/stats.py)"
            )
        cap = max(16, 1 << (max(total, 1) - 1).bit_length())
        self.capacity_hints[key] = cap
        return cap

    @staticmethod
    def _join_keys_aligned(left: Page, right: Page, left_keys, right_keys):
        """(build_keys, probe_keys) aligned for the join kernels, expanding
        two-limb long-decimal key columns into (hi, lo-flipped) pairs on
        BOTH sides symmetrically (_key_lowereds)."""
        build_keys, probe_keys, bvr, pvr = [], [], [], []
        for lc, rc in zip(left_keys, right_keys):
            bc, pc = right.columns[rc], left.columns[lc]
            if bc.hi is not None or pc.hi is not None:
                # symmetric two-limb expansion on BOTH sides (_key_lowereds)
                build_keys.extend(_key_lowereds(bc, force_two_limb=True))
                probe_keys.extend(_key_lowereds(pc, force_two_limb=True))
                bvr.extend([None, None])
                pvr.extend([None, None])
            else:
                build_keys.append(_col_to_lowered(bc))
                probe_keys.append(_col_to_lowered(pc))
                bvr.append(bc.vrange)
                pvr.append(pc.vrange)
        return join_ops.align_join_keys(build_keys, probe_keys, bvr, pvr)

    def _expansion_keys(self, node: P.JoinNode, left: Page, right: Page):
        if node.left_keys:
            return self._join_keys_aligned(
                left, right, node.left_keys, node.right_keys
            )
        # cross join: everything matches everything (constant key)
        build_keys = [(jnp.zeros((right.num_rows,), jnp.int32), None)]
        probe_keys = [(jnp.zeros((left.num_rows,), jnp.int32), None)]
        return build_keys, probe_keys


    @staticmethod
    def _gather_right_cols(right_cols, rows, mask) -> List[Column]:
        """Gather build-side payload columns by matched row ids, carrying
        two-limb hi limbs as extra gather operands."""
        lows = []
        for rc in right_cols:
            if rc.type.is_nested:
                raise NotImplementedError("array/map columns through join payloads")
            lows.append(_col_to_lowered(rc))
        hi_map = {}
        for i, rc in enumerate(right_cols):
            if rc.hi is not None:
                hi_map[i] = len(lows)
                lows.append((rc.hi, None))
        g = join_ops.gather_columns(lows, rows, mask)
        out = []
        for i, rc in enumerate(right_cols):
            v, valid = g[i]
            hi = g[hi_map[i]][0] if i in hi_map else None
            out.append(
                Column(
                    rc.type, v, ~valid if valid is not None else None,
                    rc.dictionary, rc.vrange if hi is None else None, hi=hi,
                )
            )
        return out

    @staticmethod
    def _build_presorted(page: Page, key_channels) -> bool:
        """True when the build page's single join key is ascending,
        null-free, and dead rows form a tail — build_side skips its sort."""
        if len(key_channels) != 1:
            return False
        col = page.columns[key_channels[0]]
        if not col.ascending or col.nulls is not None:
            return False
        return page.sel is None or page.live_prefix

    def expand_join(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        """General M:N inner/left join: count matches per probe row, then
        gather into a static-capacity probe-major output (ops/join.py
        probe_counts + expand; reference JoinHash position-links chains)."""
        build_keys, probe_keys = self._expansion_keys(node, left, right)
        build = join_ops.build_side(
            build_keys, right.sel,
            presorted=node.left_keys and self._build_presorted(right, node.right_keys))
        lo, counts = join_ops.probe_counts(build, probe_keys, left.sel)
        n = left.num_rows
        outer = node.join_type == "left"
        probe_live = (
            left.sel if left.sel is not None else jnp.ones((n,), dtype=bool)
        )
        plain_outer = outer and node.filter is None
        emit = jnp.where(probe_live, jnp.maximum(counts, 1), 0) if plain_outer else counts
        capacity = self.hint_capacity(f"join:{node.id}", emit)
        p, k, live, total = join_ops.expand(emit, capacity)
        self.errors.append((f"CAPACITY_EXCEEDED:join:{node.id}", total > capacity))
        # ONE batched random gather at p for lo/counts and every left column
        # (separate computed-index gathers don't fuse: ~40 ms each per 6M
        # rows on v5e — see ranks.batched_gather)
        left_arrays = [lo, counts]
        for c in left.columns:
            if c.type.is_nested:
                raise NotImplementedError("array/map columns through join payloads")
            left_arrays.append(c.values)
            if c.nulls is not None:
                left_arrays.append(c.nulls)
            if c.hi is not None:
                left_arrays.append(c.hi)
        g = ranks_ops.batched_gather(left_arrays, p)
        lo_p, counts_p = g[0], g[1]
        matched = live & (k < counts_p)
        b_idx = jnp.clip(lo_p + k, 0, build.n - 1)
        rows = build.rows[b_idx]
        out_cols = []
        gi = 2
        for c in left.columns:
            v = g[gi]
            gi += 1
            nulls = None
            if c.nulls is not None:
                nulls = g[gi]
                gi += 1
            chi = None
            if c.hi is not None:
                chi = g[gi]
                gi += 1
            out_cols.append(
                Column(c.type, v, nulls, c.dictionary,
                       c.vrange if chi is None else None, hi=chi))
        out_cols.extend(self._gather_right_cols(right.columns, rows, matched))
        page = Page(out_cols, live, left.replicated and right.replicated)
        if node.filter is None:
            return page
        lv = self._lower(node.filter, page)
        passed = lv.vals if lv.valid is None else (lv.vals & lv.valid)
        if not outer:
            return Page(out_cols, live & passed, page.replicated)
        # left join with filter: expanded rows that pass, plus one null-build
        # row for each probe row with no passing match
        passing = live & matched & passed
        # p is probe-major (non-decreasing) — monotonic segment sum, no scatter
        any_pass = (
            seg.monotonic_segment_sum(passing.astype(jnp.int32), p, n) > 0
        )
        tail_sel = probe_live & ~any_pass
        tail_cols = []
        for c in left.columns:
            tail_cols.append(c)
        for rc in right.columns:
            tail_cols.append(
                Column(
                    rc.type,
                    jnp.zeros((n,), dtype=rc.values.dtype),
                    jnp.ones((n,), dtype=bool),
                    rc.dictionary,
                )
            )
        head = Page(out_cols, passing, page.replicated)
        tail = Page(tail_cols, tail_sel, page.replicated)
        return Page.concat_pages(head, tail)

    def semi_join_filtered(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        """Semi/anti join with a residual filter (correlated EXISTS with
        non-equality predicates): expand the matches, evaluate the filter,
        then reduce any-passing back to the probe rows."""
        build_keys, probe_keys = self._expansion_keys(node, left, right)
        build = join_ops.build_side(
            build_keys, right.sel,
            presorted=node.left_keys and self._build_presorted(right, node.right_keys))
        lo, counts = join_ops.probe_counts(build, probe_keys, left.sel)
        n = left.num_rows
        capacity = self.hint_capacity(f"join:{node.id}", counts)
        p, k, live, total = join_ops.expand(counts, capacity)
        self.errors.append((f"CAPACITY_EXCEEDED:join:{node.id}", total > capacity))
        left_arrays = [lo]
        for c in left.columns:
            if c.type.is_nested:
                raise NotImplementedError("array/map columns through join payloads")
            left_arrays.append(c.values)
            if c.nulls is not None:
                left_arrays.append(c.nulls)
            if c.hi is not None:
                left_arrays.append(c.hi)
        g = ranks_ops.batched_gather(left_arrays, p)
        b_idx = jnp.clip(g[0] + k, 0, build.n - 1)
        rows = build.rows[b_idx]
        exp_cols = []
        gi = 1
        for c in left.columns:
            v = g[gi]
            gi += 1
            nulls = None
            if c.nulls is not None:
                nulls = g[gi]
                gi += 1
            chi = None
            if c.hi is not None:
                chi = g[gi]
                gi += 1
            exp_cols.append(
                Column(c.type, v, nulls, c.dictionary,
                       c.vrange if chi is None else None, hi=chi))
        exp_cols.extend(self._gather_right_cols(right.columns, rows, live))
        exp_page = Page(exp_cols, live, left.replicated and right.replicated)
        lv = self._lower(node.filter, exp_page)
        passed = lv.vals if lv.valid is None else (lv.vals & lv.valid)
        hit = (
            seg.monotonic_segment_sum((live & passed).astype(jnp.int32), p, n) > 0
        )
        keep = hit if node.join_type == "semi" else ~hit
        sel = keep if left.sel is None else left.sel & keep
        return Page(left.columns, sel, left.replicated)

    def _dense_join_cols(self, node: P.JoinNode, left: Page, right: Page):
        """(build_col, probe_col, lo, span) when the single-int-key dense
        direct-address kernel applies (ops/join.py dense_span), else None.
        Varchar (page-local dictionary codes) and two-limb decimals stay on
        the sort path."""
        if len(node.right_keys) != 1:
            return None
        bc = right.columns[node.right_keys[0]]
        pc = left.columns[node.left_keys[0]]
        if bc.hi is not None or pc.hi is not None:
            return None
        if bc.type.is_varchar or pc.type.is_varchar:
            return None
        if not (jnp.issubdtype(bc.values.dtype, jnp.integer)
                and jnp.issubdtype(pc.values.dtype, jnp.integer)):
            return None
        ds = join_ops.dense_span(bc.vrange, right.num_rows)
        if ds is None:
            return None
        return bc, pc, ds[0], ds[1]

    # ------------------------------------------------------ fused join tier
    def _fused_join_enabled(self) -> bool:
        props = getattr(self.session, "properties", None) or {}
        return bool(props.get("fused_join_enabled", True))

    def _pallas_merge_requested(self) -> bool:
        """``fused_join_pallas`` asks for the COMPILED Pallas merge kernel
        (ops/merge_pallas.py). OPT-IN: unset keeps the XLA rank merge.
        Interpret mode is not reachable from here — tests that want it
        pass ``interpret=True`` to the ops themselves."""
        props = getattr(self.session, "properties", None) or {}
        return bool(props.get("fused_join_pallas"))

    def _merge_sentinel_safe(self, node: P.JoinNode, left: Page, right: Page,
                             build_keys) -> bool:
        """The FULL Pallas merge contract: a single int32 key (the
        kernel's only lane dtype) whose PROVEN value range keeps the
        dtype's max (the dead-row sentinel and the kernel's pad value)
        unreachable by any live key. Checking the whole contract here
        keeps the ``merge-pallas`` selection metric truthful — the
        kernel's own guard would otherwise degrade silently to XLA after
        the tier was already counted."""
        if len(node.right_keys) != 1 or len(build_keys) != 1:
            return False
        bc = right.columns[node.right_keys[0]]
        pc = left.columns[node.left_keys[0]]
        if bc.hi is not None or pc.hi is not None:
            return False
        if bc.type.is_varchar or pc.type.is_varchar:
            return False
        dt = build_keys[0][0].dtype
        if dt != jnp.int32:
            return False
        return (bc.vrange is not None and pc.vrange is not None
                and max(int(bc.vrange[1]), int(pc.vrange[1]))
                < jnp.iinfo(dt).max)

    def _cached_sorted_build(self, node: P.JoinNode, right: Page, build_keys):
        """SortedBuild served by the device build cache, or None. Eager
        tier only (traced tiers sort in-program — their artifact is the
        compiled executable itself); the build side must be a bare
        versioned TableScanNode so the artifact's identity is provable
        from the scan signature + join-key signature."""
        if not self.eager_tier:
            return None
        scan = node.right
        if not isinstance(scan, P.TableScanNode):
            return None
        from trino_tpu import devcache

        constraint = scan_constraint_with(scan, self.dyn_domains)
        dtypes = ",".join(str(v.dtype) for v, _ in build_keys)

        def load():
            build = join_ops.build_side(build_keys, right.sel)
            arrays = list(build.cols) + [build.rows, build.live]
            nbytes = sum(int(a.size) * a.dtype.itemsize for a in arrays)
            return build, int(build.n), nbytes, 0

        built, _disposition = devcache.cached_build(
            self.session, scan, constraint,
            self._host_applied_domains(scan), tuple(node.right_keys),
            dtypes, load)
        return built

    def _merge_sorted_tier(self, node: P.JoinNode, left: Page, right: Page,
                           build, build_keys, probe_keys, record: bool = True):
        """(rows, matched) by merging probes against an already-sorted
        build — the Pallas tiled merge when its contract holds, the XLA
        rank merge otherwise. ``record=False`` skips the selection metric
        (the overlapped exchange calls this once per send block but the
        selection is one join)."""
        use_pallas = (self._pallas_merge_requested()
                      and self._merge_sentinel_safe(node, left, right,
                                                    build_keys))
        if use_pallas and jax.default_backend() != "tpu":
            # the kernel is Mosaic-compiled, TPU only; the property says
            # "the kernel", so this backend fails the query rather than
            # interpreting it (or quietly taking the XLA merge)
            raise QueryError(
                "fused_join_pallas=true needs the TPU backend: the Pallas "
                "merge kernel does not compile for "
                f"{jax.default_backend()!r}", code="PALLAS_MERGE_BACKEND")
        if record:
            M.FUSED_JOIN_SELECTIONS.inc(
                1, "merge-pallas" if use_pallas else "merge-sorted")
        return fused_ops.merge_sorted_build(
            build, probe_keys,
            use_pallas=use_pallas,
            pallas_block_build=self.capacity_hints.get(
                f"jtile:{node.id}", 2048),
        )

    def _sortmerge_probe(self, node: P.JoinNode, left: Page, right: Page):
        """(build_row_idx, matched) for the N:1 lookup join when the dense
        direct-address table does not apply: the fused sort-merge tier
        (ops/fused_join.py — one combined sort, no SortedBuild
        intermediate) behind the cost gate, with two special build-side
        shapes routed to the merge tier instead (a presorted key skips all
        build work; a device-cached sorted build skips the build sort on
        every warm join); legacy build_side + probe_unique when the tier
        is disabled."""
        build_keys, probe_keys = self._join_keys_aligned(
            left, right, node.left_keys, node.right_keys
        )
        presorted = self._build_presorted(right, node.right_keys)
        if self._fused_join_enabled():
            cached = None if presorted else self._cached_sorted_build(
                node, right, build_keys)
            if presorted or cached is not None:
                build = cached if cached is not None else join_ops.build_side(
                    build_keys, right.sel, presorted=True)
                return self._merge_sorted_tier(
                    node, left, right, build, build_keys, probe_keys)
            M.FUSED_JOIN_SELECTIONS.inc(1, "fused")
            return fused_ops.fused_probe_unique(
                build_keys, right.sel, probe_keys)
        M.FUSED_JOIN_SELECTIONS.inc(1, "legacy")
        build = join_ops.build_side(build_keys, right.sel, presorted=presorted)
        return join_ops.probe_unique(build, probe_keys)

    def _lookup_probe(self, node: P.JoinNode, left: Page, right: Page):
        """(build_row_idx, matched) for every probe slot of the N:1 lookup
        join: the half that moves no payload."""
        dense = self._dense_join_cols(node, left, right)
        if dense is None:
            return self._sortmerge_probe(node, left, right)
        # cost gate: dense-keyed builds keep the direct-address fast path
        # (one scatter of the build's row ids into the span table, one
        # bounded gather of it a probe slot, no sort: the tier every
        # benchmark cell takes for lineitem-orders, PERF.md section 5)
        M.FUSED_JOIN_SELECTIONS.inc(1, "dense")
        bc, pc, lo, span = dense
        table = join_ops.dense_unique_table(
            _col_to_lowered(bc), right.sel, lo, span)
        return join_ops.dense_probe_unique(table, _col_to_lowered(pc), lo)

    def lookup_join(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        rows, matched = self._lookup_probe(node, left, right)
        return self._assemble_lookup_output(node, left, right, rows, matched)

    def compacted_lookup_join(self, node: P.JoinNode, left: Page, right: Page,
                              into: P.CompactNode) -> Page:
        """``compact_to(lookup_join(node, left, right), capacity,
        "cmp:<into.id>")`` slot for slot, with the squeeze moved between
        the probe and the payload gather. The match mask is known after
        the probe, so the probe columns and the matched build row ids are
        gathered at the kept positions (``capacity`` rows, in the one
        batched gather ``compact_to`` issues) and the build payloads at
        THOSE row ids: ``capacity`` rows, not the probe page's n. q3's
        lineitem-orders join at SF 10 kept 2,097,152 of 62,914,560 slots
        and wrote four payload columns and their null masks for all of
        them first: the largest device operation of both SF 10 cells
        (PERF.md section 6, PR 33). Same capacity hint, same
        CAPACITY_EXCEEDED:cmp:<id> flag; a page ``compact_to`` would
        return as it came takes the plain path and the Compact above
        returns it likewise."""
        key = f"cmp:{into.id}"
        rows, matched = self._lookup_probe(node, left, right)
        sel = matched if left.sel is None else (left.sel & matched)
        total = jnp.sum(sel.astype(jnp.int32))
        capacity = self.hint_capacity(key, total)
        if (capacity >= left.num_rows
                or any(c.type.is_nested for c in left.columns)):
            return self._assemble_lookup_output(
                node, left, right, rows, matched)
        idx, live = self._kept_positions(sel, total, capacity, key)
        if self.eager_tier:  # a traced tier would count its trace, not its runs
            count_charged("compactedJoins")
            # the page is squeezed here, for that node: its row keeps count
            self._kernel_row(into)["prefixCompactions"] += 1
        cols, (rows_at,) = self._columns_at(left.columns, idx, (rows,))
        # matched[idx] with no gather (a word a slot out of the n-slot
        # mask read 20 ms at q3's shape): a live slot holds a matched row,
        # a slot past the count holds row 0
        matched_at = live | matched[0]
        cols.extend(self._gather_right_cols(right.columns, rows_at, matched_at))
        return Page(cols, live, left.replicated, live_prefix=True)

    def _assemble_lookup_output(self, node: P.JoinNode, left: Page,
                                right: Page, rows, matched) -> Page:
        """Projection half of the lookup join: gather build payloads at the
        matched rows, for EVERY probe slot, and apply join-type/filter
        semantics. ROW-LOCAL in the probe (each output row depends only on
        its probe row and the whole build) — the property the overlapped
        SPMD exchange relies on to consume probe blocks independently
        (parallel/spmd.py). An inner join with no filter whose consumer is
        a Compact does not come here: ``compacted_lookup_join`` gathers at
        the kept slots only."""
        out_cols = list(left.columns)
        out_cols.extend(self._gather_right_cols(right.columns, rows, matched))
        if node.join_type == "inner":
            sel = matched if left.sel is None else (left.sel & matched)
        else:  # left outer: probe rows always survive; build cols null when unmatched
            sel = left.sel
        page = Page(out_cols, sel, left.replicated)
        if node.filter is not None:
            lv = self._lower(node.filter, page)
            passed = lv.vals if lv.valid is None else (lv.vals & lv.valid)
            if node.join_type == "left":
                # probe rows survive; a failing filter just voids the match
                keep_match = matched & passed
                new_cols = list(left.columns)
                for rc, oc in zip(right.columns, out_cols[len(left.columns):]):
                    nulls = ~keep_match if oc.nulls is None else (oc.nulls | ~keep_match)
                    new_cols.append(Column(oc.type, oc.values, nulls, oc.dictionary))
                return Page(new_cols, left.sel, left.replicated)
            page = Page(out_cols, passed if page.sel is None else page.sel & passed, left.replicated)
        return page

    def semi_join(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        dense = self._dense_join_cols(node, left, right)
        if dense is not None:
            M.FUSED_JOIN_SELECTIONS.inc(1, "dense")
            bc, pc, lo, span = dense
            hit = join_ops.dense_membership(
                _col_to_lowered(bc), right.sel, _col_to_lowered(pc), lo, span)
            keep = hit if node.join_type == "semi" else ~hit
            sel = keep if left.sel is None else left.sel & keep
            return Page(left.columns, sel, left.replicated)
        build_keys, probe_keys = self._join_keys_aligned(
            left, right, node.left_keys, node.right_keys
        )
        presorted = self._build_presorted(right, node.right_keys)
        if self._fused_join_enabled():
            # same tier gate as the lookup join: presorted/device-cached
            # sorted builds take the merge tier, everything else fuses
            # build+probe into one combined sort (duplicates on the build
            # side are fine for membership — any live equal row flags)
            cached = None if presorted else self._cached_sorted_build(
                node, right, build_keys)
            if presorted or cached is not None:
                build = cached if cached is not None else join_ops.build_side(
                    build_keys, right.sel, presorted=True)
                _rows, hit = self._merge_sorted_tier(
                    node, left, right, build, build_keys, probe_keys)
            else:
                M.FUSED_JOIN_SELECTIONS.inc(1, "fused")
                hit = fused_ops.fused_membership(
                    build_keys, right.sel, probe_keys)
        else:
            M.FUSED_JOIN_SELECTIONS.inc(1, "legacy")
            hit = join_ops.membership(
                build_keys, right.sel, probe_keys, presorted=presorted)
        keep = hit if node.join_type == "semi" else ~hit
        sel = keep if left.sel is None else left.sel & keep
        return Page(left.columns, sel, left.replicated)

    def singleton_cross(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        """Cross join against a single-row relation (scalar subquery)."""
        r_sel = right.sel
        nr = right.num_rows
        if r_sel is None:
            live = jnp.asarray(nr, dtype=jnp.int64)
            idx = 0
        else:
            live = jnp.sum(r_sel)
            idx = jnp.argmax(r_sel)
        self.errors.append(("SCALAR_SUBQUERY_MULTIPLE_ROWS", live > 1))
        self.errors.append(("SCALAR_SUBQUERY_NO_ROWS", live < 1))
        n = left.num_rows
        out_cols = list(left.columns)
        for rc in right.columns:
            v = jnp.broadcast_to(rc.values[idx], (n,))
            nulls = (
                jnp.broadcast_to(rc.nulls[idx], (n,)) if rc.nulls is not None else None
            )
            out_cols.append(Column(rc.type, v, nulls, rc.dictionary, rc.vrange))
        page = Page(out_cols, left.sel, left.replicated)
        if node.filter is not None:
            lv = self._lower(node.filter, page)
            passed = lv.vals if lv.valid is None else lv.vals & lv.valid
            page = Page(out_cols, passed if page.sel is None else page.sel & passed, left.replicated)
        return page

    # ----------------------------------------------------- pattern matching
    def _exec_MatchRecognizeNode(self, node: "P.MatchRecognizeNode") -> Page:
        """MATCH_RECOGNIZE (reference: PatternRecognitionOperator): host
        tier only — the backtracking matcher is sequential by nature (see
        exec/match_recognize.py). Traced tiers route queries containing it
        through the gathered coordinator fragment."""
        if not self.eager_tier:
            raise NotImplementedError(
                "MATCH_RECOGNIZE executes on the host tier")
        from trino_tpu.exec.match_recognize import run_match_recognize

        page = self.execute(node.source)
        names = node.input_names or node.source.output_names
        # case-insensitive resolution, matching the analyzer's (plan-time
        # validation lowercases identifiers)
        lnames = [n.lower() for n in names]
        pyrows = [dict(zip(lnames, r)) for r in page.to_pylist()]
        part_names = [lnames[c] for c in node.partition_channels]
        parts: Dict[tuple, List[dict]] = {}
        for r in pyrows:
            parts.setdefault(tuple(r[n] for n in part_names), []).append(r)

        class _K:
            """Total-order sort key with SQL null placement (nulls last
            ascending, first descending — the engine's default)."""

            __slots__ = ("v", "asc")

            def __init__(self, v, asc):
                self.v, self.asc = v, asc

            def __lt__(self, other):
                a, b = self.v, other.v
                if a is None or b is None:
                    if a is None and b is None:
                        return False
                    return (a is None) != self.asc  # None last when asc
                return (a < b) if self.asc else (b < a)

            def __eq__(self, other):
                # tuple comparison consults secondary keys only when
                # earlier keys compare EQUAL — identity-based equality
                # would freeze ties in input order
                return self.v == other.v

        sort_cols = [(lnames[c], asc) for c, asc, _n in node.sort_channels]

        def order_key(row):
            return tuple(_K(row[n], asc) for n, asc in sort_cols)

        out_rows: List[tuple] = []
        for key in sorted(parts, key=lambda k: tuple(map(repr, k))):
            for mvals in run_match_recognize(
                    parts[key], order_key, list(node.pattern),
                    list(node.defines), list(node.measures),
                    node.after_match):
                out_rows.append(key + mvals)
        if not out_rows:
            # zero-length arrays break downstream gathers: the no-match
            # result is the canonical 1-slot all-dead page
            return Page.all_dead(node.output_types)
        cols = []
        for i, (t, _n) in enumerate(zip(node.output_types, node.output_names)):
            cols.append(Column.from_python(t, [r[i] for r in out_rows]))
        return Page(cols)

    # ------------------------------------------------------------- ordering
    def _exec_SortNode(self, node: P.SortNode) -> Page:
        page = self.execute(node.source)
        return self.sorted_page(page, node.sort_channels)

    def sorted_page(self, page: Page, sort_channels, limit: Optional[int] = None) -> Page:
        """Move rows into sort order (dead rows last); sel becomes a prefix
        mask of the live (and limit-capped) rows. All columns ride the ONE
        payload-carrying sort (sort_ops.sort_payloads) — never a computed-
        permutation gather per column."""
        n = page.num_rows
        if any(c.type.is_nested for c in page.columns):
            # nested columns cannot ride a device payload sort (children
            # re-flatten with data-dependent shapes); sort host-side — this
            # path serves root-level ORDER BY over array_agg/unnest results
            return self._sorted_page_host(page, sort_channels, limit)
        keys = [
            (kl, asc, nf)
            for c, asc, nf in sort_channels
            for kl in _key_lowereds(page.columns[c])
        ]
        payloads = []
        for c in page.columns:
            payloads.append(c.values)
            if c.nulls is not None:
                payloads.append(c.nulls)
            if c.hi is not None:
                payloads.append(c.hi)
        sorted_arrays = sort_ops.sort_payloads(keys, page.sel, payloads)
        live = (
            jnp.asarray(n, dtype=jnp.int64) if page.sel is None else jnp.sum(page.sel)
        )
        if limit is not None:
            live = jnp.minimum(live, limit)
        sel = jnp.arange(n) < live
        cols = []
        i = 0
        for c in page.columns:
            v = sorted_arrays[i]
            i += 1
            nulls = None
            if c.nulls is not None:
                nulls = sorted_arrays[i]
                i += 1
            chi = None
            if c.hi is not None:
                chi = sorted_arrays[i]
                i += 1
            cols.append(Column(c.type, v, nulls, c.dictionary,
                               c.vrange if chi is None else None, hi=chi))
        return Page(cols, sel, page.replicated)

    def _sorted_page_host(self, page: Page, sort_channels, limit=None) -> Page:
        """Host (numpy) ORDER BY for pages carrying nested columns: compact,
        lexsort with SQL null placement (ops/sort.py _sort_key semantics),
        host_take the permutation (which re-flattens children correctly)."""
        from trino_tpu.data.page import host_take

        compacted = page.compact()
        n = compacted.num_rows
        lex_keys = []  # least-significant first for np.lexsort
        for c, asc, nf in reversed(list(sort_channels)):
            col = compacted.columns[c]
            if col.type.is_nested:
                raise NotImplementedError("ORDER BY an array/map column")
            v = host_read(col.values, "host-sort")
            if v.dtype == np.bool_:
                v = v.astype(np.int8)
            if not asc:
                v = -v if np.issubdtype(v.dtype, np.floating) else ~v
            nulls_first = (not asc) if nf is None else nf
            if col.nulls is not None:
                isnull = host_read(col.nulls, "host-sort")
                rank = (~isnull).astype(np.int8) if nulls_first else isnull.astype(np.int8)
                lex_keys.append(np.where(isnull, np.zeros((), v.dtype), v))
                lex_keys.append(rank)
            else:
                lex_keys.append(v)
        order = (
            np.lexsort(lex_keys) if lex_keys else np.arange(n)
        )
        if limit is not None:
            order = order[:limit]
        return Page([host_take(c, order) for c in compacted.columns], None,
                    page.replicated)

    def _exec_TopNNode(self, node: P.TopNNode) -> Page:
        page = self.execute(node.source)
        return self.sorted_page(page, node.sort_channels, limit=node.count)

    def _exec_LimitNode(self, node: P.LimitNode) -> Page:
        page = self.execute(node.source)
        return self.sorted_page(page, [], limit=node.count)

    def _exec_OutputNode(self, node: P.OutputNode) -> Page:
        return self.execute(node.source)


# Aggregates that keep the eager body on a direct layout: the nested
# outputs regroup by a sort, and checksum hashes a dictionary's CONTENT.
_UNFUSED_AGGREGATES = frozenset(
    {"array_agg", "histogram", "map_agg", "checksum"})


@dataclasses.dataclass(frozen=True)
class _AggregationSpec:
    """The static side of ``direct_aggregation``: all an aggregation body
    reads besides its page's arrays. ``jax.jit`` memoises on it, so it
    holds no dictionary content and no value range: splits that differ
    only in those share one program."""

    entry: str  # the Executor method whose body the program is
    group_channels: Tuple[int, ...]
    aggregates: Tuple[P.AggregateCall, ...]
    source_types: Tuple[T.Type, ...]  # partial: accumulator types follow
    page: StaticSpec
    # argument channels whose long-decimal sum stats bound inside int64
    sum_fits: Tuple[int, ...]


class _TracedAggregation(Executor):
    """Executor's aggregation methods with nothing behind them but an
    error list: what ``direct_aggregation`` traces. Not the eager tier, so
    it neither spills nor takes the seam it was called from."""

    eager_tier = False

    def __init__(self, sum_fits):
        self.errors = []
        self.sum_fits = sum_fits

    def _sum_fits_int64(self, page, channel, n):
        return channel in self.sum_fits


@functools.partial(jax.jit, static_argnums=0)
def direct_aggregation(spec: _AggregationSpec, arrays):
    """One aggregation body over one page as one XLA program: (output
    arrays, their StaticSpec with the deferred errors' codes as notes, the
    errors' flags)."""
    body = _TracedAggregation(spec.sum_fits)
    node = types.SimpleNamespace(
        group_channels=list(spec.group_channels),
        aggregates=list(spec.aggregates),
        source=types.SimpleNamespace(output_types=list(spec.source_types)))
    out = getattr(body, spec.entry)(
        node, unflatten_page(spec.page.page_spec(), arrays))
    out_arrays, out_spec = flatten_page(out)
    return (out_arrays,
            static_spec(out_spec, tuple(code for code, _ in body.errors)),
            [flag for _, flag in body.errors])


@dataclasses.dataclass
class QueryResult:
    column_names: List[str]
    columns: List[Column]
    rows: List[tuple]

    def __repr__(self):
        return f"QueryResult({self.column_names}, {len(self.rows)} rows)"
