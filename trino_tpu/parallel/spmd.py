"""SPMD distributed execution: one `shard_map` program per query body.

Reference: the distributed data plane — splits scheduled across workers
(SourcePartitionedScheduler), hash-repartition shuffles between stages
(PartitionedOutputOperator -> HTTP -> ExchangeOperator, SURVEY.md §2.6/§3.4).
TPU-first redesign (SURVEY.md §7.1 "shuffle = collective"): the whole
multi-stage pipeline compiles into a single SPMD program over a device mesh:

- leaf scans = data-parallel splits, one shard per device (padded to a
  common shape; the pad rows carry sel=False) — SOURCE_DISTRIBUTION analog;
- low-cardinality aggregation = local partial aggregate, `all_gather` of the
  (small) partial-state pages over ICI, local final aggregate — the
  partial/FINAL split HashAggregationOperator does across an exchange;
- high-cardinality aggregation = hash-repartition raw rows by group-key
  hash (`all_to_all`, parallel/exchange.py — FIXED_HASH_DISTRIBUTION),
  aggregate locally, keep the result sharded;
- join build sides: `all_gather` (FIXED_BROADCAST_DISTRIBUTION) when small,
  else co-partition both sides by key hash and join locally (partitioned
  join) — the DetermineJoinDistributionType choice, from connector stats;
- sort/topN/limit run on the gathered (replicated) result.

Collectives ride ICI inside the compiled program — there is no serialized
page shuttle between stages on this path.
"""
from __future__ import annotations

import dataclasses
import time as _time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec

from trino_tpu import types as T
from trino_tpu.connector import spi as spi_mod
from trino_tpu.data.page import Column, Page
from trino_tpu.data import page as page_mod
from trino_tpu.exec.executor import Executor, QueryError, _col_to_lowered
from trino_tpu.exec.page_tree import ColSpec, PageSpec, flatten_page, unflatten_page
from trino_tpu.ops import aggregate as agg_ops
from trino_tpu.ops import groupby as gb
from trino_tpu.ops import ranks as ranks_ops
from trino_tpu.sql.planner import plan as P

AXIS = "d"


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (error flags are
    replicated by construction, the checker can't see it)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _mesh_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (device) axis split over the mesh, the rest replicated —
    the layout of every staged [ndev, rows, ...] scan array."""
    return NamedSharding(mesh, PSpec(AXIS))


def _gather_flat(x: jnp.ndarray) -> jnp.ndarray:
    """all_gather along the mesh axis and flatten device dim into rows."""
    g = jax.lax.all_gather(x, AXIS)  # [ndev, n, ...]
    return g.reshape((-1,) + g.shape[2:])


def gather_page(page: Page) -> Page:
    """Replicate a sharded page on every device (broadcast exchange).
    Idempotent: already-replicated pages pass through."""
    if page.replicated:
        return page
    cols = [
        Column(
            c.type,
            _gather_flat(c.values),
            _gather_flat(c.nulls) if c.nulls is not None else None,
            c.dictionary,
            c.vrange,
            hi=_gather_flat(c.hi) if c.hi is not None else None,
        )
        for c in page.columns
    ]
    sel = (
        _gather_flat(page.sel)
        if page.sel is not None
        else None
    )
    return Page(cols, sel, replicated=True)


class SpmdExecutor(Executor):
    """Runs the plan per-shard inside shard_map; exchanges are collectives.

    Distribution choice per exchange (reference: AddExchanges.java:138 +
    DetermineJoinDistributionType): broadcast (all_gather) for small build
    sides / low-cardinality aggregations, hash repartition (all_to_all,
    parallel/exchange.py) when stats say the data is too big to replicate —
    the same predicates (sql/planner/stats.py) drive build-time capacity
    hints, so the trace always finds its hints."""

    eager_tier = False  # runs under jax tracing: no host-side syncs
    enable_dynamic_filtering = False  # scans pre-staged before tracing
    collect_stats = False  # tracing once; per-call timing is meaningless

    def __init__(self, session, staged: Dict[int, Page], capacity_hints=None, n_devices: int = 1):
        super().__init__(session, capacity_hints)
        self.staged = staged
        self.n_devices = n_devices

    def _exec_TableScanNode(self, node: P.TableScanNode) -> Page:
        return self.staged[node.id]

    # ------------------------------------------------------ hash exchange
    def _repartition(self, page: Page, key_channels, hint_key: str) -> Page:
        from trino_tpu.parallel import exchange

        page = self._narrowed_for_exchange(page)
        capacity = self.hint_capacity(hint_key, None)
        out, overflow = exchange.repartition_page(
            page, key_channels, self.n_devices, capacity, AXIS
        )
        self.errors.append((f"CAPACITY_EXCEEDED:{hint_key}", overflow))
        return out

    def _join_repartitioned(self, node: P.JoinNode, left: Page, right: Page):
        """Co-partition both join sides by key hash when stats prefer it and
        neither side is already replicated. Returns None to fall back to the
        broadcast path."""
        from trino_tpu.sql.planner import stats

        if left.replicated or right.replicated:
            return None
        if not stats.join_repartitions(self.session, node, self.n_devices):
            return None
        left2 = self._repartition(left, node.left_keys, f"xchgl:{node.id}")
        right2 = self._repartition(right, node.right_keys, f"xchgr:{node.id}")
        return left2, right2

    # ----------------------------------------------------- distributed agg
    def aggregate_page(self, node: P.AggregationNode, page: Page) -> Page:
        """Low cardinality: partial aggregate -> all_gather partial states ->
        final combine (HashAggregationOperator PARTIAL -> exchange -> FINAL).
        High cardinality: hash-repartition RAW rows by group key, aggregate
        single-step locally, output stays sharded (the partial step would not
        reduce — the SkipAggregationBuilder insight). DISTINCT aggregates
        can't be split: gather raw rows and aggregate single-step."""
        from trino_tpu.sql.planner import stats

        if page.replicated:
            # every device already holds all rows: single-step local aggregate
            return super().aggregate_page(node, page)
        if stats.agg_repartitions(self.session, node, self.n_devices):
            page2 = self._repartition(page, node.group_channels, f"xchg:{node.id}")
            return Executor.aggregate_page(self, node, page2)  # sharded out
        if not P.can_split_aggs(node.aggregates):
            return super().aggregate_page(node, gather_page(page))
        partial = self.aggregate_partial(node, page)
        gathered = gather_page(partial)
        final = P.AggregationNode(
            None, list(range(len(node.group_channels))), node.aggregates,
            step="final", names=node.names,
        )
        out = self.aggregate_final(final, gathered)
        return Page(out.columns, out.sel, replicated=True)

    # -------------------------------------------------- distributed joins
    def _overlap_blocks(self) -> int:
        props = getattr(self.session, "properties", None) or {}
        return int(props.get("exchange_overlap_blocks", 0) or 0)

    def _narrowed_for_exchange(self, page: Page) -> Page:
        """Two-limb columns degrade to low words with the deferred
        overflow check before any device exchange (no limb lanes)."""
        if not any(c.hi is not None for c in page.columns):
            return page
        return Page(
            [self._narrowed_or_flag(c, page.sel) for c in page.columns],
            page.sel, page.replicated, live_prefix=page.live_prefix,
        )

    def _overlapped_join(self, node: P.JoinNode, left: Page, right: Page,
                         semi: bool) -> Optional[Page]:
        """Partitioned lookup/semi join with the PROBE-side exchange
        pipelined against join compute: the build side co-partitions
        first (it must be complete before any probe row can match), then
        the probe side ships in ``exchange_overlap_blocks`` double-
        buffered send blocks — the ``all_to_all`` for block k+1 issues
        before the join kernel consumes block k, so ICI transfer and
        compute overlap instead of running as exchange-then-compute
        phases. Build artifacts (the dense table or the sorted build) are
        hoisted OUT of the per-block consume, so the per-block work is
        pure probe. Bit-identical to the unoverlapped path: the consume
        is row-local and the block outputs restack to the one-shot row
        order (exchange._restack_blocks). Returns None when the pipeline
        doesn't apply (disabled, broadcast distribution, replicated
        inputs)."""
        from trino_tpu.obs import metrics as M
        from trino_tpu.obs import trace as tracing
        from trino_tpu.ops import join as join_ops
        from trino_tpu.parallel import exchange
        from trino_tpu.sql.planner import stats

        blocks = self._overlap_blocks()
        if blocks <= 1 or left.replicated or right.replicated:
            return None
        if not self._fused_join_enabled():
            # the per-block consume rides the fused module's merge tier;
            # disabling the fused tier must disable the pipeline too (the
            # kill switch covers ALL new join-kernel code paths)
            return None
        if not stats.join_repartitions(self.session, node, self.n_devices):
            return None
        right2 = self._repartition(right, node.right_keys, f"xchgr:{node.id}")
        left = self._narrowed_for_exchange(left)
        capacity = self.hint_capacity(f"xchgl:{node.id}", None)
        # ---- build artifacts, hoisted out of the per-block consume (the
        # per-block work must be pure probe: one dense table / membership
        # LUT / sorted build, shared by every block)
        dense = self._dense_join_cols(node, left, right2)
        table = lut = build = None
        if dense is not None:
            bc, pc, lo, span = dense
            if semi:
                lut = join_ops.dense_membership_table(
                    _col_to_lowered(bc), right2.sel, lo, span)
            else:
                table = join_ops.dense_unique_table(
                    _col_to_lowered(bc), right2.sel, lo, span)
            M.FUSED_JOIN_SELECTIONS.inc(1, "dense")
        else:
            bk, _pk = self._join_keys_aligned(
                left, right2, node.left_keys, node.right_keys)
            build = join_ops.build_side(
                bk, right2.sel,
                presorted=self._build_presorted(right2, node.right_keys))
        recorded = [False]  # first consume records the merge-tier selection

        def consume(lp: Page) -> Page:
            if dense is not None:
                bc, pc, lo, span = dense
                plowered = _col_to_lowered(lp.columns[node.left_keys[0]])
                if semi:
                    hit = join_ops.dense_membership_probe(lut, plowered, lo)
                else:
                    rows, matched = join_ops.dense_probe_unique(
                        table, plowered, lo)
            else:
                bkeys, pkeys = self._join_keys_aligned(
                    lp, right2, node.left_keys, node.right_keys)
                # the tier selection is counted ONCE per join (first
                # block), not once per send block
                rows, matched = self._merge_sorted_tier(
                    node, lp, right2, build, bkeys, pkeys,
                    record=not recorded[0])
                recorded[0] = True
                if semi:
                    hit = matched
            if semi:
                keep = hit if node.join_type == "semi" else ~hit
                sel = keep if lp.sel is None else lp.sel & keep
                return Page(lp.columns, sel, lp.replicated)
            return self._assemble_lookup_output(
                node, lp, right2, rows, matched)

        with tracing.span("exchange/overlap") as sp:
            sp.set("blocks", blocks)
            sp.set("join", node.id)
            out, overflow = exchange.repartition_page_overlapped(
                left, node.left_keys, self.n_devices, capacity, AXIS,
                blocks, consume)
        self.errors.append((f"CAPACITY_EXCEEDED:xchgl:{node.id}", overflow))
        M.EXCHANGE_OVERLAPPED.inc(1, str(blocks))
        return out

    def _join_compacts_match(self, source: P.PlanNode) -> bool:
        """Never here: this tier's lookup join is an exchange first (the
        overlapped pipeline consumes probe BLOCKS through the row-local
        ``_assemble_lookup_output``, the other two move a side across the
        mesh), so a Compact on a join squeezes the joined shard page as
        it does on any other source."""
        return False

    def lookup_join(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        out = self._overlapped_join(node, left, right, semi=False)
        if out is not None:
            return out
        rp = self._join_repartitioned(node, left, right)
        if rp is not None:
            return Executor.lookup_join(self, node, *rp)
        # broadcast exchange: replicate the (small, unique-keyed) build side
        return super().lookup_join(node, left, gather_page(right))

    def semi_join(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        out = self._overlapped_join(node, left, right, semi=True)
        if out is not None:
            return out
        rp = self._join_repartitioned(node, left, right)
        if rp is not None:
            return Executor.semi_join(self, node, *rp)
        return super().semi_join(node, left, gather_page(right))

    def singleton_cross(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        return super().singleton_cross(node, left, gather_page(right))

    def expand_join(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        rp = self._join_repartitioned(node, left, right)
        if rp is not None:
            return Executor.expand_join(self, node, *rp)
        # M:N expansion probes stay local; the build side is broadcast.
        # Stats-estimated capacity hints upper-bound every shard's local
        # match count (probe shard ⊆ all probes).
        return super().expand_join(node, left, gather_page(right))

    def semi_join_filtered(self, node: P.JoinNode, left: Page, right: Page) -> Page:
        rp = self._join_repartitioned(node, left, right)
        if rp is not None:
            return Executor.semi_join_filtered(self, node, *rp)
        return super().semi_join_filtered(node, left, gather_page(right))

    # ----------------------------------------------------------- set ops
    def _exec_UnionNode(self, node) -> Page:
        """UNION ALL of shards is the union of per-shard concatenations —
        unless replication statuses differ, where local concat would
        multiply the replicated side; gather everything then."""
        pages = [self.execute(s) for s in node.sources_]
        if len({p.replicated for p in pages}) > 1:
            pages = [gather_page(p) for p in pages]
        return Page.concat_all(pages)

    def set_op_pages(self, node, left: Page, right: Page) -> Page:
        """Whole-row membership needs equal rows co-located: big inputs
        co-partition by row hash over ALL columns (NULLs hash to a constant
        so set-semantics NULL equality survives the exchange); the combined
        page carries an explicit side-tag column through the shuffle. Small
        inputs gather (cheaper than an exchange)."""
        from trino_tpu.sql.planner import stats

        if (left.replicated or right.replicated
                or not stats.setop_repartitions(self.session, node, self.n_devices)):
            return super().set_op_pages(node, gather_page(left), gather_page(right))
        both = Page.concat_pages(left, right)
        n_l = left.num_rows
        side = jnp.arange(both.num_rows, dtype=jnp.int32) >= n_l
        tagged = Page(
            both.columns + [Column(T.BOOLEAN, side)], both.sel, both.replicated
        )
        recv = self._repartition(
            tagged, list(range(both.channel_count)), f"xchgs:{node.id}"
        )
        body = Page(recv.columns[:-1], recv.sel, recv.replicated)
        return self._set_op_grouped(node, body, recv.columns[-1].values)

    # --------------------------------------------------- distributed sort
    def _exec_TopNNode(self, node: P.TopNNode) -> Page:
        """Distributed top-N: per-shard top-N (the global top-N is a subset
        of the union of shard top-Ns), all_gather the N*D survivors (tiny),
        final local sort. The reference's TopNOperator-per-task + single
        merge consumer (MergeOperator), without gathering full shards."""
        page = self.execute(node.source)
        if page.replicated:
            return Executor.sorted_page(self, page, node.sort_channels, node.count)
        local = Executor.sorted_page(self, page, node.sort_channels, node.count)
        gathered = gather_page(_take_prefix(local, node.count))
        return Executor.sorted_page(self, gathered, node.sort_channels, node.count)

    def _exec_LimitNode(self, node: P.LimitNode) -> Page:
        """LIMIT without ordering: any N rows qualify — take N per shard,
        gather only those."""
        page = self.execute(node.source)
        if page.replicated:
            return Executor.sorted_page(self, page, [], node.count)
        local = Executor.sorted_page(self, page, [], node.count)
        gathered = gather_page(_take_prefix(local, node.count))
        return Executor.sorted_page(self, gathered, [], node.count)

    def _exec_SortNode(self, node: P.SortNode) -> Page:
        """Full ORDER BY: big inputs range-partition by sampled splitters
        and sort locally — the output stays SHARDED, globally ordered by
        device index (the reference's range exchange + ordered-merge
        consumer, redesigned: the 'merge' IS the mesh's device order,
        realized as concatenation order when the root gathers). Small
        inputs gather and sort locally."""
        from trino_tpu.sql.planner import stats

        page = self.execute(node.source)
        if page.replicated or not stats.sort_repartitions(
                self.session, node.source, self.n_devices):
            return Executor.sorted_page(self, gather_page(page), node.sort_channels)
        recv = self._range_exchange(page, node.sort_channels, f"xchgo:{node.id}")
        return Executor.sorted_page(self, recv, node.sort_channels)

    SORT_SAMPLES_PER_SHARD = 32

    def _range_exchange(self, page: Page, sort_channels, hint_key: str) -> Page:
        """Route rows to devices by lexicographic comparison against
        sampled splitters, so device d receives exactly the d-th key range.
        Splitters come from per-shard evenly spaced samples of the locally
        sorted keys, all_gathered and re-sampled — the classic sample-sort
        recipe; skew beyond the capacity hint doubles-and-recompiles."""
        from trino_tpu.ops import sort as sort_ops
        from trino_tpu.parallel import exchange

        n = page.num_rows
        live = page.sel if page.sel is not None else jnp.ones((n,), bool)
        keys = [
            ((page.columns[c].values,
              None if page.columns[c].nulls is None else ~page.columns[c].nulls),
             asc, nf)
            for c, asc, nf in sort_channels
        ]
        t_ops = sort_ops._sort_operands(keys, None)  # ascending-comparable
        # local live-first key sort -> evenly spaced live samples
        s_ops = ranks_ops.stable_sort([~live] + t_ops, 1 + len(t_ops))[1:]
        nlive = jnp.maximum(jnp.sum(live).astype(jnp.int32), 1)
        m = self.SORT_SAMPLES_PER_SHARD
        pos = jnp.clip(
            ((jnp.arange(m, dtype=jnp.int32) * 2 + 1) * nlive) // (2 * m), 0, n - 1
        )
        samples = [o[pos] for o in s_ops]
        gath = [jax.lax.all_gather(s, AXIS).reshape(-1) for s in samples]
        gsorted = ranks_ops.stable_sort(gath, len(gath))
        total = m * self.n_devices
        sp_pos = (jnp.arange(1, self.n_devices, dtype=jnp.int32) * total) // self.n_devices
        splitters = [g[sp_pos] for g in gsorted]
        # pid = number of splitters the row is lexicographically greater
        # than (ties co-locate on the lower device)
        pid = jnp.zeros((n,), jnp.int32)
        for d in range(self.n_devices - 1):
            gt = jnp.zeros((n,), bool)
            eq = jnp.ones((n,), bool)
            for o, sp in zip(t_ops, splitters):
                gt = gt | (eq & (o > sp[d]))
                eq = eq & (o == sp[d])
            pid = pid + gt.astype(jnp.int32)
        capacity = self.hint_capacity(hint_key, None)
        out, overflow = exchange.repartition_by_pid(
            page, pid, self.n_devices, capacity, AXIS
        )
        self.errors.append((f"CAPACITY_EXCEEDED:{hint_key}", overflow))
        return out

    def sorted_page(self, page: Page, sort_channels, limit=None) -> Page:
        return super().sorted_page(gather_page(page), sort_channels, limit)

    def window_over_page(self, node, page: Page) -> Page:
        """Windows need whole partitions co-located: big partitioned inputs
        hash-repartition by the PARTITION BY keys; global frames (no
        partition keys) and small inputs gather."""
        from trino_tpu.sql.planner import stats

        if (page.replicated
                or not stats.window_repartitions(self.session, node, self.n_devices)):
            return super().window_over_page(node, gather_page(page))
        recv = self._repartition(page, node.partition_channels, f"xchgw:{node.id}")
        return Executor.window_over_page(self, node, recv)


def _take_prefix(page: Page, k: int) -> Page:
    """First k slots of a page (static slice; sorted pages carry their live
    rows as a prefix)."""
    k = min(k, page.num_rows)
    return Page(
        [
            Column(c.type, c.values[:k],
                   None if c.nulls is None else c.nulls[:k],
                   c.dictionary, c.vrange)
            for c in page.columns
        ],
        page.sel[:k] if page.sel is not None else None,
        page.replicated,
    )


def stage_sharded_scans(session, root: P.OutputNode, n_devices: int,
                        dyn_domains=None, profile=None, mesh=None):
    """Enumerate splits per scan, load per-device shards, pad to a common
    per-device shape, stack [ndev, rows]. This is the SOURCE_DISTRIBUTION
    split assignment done statically. ``dyn_domains`` carries phase-1
    resolved dynamic-filter domains (exec/host_eval.py) — the reference's
    split-time DynamicFilter blocking, realised as two-phase execution:
    probe splits are enumerated AND row-filtered under the build-side key
    domains before any device sees them.

    Each scan's stacked shard arrays consult the device table cache
    (trino_tpu/devcache/) first: a warm entry skips split enumeration,
    generation/IO, dynamic-domain pruning, AND the host->device transfer
    — the shard component of the key pins the mesh width, so a cache
    built for one device count never serves another.

    With ``mesh`` the stacked arrays go host -> device ALREADY SHARDED
    along the mesh axis (shard i straight to device i); without it they
    land whole on the default device (single-device callers and tests)."""
    from trino_tpu import devcache
    from trino_tpu.exec.executor import (
        dynamic_domain_map, scan_constraint_with)

    dyn_domains = dyn_domains or {}
    staged: Dict[int, List] = {}
    specs: Dict[int, PageSpec] = {}
    for node in P.walk_plan(root):
        if not isinstance(node, P.TableScanNode):
            continue
        constraint = scan_constraint_with(node, dyn_domains)

        def load(node=node, constraint=constraint):
            from trino_tpu.exec import staging as _staging
            from trino_tpu.obs import metrics as _M
            from trino_tpu.obs import trace as _tracing

            arrays, spec, total_rows = _stage_scan_shards(
                session, node, n_devices, constraint, dyn_domains, profile)
            # cache-resident arrays live ON DEVICE: transfer here (a
            # no-op for already-device arrays), so a warm hit hands back
            # HBM-resident shards with zero host work. Each stacked
            # [ndev, rows] shard array is put once, already sharded along
            # the mesh axis where there is a mesh, and the scan waits once
            # for all of them (exec/staging.PagePuts).
            t0 = _time.perf_counter()
            with _tracing.span("staging/transfer", table=node.table) as sp:
                sharding = _mesh_sharding(mesh) if mesh is not None else None
                with _staging.PagePuts() as puts:
                    arrays = [puts.put(a, sharding)
                              if isinstance(a, np.ndarray)
                              else jax.device_put(a, sharding)
                              for a in arrays]
                sp.set("arrays", len(arrays))
                sp.set("puts", puts.count)
                sp.set("bytes", puts.nbytes)
            _M.STAGING_PHASE_SECONDS.inc(_time.perf_counter() - t0,
                                         "transfer")
            nbytes = sum(int(a.size) * a.dtype.itemsize for a in arrays)
            return (arrays, spec, total_rows), total_rows, nbytes, n_devices

        ent, _disposition = devcache.cached_stage(
            session, node, constraint, dynamic_domain_map(node, dyn_domains),
            f"spmd:{n_devices}", load)
        arrays, spec, total_rows = ent.value
        staged[node.id] = arrays
        specs[node.id] = spec
        node.runtime_rows = total_rows  # staged truth for capacity estimates
    return staged, specs


def _stage_scan_shards(session, node, n_devices: int, constraint,
                       dyn_domains, profile=None):
    """Stage ONE scan's per-device shards: ``(arrays, PageSpec,
    total_rows)`` — the cold path behind the device-cache loader. Split
    reads run through the pipelined engine (exec/staging.py): the
    adaptive target fans big tables out FINER than the mesh (contiguous
    fine-split groups per device), every fine split consults the host-RAM
    tier — so a mesh-width change regroups warm host entries instead of
    re-running the connector — and scans overlap on the shared pool."""
    from trino_tpu.exec import staging
    from trino_tpu.exec.executor import (
        apply_dynamic_domains, dynamic_domain_map)

    conn = session.catalogs[node.catalog]
    target = staging.target_split_count(
        session, conn, node.schema, node.table, floor=n_devices,
        handle=node.table_handle)
    splits = conn.get_splits(
        node.schema, node.table, target, constraint=constraint,
        handle=node.table_handle)

    def prune(datas):
        return apply_dynamic_domains(node, dyn_domains, datas)

    split_datas, prof = staging.stage_splits(
        session, node, conn, splits, constraint, prune=prune,
        applied_domains=dynamic_domain_map(node, dyn_domains))
    if profile is not None:
        profile["df_apply_s"] = (
            profile.get("df_apply_s", 0.0) + prof.prune_s)
    # contiguous split groups per device: with <= n_devices splits, split
    # i stages on device i (the historical assignment — bit-compatible
    # with the pre-pipeline layout); finer adaptive split sets group into
    # n_devices contiguous covers so each shard still reads an ascending
    # key range and per-shard sortedness survives the concat
    if len(split_datas) <= n_devices:
        groups = [[split_datas[i]] if i < len(split_datas) else []
                  for i in range(n_devices)]
    else:
        bounds = [len(split_datas) * i // n_devices
                  for i in range(n_devices + 1)]
        groups = [split_datas[bounds[i]:bounds[i + 1]]
                  for i in range(n_devices)]
    total_rows = 0
    shard_pages = []
    for di in range(n_devices):
        group = [d for d in groups[di] if d]
        if group:
            data = group[0] if len(group) == 1 else {
                name: spi_mod.concat_column_data([g[name] for g in group])
                for name in node.column_names
            }
            if data:
                total_rows += len(next(iter(data.values())).values)
        else:
            # devices beyond the split count scan NOTHING. Built here
            # from the scan node's own schema — no connector round-trip:
            # a synthetic empty Split would either clobber a pushdown
            # handle riding Split.info (breaking schema resolution for
            # pushed aggregations) or, preserved, re-run a GLOBAL pushed
            # statement on every extra device (duplicating rows).
            from trino_tpu.data.page import Column as _Col

            data = {
                name: spi_mod.column_data_from_column(
                    _Col.from_python(typ, []))
                for name, typ in zip(node.column_names, node.column_types)
            }
        cols = []
        for name, typ in zip(node.column_names, node.column_types):
            cd = data[name]
            vals = np.asarray(cd.values)
            # physical narrowing, same rule as staging.put_page:
            # table-wide ranges keep every shard dtype-uniform
            if vals.dtype == np.int64 and page_mod.fits_int32(cd.vrange):
                vals = vals.astype(np.int32)
            cols.append(
                Column(
                    typ,
                    vals,
                    np.asarray(cd.nulls) if cd.nulls is not None else None,
                    cd.dictionary,
                    cd.vrange,
                    hi=np.asarray(cd.hi) if cd.hi is not None else None,
                )
            )
        shard_pages.append(cols)
    max_rows = max((len(c[0].values) if c else 0) for c in shard_pages)
    max_rows = max(max_rows, 1)
    # unify per-shard dictionaries: codes must mean the same string on
    # every device (the "stable dictionary ids" FTE determinism concern,
    # SURVEY.md §7.3 item 8)
    for ci, typ in enumerate(node.column_types):
        if not typ.is_varchar:
            continue
        merged = shard_pages[0][ci].dictionary
        for p in shard_pages[1:]:
            if p[ci].dictionary.values != merged.values:
                merged = merged.merge(p[ci].dictionary)
        for p in shard_pages:
            d = p[ci].dictionary
            if d.values != merged.values:
                table = np.asarray(d.recode_table(merged))
                codes = np.asarray(p[ci].values)
                p[ci] = Column(
                    typ,
                    np.where(codes >= 0, table[np.clip(codes, 0, None)], -1).astype(np.int32),
                    p[ci].nulls,
                    merged,
                )
            else:
                p[ci] = Column(typ, p[ci].values, p[ci].nulls, merged)
    stacked_cols = []
    for ci in range(len(node.column_names)):
        anyhi = any(p[ci].hi is not None for p in shard_pages)
        vals = np.stack(
            [
                _pad(np.asarray(p[ci].values).astype(np.int64)
                     if anyhi else np.asarray(p[ci].values), max_rows)
                for p in shard_pages
            ]
        )
        anynull = any(p[ci].nulls is not None for p in shard_pages)
        nulls = (
            np.stack(
                [
                    _pad(
                        np.asarray(p[ci].nulls)
                        if p[ci].nulls is not None
                        else np.zeros(len(p[ci].values), bool),
                        max_rows,
                    )
                    for p in shard_pages
                ]
            )
            if anynull
            else None
        )
        # hi-limb presence must be uniform across shards (the PageSpec
        # is static): missing shards sign-extend their low words
        hi = (
            np.stack(
                [
                    _pad(
                        np.asarray(p[ci].hi)
                        if p[ci].hi is not None
                        else (np.asarray(p[ci].values).astype(np.int64) >> 63),
                        max_rows,
                    )
                    for p in shard_pages
                ]
            )
            if anyhi
            else None
        )
        stacked_cols.append((vals, nulls, hi, shard_pages[0][ci].dictionary))
    sel = np.stack(
        [
            np.arange(max_rows) < len(p[0].values) if p else np.zeros(max_rows, bool)
            for p in shard_pages
        ]
    )
    arrays = []
    col_specs = []
    vranges = [c.vrange for c in shard_pages[0]]
    for (vals, nulls, hi, d), typ, vr in zip(
            stacked_cols, node.column_types, vranges):
        arrays.append(vals)
        if nulls is not None:
            arrays.append(nulls)
        if hi is not None:
            arrays.append(hi)
        col_specs.append(ColSpec(
            typ, d, nulls is not None, vr, has_hi=hi is not None))
    arrays.append(sel)
    return arrays, PageSpec(col_specs, True), total_rows


def _pad(a: np.ndarray, n: int) -> np.ndarray:
    if len(a) == n:
        return a
    pad = np.zeros((n - len(a),) + a.shape[1:], dtype=a.dtype)
    return np.concatenate([a, pad])


@dataclasses.dataclass
class DistributedQuery:
    """A query compiled to one shard_map program over a device mesh."""

    mesh: Mesh
    fn: object
    inputs: List
    out_spec_cell: List
    error_codes_cell: List
    session: object = None
    root: P.OutputNode = None
    capacity_hints: Dict[str, int] = dataclasses.field(default_factory=dict)
    # two-phase profile (see CompiledQuery): benchmarks charge this host
    # time to every run — it is query work done off-device
    phase1_s: float = 0.0
    df_apply_s: float = 0.0
    # capacity-overflow regrowth recompiles (0 when the hints were right
    # the first time — e.g. under adaptive_capacity_reseed)
    recompiles: int = 0
    # kernel-ledger rollup (obs/devprofiler.py): one "SpmdBody" row
    # accumulating this query's shard_map-body dispatches
    kernel_stats: Dict[tuple, dict] = dataclasses.field(default_factory=dict)
    # compile-ledger identity, computed lazily once per instance
    _fingerprint: str = ""

    MAX_RECOMPILES = 16

    @classmethod
    def build(
        cls, session, root: P.OutputNode, mesh: Mesh, capacity_hints: Dict[str, int] = None
    ) -> "DistributedQuery":
        """Two-phase compile (see CompiledQuery.build): phase 1 host-resolves
        dynamic-filter domains, scans stage narrowed, and capacities estimate
        from staged truth (global totals upper-bound each shard); overflow at
        runtime doubles the bucket and recompiles (see CompiledQuery.run)."""
        from trino_tpu.exec import host_eval
        from trino_tpu.sql.planner import stats

        n_devices = mesh.devices.size
        # a ROOT-level ORDER BY over nested (array/map/row) outputs cannot
        # sort under tracing (the nested host-sort fallback needs concrete
        # arrays); peel it off the traced plan and apply it host-side after
        # the gather — semantically identical (the sort is the last step)
        post_sort = None
        if (isinstance(root.source, P.SortNode)
                and any(t.is_nested for t in root.source.output_types)):
            post_sort = list(root.source.sort_channels)
            root = P.OutputNode(root.source.source, root.column_names)
        t0 = _time.perf_counter()
        dyn = host_eval.resolve_dynamic_filters(session, root)
        phase1_s = _time.perf_counter() - t0
        prof: Dict[str, float] = {}
        staged_arrays, specs = stage_sharded_scans(
            session, root, n_devices, dyn, profile=prof, mesh=mesh)
        if capacity_hints is None:
            capacity_hints = stats.estimate_capacity_hints(session, root)
            capacity_hints.update(stats.estimate_exchange_hints(session, root, n_devices))
        from trino_tpu.adaptive.reseed import (
            apply_reseed, reseed_enabled, staged_pages_from_arrays)

        if reseed_enabled(session):
            # adaptive capacity reseeding: per-(shard, partition) key
            # histograms of the STAGED rows price expansion joins and the
            # hash-exchange send blocks exactly — skewed keys size their
            # real hot partition instead of the 2x-uniform guess, so the
            # run loop never pays a regrowth recompile
            pages = staged_pages_from_arrays(staged_arrays, specs)
            apply_reseed(session, root, pages, n_devices, capacity_hints)
        layout = [(nid, len(arrs)) for nid, arrs in staged_arrays.items()]
        flat_inputs: List = []
        # a warm device-cache entry may have been staged for another mesh
        # of this width (or for none): device_put is a no-op for arrays
        # already sharded over THIS mesh and a reshard otherwise
        for _, arrs in staged_arrays.items():
            flat_inputs.extend(
                jax.device_put(a, _mesh_sharding(mesh)) for a in arrs)
        dq = cls(mesh, None, flat_inputs, [None], [None], session, root, dict(capacity_hints))
        dq.phase1_s = phase1_s
        dq.df_apply_s = prof.get("df_apply_s", 0.0)
        dq._layout = layout
        dq._specs = specs
        dq._post_sort = post_sort
        dq._jit()
        return dq

    def _jit(self):
        session, root = self.session, self.root
        layout, specs, hints = self._layout, self._specs, self.capacity_hints
        out_spec_cell, error_codes_cell = self.out_spec_cell, self.error_codes_cell

        def per_shard(flat):
            # flat arrays arrive with the device axis stripped by shard_map
            pages: Dict[int, Page] = {}
            i = 0
            for nid, count in layout:
                local = [a.reshape(a.shape[1:]) for a in flat[i : i + count]]
                pages[nid] = unflatten_page(specs[nid], local)
                i += count
            ex = SpmdExecutor(session, pages, dict(hints), n_devices=self.mesh.devices.size)
            out_page = ex.execute(root)
            if not out_page.replicated:
                # scan/filter/project-only plans never hit an exchange:
                # gather so run() sees the full result, not shard 0's slice
                out_page = gather_page(out_page)
            out_arrays, out_spec = flatten_page(out_page)
            out_spec_cell[0] = out_spec
            error_codes_cell[0] = [c for c, _ in ex.errors]
            # re-add a leading device axis so out_specs can shard it
            return (
                [a[None] for a in out_arrays],
                [f[None] for _, f in ex.errors],
            )

        shard_fn = _shard_map(
            per_shard,
            mesh=self.mesh,
            in_specs=(PSpec(AXIS),),
            out_specs=(PSpec(AXIS), PSpec(AXIS)),
        )
        self.fn = jax.jit(shard_fn)
        # compile-cache state (see CompiledQuery._jit): the next call on
        # this jitted callable traces + compiles (a miss); later calls
        # reuse the executable (hits) — the compile ledger records both
        self._executable_fresh = True

    def _profile_run(self, fresh: bool, dispatch_wall_s: float,
                     body_device_s: float, estimated: bool) -> None:
        """Feed the device profiler: one compile-ledger event per run + a
        ``SpmdBody`` kernel row. Best-effort — accounting never fails."""
        try:
            from trino_tpu.cache.plan_key import plan_fingerprint
            from trino_tpu.obs.devprofiler import (
                DEVICE_PROFILER, shape_signature)

            if not self._fingerprint:
                self._fingerprint = plan_fingerprint(self.root)
            DEVICE_PROFILER.record_compile(
                "spmd", self._fingerprint, shape_signature(self.inputs),
                dispatch_wall_s if fresh else 0.0,
                "miss" if fresh else "hit", started=fresh)
            wall = (body_device_s if fresh
                    else dispatch_wall_s + (0.0 if estimated
                                            else body_device_s))
            key = (str(self.root.id), "SpmdBody", "spmd")
            ks = self.kernel_stats.get(key)
            if ks is None:
                ks = self.kernel_stats[key] = {
                    "planNodeId": key[0], "operator": key[1],
                    "tier": "spmd", "launches": 0, "wallS": 0.0,
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
        from trino_tpu.exec.executor import QueryError, raise_query_errors
        from trino_tpu.sql.planner import stats

        for _ in range(self.MAX_RECOMPILES):
            fresh = getattr(self, "_executable_fresh", False)
            if fresh:
                try:
                    from trino_tpu.obs.devprofiler import DEVICE_PROFILER

                    DEVICE_PROFILER.compile_started()
                except Exception:  # noqa: BLE001 — accounting only
                    pass
            t0 = _time.perf_counter()
            out_arrays, error_flags = self.fn(self.inputs)
            dispatch_s = _time.perf_counter() - t0
            props = getattr(self.session, "properties", None) or {}
            sync = bool(props.get("device_profiling", False))
            body_device_s = 0.0 if fresh else dispatch_s
            estimated = True
            if sync:
                t_sync = _time.perf_counter()
                try:
                    jax.block_until_ready(out_arrays)
                except Exception:  # noqa: BLE001 — profiling never fails
                    pass
                body_device_s = _time.perf_counter() - t_sync
                estimated = False
            self._profile_run(fresh, dispatch_s, body_device_s, estimated)
            self._executable_fresh = False
            codes = self.error_codes_cell[0]
            # flags are stacked per device: overflow on ANY shard grows the
            # bucket (capacity first — other flags may be truncation artifacts)
            grown = stats.grow_overflowed_hints(self.capacity_hints, codes, error_flags)
            if grown is not None:
                self.capacity_hints = grown
                self.recompiles += 1
                self._jit()
                continue
            raise_query_errors(codes, error_flags)
            # results are replicated across shards post-gather: take shard 0
            local = [np.asarray(a)[0] for a in out_arrays]
            page = unflatten_page(self.out_spec_cell[0], local)
            post_sort = getattr(self, "_post_sort", None)
            if post_sort is not None:
                from trino_tpu.exec.executor import Executor

                page = Executor(self.session).sorted_page(page, post_sort)
            return page
        raise QueryError("capacity still exceeded after recompiles (join or exchange bucket)")
