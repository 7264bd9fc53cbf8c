"""Fused sort–merge join: one compiled region from keys to match spans.

The legacy pipeline (ops/join.py) materializes a ``SortedBuild`` between
phases: sort the build side (nb rows), THEN rank the probes against it
with a combined sort of build+probe (N = nb + np rows, ops/ranks.py), THEN
return ranks to probe order through a second N-row payload sort, THEN
gather ``build.rows`` at the matched rank (one more np-row random pass).
Every phase re-touches the full working set.

The fused formulation here sorts build and probe keys TOGETHER and emits
the matched build row directly into the projection gather:

1. ONE combined stable sort of the raw aligned key columns over N rows,
   builds concatenated first (equal keys keep builds before probes — no
   tag operand), payload = combined row index. Dead/null build rows ride
   along UNMASKED and inert: they are simply never encoded as candidates
   in step 2, so the sentinel masking, dtype widening, and dead-flag
   column of ``build_side`` all disappear.
2. In sorted space, the matching build row propagates to every probe slot
   of its equal-key run by ONE streaming pass: encode
   ``run_id * (nb + 1) + (build_row + 1)`` at live-build slots (0
   elsewhere) and take a running max (``lax.cummax``). A probe slot
   decodes a match iff the running max carries its own run_id — the
   within-run reset costs no segmented scan.
3. Matched build rows return to probe order by ONE np-row scatter through
   the sort permutation (the permutation's probe slots are unique, so the
   scatter is ``unique_indices`` at the measured ~7 ns/element
   random-access floor) — cheaper than the legacy second N-row sort
   whenever np is not much larger than the sort's row budget, and N never
   re-enters the pipeline after step 2.

Total: one N-row sort + two streaming prefixes + one np scatter, versus
sort(nb) + sort(N) + sort(N) + gather(np). The build-side sort is gone
and N is touched once — on the 16M/4M case that is the measured >=2x.

When the build side is ALREADY sorted (ops/join.py ``SortedBuild`` from
the device build cache or a presorted column), the combined sort shrinks
to the probe side and the rank step runs as a tiled two-pointer merge —
optionally the Pallas kernel in ops/merge_pallas.py (see
``merge_sorted_build``), where XLA has no fusion story at all.

Scope: the fused tier serves the N:1 lookup join and semi/anti
membership — the kernels under TPC-H q3/q18's 300x gap. M:N expansion
joins keep the legacy two-pass count+emit (their output capacity
machinery needs probe-order counts anyway; see the tier table in
README "Join kernels").
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from trino_tpu.ops import ranks, scans

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]


# liveness/null-match semantics are SHARED with the legacy kernels — one
# definition, so the fused tier can never silently diverge from the
# pipeline it must stay bit-compatible with
from trino_tpu.ops.join import _live_mask as _build_live  # noqa: E402
from trino_tpu.ops.join import probe_valid as _probe_valid  # noqa: E402


def _as_key(v: jnp.ndarray) -> jnp.ndarray:
    return v.astype(jnp.int8) if v.dtype == jnp.bool_ else v


def fused_match_rows(
    build_keys: List[Lowered],
    build_sel: Optional[jnp.ndarray],
    probe_keys: List[Lowered],
) -> jnp.ndarray:
    """Per probe row (original order): the ORIGINAL build row index of a
    live equal-key build row, or -1 when none exists. Duplicate build keys
    resolve to the last live duplicate in sorted order (the caller proves
    uniqueness for N:1 joins; membership only needs "any").

    This is the whole fused region: callers derive ``(rows, matched)``
    as ``(clip(m, 0), m >= 0)`` and feed ``rows`` straight into the
    projection gather.
    """
    nb = build_keys[0][0].shape[0]
    np_ = probe_keys[0][0].shape[0]
    if np_ == 0:
        return jnp.zeros((0,), jnp.int32)
    if nb == 0:
        return jnp.full((np_,), -1, jnp.int32)
    n = nb + np_
    operands = []
    for (bv, _), (pv, _) in zip(build_keys, probe_keys):
        bv, pv = _as_key(bv), _as_key(pv)
        dt = jnp.promote_types(bv.dtype, pv.dtype)
        operands.append(jnp.concatenate([bv.astype(dt), pv.astype(dt)]))
    idx = jnp.arange(n, dtype=jnp.int32)
    # liveness follows the sort's permutation with the keys and the index
    live_b = _build_live(build_keys, build_sel)
    live_concat = jnp.concatenate([live_b, jnp.ones((np_,), bool)])
    out = ranks.stable_sort(tuple(operands) + (idx, live_concat),
                            len(operands))
    sorted_cols, idx_s, live_s = out[:-2], out[-2], out[-1]
    is_build = idx_s < nb
    # equal-key run boundaries (any key column differs from the previous)
    neq = jnp.zeros((n - 1,), bool)
    for c in sorted_cols:
        neq = neq | (c[1:] != c[:-1])
    run_start = jnp.concatenate([jnp.ones((1,), bool), neq])
    run_id = scans.cumsum(run_start.astype(jnp.int32))
    # candidate encoding at LIVE build slots only: dead/null builds never
    # match, so they need no masking anywhere upstream
    cand_live = is_build & live_s
    stride = jnp.int64(nb + 1)
    enc = run_id.astype(jnp.int64) * stride + jnp.where(
        cand_live, idx_s.astype(jnp.int64) + 1, jnp.int64(0)
    )
    m = scans.cummax(enc)
    has_build = (m // stride) == run_id.astype(jnp.int64)
    brow_sorted = jnp.where(
        has_build & (m % stride > 0), (m % stride - 1).astype(jnp.int32),
        jnp.int32(-1),
    )
    # back to probe order: scatter through the sort permutation's probe
    # slots (unique by construction); build slots map to DISTINCT
    # out-of-bounds slots (np_ + idx_s) and drop, so ``unique_indices``
    # stays truthful — duplicated OOB indices are documented UB (same
    # convention as dense_unique_table's span + iota)
    probe_pos = jnp.where(is_build, jnp.int32(np_) + idx_s,
                          idx_s - jnp.int32(nb))
    return (
        jnp.full((np_,), -1, jnp.int32)
        .at[probe_pos]
        .set(brow_sorted, mode="drop", unique_indices=True)
    )


@jax.named_scope("fused_probe_unique")
def fused_probe_unique(
    build_keys: List[Lowered],
    build_sel: Optional[jnp.ndarray],
    probe_keys: List[Lowered],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused analog of ``build_side`` + ``probe_unique``: (build_row_idx,
    matched) in probe order, no SortedBuild ever materialized."""
    m = fused_match_rows(build_keys, build_sel, probe_keys)
    matched = m >= 0
    pvalid = _probe_valid(probe_keys)
    if pvalid is not None:
        matched = matched & pvalid
    return jnp.maximum(m, 0), matched


@jax.named_scope("fused_membership")
def fused_membership(
    build_keys: List[Lowered],
    build_sel: Optional[jnp.ndarray],
    probe_keys: List[Lowered],
) -> jnp.ndarray:
    """Fused analog of ``membership`` (semi/anti join): build duplicates
    are fine — any live equal-key build row flags the probe."""
    _, matched = fused_probe_unique(build_keys, build_sel, probe_keys)
    return matched


# ------------------------------------------------- pre-sorted build merge
def merge_sorted_build(
    build,  # ops/join.py SortedBuild
    probe_keys: List[Lowered],
    *,
    use_pallas: bool = False,
    pallas_block_build: int = 2048,
    pallas_interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(build_row_idx, matched) against an ALREADY-SORTED build (an
    ops/join.py ``SortedBuild`` — e.g. served warm by the device build
    cache, or a presorted key column whose sort was skipped).

    Only the probe side is unsorted work; the rank step is the tiled
    two-pointer merge. With ``use_pallas`` the merge runs as the Pallas
    kernel in ops/merge_pallas.py: sorted probe blocks stream against
    DMA'd build windows entirely in VMEM, an access pattern XLA cannot
    recover from a searchsorted-style lowering. PRECONDITION for
    ``use_pallas``: the caller has PROVEN the dead-row sentinel
    unreachable from the key's value range (executor
    ``_merge_sentinel_safe``) — the kernel cannot tell a sentinel-masked
    dead row from a live key equal to it. A hard shape/dtype guard
    (single int32 key) still degrades silently to the XLA fallback: the
    same merge expressed as ranks over the combined sort (ops/ranks.py).
    """
    from trino_tpu.ops import join as join_ops

    nb = build.n
    np_ = probe_keys[0][0].shape[0]
    if np_ == 0 or nb == 0:
        z = jnp.zeros((np_,), jnp.int32)
        return z, jnp.zeros((np_,), bool)
    pcols = join_ops._probe_cols(build, probe_keys)
    # one np-row gather serves both the row id and the live guard: dead
    # build slots pre-encode as -1 (streaming elementwise pass over nb)
    rows_live = jnp.where(build.live, build.rows.astype(jnp.int32),
                          jnp.int32(-1))
    if (
        use_pallas
        and build.single
        and len(pcols) == 1
        and pcols[0].dtype == jnp.int32
        and build.cols[0].dtype == jnp.int32
    ):
        from trino_tpu.ops import merge_pallas

        # NULL probe slots carry RAW physical values the vrange proof does
        # not bound — mask them in-range (0) so no slot can equal the
        # kernel's INT32_MAX pad (an equal slot would drag its block's
        # covering window into the pad tail); their matches are voided by
        # the pvalid mask below either way
        pv = _probe_valid(probe_keys)
        pkey = pcols[0] if pv is None else jnp.where(pv, pcols[0], 0)
        perm = ranks.argsort32(pkey)
        p_sorted = pkey[perm]
        pos = merge_pallas.merge_unique_sorted(
            build.cols[0], p_sorted, block_build=pallas_block_build,
            interpret=pallas_interpret,
        )
        # back to probe order through the probe permutation (np scatter)
        pos_o = (
            jnp.zeros((np_,), jnp.int32)
            .at[perm]
            .set(pos, mode="drop", unique_indices=True)
        )
        rl = rows_live[jnp.clip(pos_o, 0, nb - 1)]
        matched = (pos_o >= 0) & (rl >= 0)
        rows = jnp.maximum(rl, 0)
    else:
        lo, counts = ranks.sorted_ranks(build.cols, pcols)
        rl = rows_live[jnp.clip(lo, 0, nb - 1)]
        matched = (counts > 0) & (rl >= 0)
        rows = jnp.maximum(rl, 0)
    pvalid = _probe_valid(probe_keys)
    if pvalid is not None:
        matched = matched & pvalid
    return rows, matched
