"""Pallas tiled two-pointer merge: sorted probe blocks vs sorted build.

The inner step of the fused sort–merge join where XLA's fusion gives up:
ranking a sorted probe vector against a sorted build vector is a MERGE —
each probe block only ever touches the narrow build window its key range
spans — but XLA has no lowering for that access pattern. ``lax.sort`` of
the concatenation re-touches both sides at full width, and
``jnp.searchsorted`` lowers to log2(nb) dependent random-gather passes
(~7 ns/element on v5e, the measured random-access floor). This kernel
expresses the merge directly:

- the probe splits into sorted blocks of ``BLOCK_PROBE`` keys (grid);
- per block, the covering build window ``[start, end)`` is known BEFORE
  the kernel runs from a searchsorted over only the G block BOUNDARY
  keys (G = np/BLOCK_PROBE, thousands — the log2 passes are trivial at
  that width; the per-element floor never applies), fed in through
  scalar prefetch;
- the kernel walks the window in ``block_build``-sized chunks DMA'd
  HBM->VMEM double-buffered (chunk k+1 transfers while chunk k
  compares), accumulating per probe key its rank (count of smaller
  build keys) and an equality flag with plain VPU compares against
  lane-rotated build rows (see ``_kernel``).

Output per probe slot: the matched build RANK (index into the sorted
build), or -1 — exactly what the projection gather consumes.

Contract (enforced by the caller, ops/fused_join.merge_sorted_build):
int32 keys whose value range proves INT32_MAX unreachable (the pad
sentinel can then never equal a live probe key), and a build already
sorted ascending with dead rows as a sentinel tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
PROBE_SUBLANES = 8
BLOCK_PROBE = PROBE_SUBLANES * LANES  # probe keys per grid step: one (8, 128) tile
_PAD = np.int32(np.iinfo(np.int32).max)
_I32 = jnp.int32
_LANE_UNROLL = 8  # lane rotations per loop trip (explicit: int32 loop bounds are not static)


def _kernel(wstart_ref, nwin_ref, probe_ref, build_hbm, out_ref,
            bwin, sem, *, block_build: int):
    """One probe tile against its build window. Everything stays in the
    (sublane, lane) layout it arrives in: a build row (128 keys on the
    lanes) is broadcast over the probe tile's 8 sublanes and ROTATED along
    the lanes 128 times, so every probe key meets every key of the row
    with elementwise compares only — no lane<->sublane relayout, no
    cross-lane reduction, no (probe x build) intermediate. Every literal
    is an explicit int32: under ``jax_enable_x64`` a bare Python int
    traces as i64, which Mosaic does not lower."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    s0 = wstart_ref[g]
    nw = nwin_ref[g]
    pk = probe_ref[...]  # (8, 128) int32, sorted row-major
    sub = block_build // LANES

    def window_dma(slot, w):
        return pltpu.make_async_copy(
            build_hbm.at[pl.ds((s0 + w * _I32(block_build)) // _I32(LANES),
                               sub), :],
            bwin.at[slot],
            sem.at[slot],
        )

    @pl.when(nw > _I32(0))
    def _():
        window_dma(_I32(0), _I32(0)).start()

    def lane_steps(_, carry):
        b, acc_lt, acc_eq = carry
        for _unrolled in range(_LANE_UNROLL):
            acc_lt = acc_lt + (b < pk).astype(_I32)
            acc_eq = acc_eq | (b == pk).astype(_I32)
            b = pltpu.roll(b, _I32(1), 1)
        return b, acc_lt, acc_eq

    def window_step(w, carry):
        slot = jax.lax.rem(w, _I32(2))

        @pl.when(w + _I32(1) < nw)
        def _():
            window_dma(jax.lax.rem(w + _I32(1), _I32(2)), w + _I32(1)).start()

        window_dma(slot, w).wait()

        def row_step(r, carry):
            acc_lt, acc_eq = carry
            row = bwin[slot, pl.ds(r, 1), :]  # (1, 128) sorted build keys
            _, acc_lt, acc_eq = jax.lax.fori_loop(
                _I32(0), _I32(LANES // _LANE_UNROLL), lane_steps,
                (jnp.broadcast_to(row, pk.shape), acc_lt, acc_eq))
            return acc_lt, acc_eq

        return jax.lax.fori_loop(_I32(0), _I32(sub), row_step, carry)

    zero = jnp.zeros(pk.shape, _I32)
    acc_lt, acc_eq = jax.lax.fori_loop(_I32(0), nw, window_step, (zero, zero))
    out_ref[...] = jnp.where(acc_eq > _I32(0), s0 + acc_lt, _I32(-1))


def _tile_index(i, *_prefetch):
    # int32 on purpose: a bare 0 is i64 under jax_enable_x64
    return i, jnp.int32(0)


@functools.partial(
    jax.jit, static_argnames=("block_build", "interpret"))
def merge_unique_sorted(
    build_sorted: jnp.ndarray,
    probe_sorted: jnp.ndarray,
    *,
    block_build: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per SORTED probe key: matched build rank or -1. Both inputs int32
    and ascending; build dead rows must be an INT32_MAX-sentinel tail
    (they then never equal a live probe key — the caller proved the
    sentinel unreachable from the column's value range)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert build_sorted.dtype == jnp.int32 and probe_sorted.dtype == jnp.int32
    nb = build_sorted.shape[0]
    np_ = probe_sorted.shape[0]
    block_build = max(128, (block_build // 128) * 128)
    if np_ == 0 or nb == 0:
        return jnp.full((np_,), -1, jnp.int32)
    # pad probe to a whole number of blocks with the last (max) key: pad
    # slots compute garbage that the final slice drops, and they cannot
    # widen any block's build window (they equal the block max)
    g = -(-np_ // BLOCK_PROBE)
    probe_pad = jnp.concatenate([
        probe_sorted,
        jnp.broadcast_to(probe_sorted[-1:], (g * BLOCK_PROBE - np_,)),
    ]).reshape(g, BLOCK_PROBE)
    # one probe block = one (8, 128) tile of the kernel's probe operand
    probe_tiles = probe_pad.reshape(g * PROBE_SUBLANES, LANES)
    # pad build with the sentinel so every window DMA stays in bounds:
    # window starts align DOWN to 128 and run a whole number of
    # block_build chunks past the covering range
    nb_pad = (-(-nb // block_build) + 2) * block_build
    build_pad = jnp.concatenate([
        build_sorted, jnp.full((nb_pad - nb,), _PAD, jnp.int32)
    ])
    # covering build window per block from its BOUNDARY keys only (G keys
    # — searchsorted's log2 random-gather passes are trivial at this
    # width; ops/ranks.py bans it for per-ELEMENT ranking, not this)
    starts = jnp.searchsorted(build_pad, probe_pad[:, 0], side="left")
    ends = jnp.searchsorted(build_pad, probe_pad[:, -1], side="right")
    wstart = ((starts // 128) * 128).astype(jnp.int32)
    nwin = (-(-(ends.astype(jnp.int32) - wstart) // block_build)).astype(jnp.int32)
    # hard in-bounds clamp: a probe key equal to the pad sentinel would
    # push ``ends`` to nb_pad and the alignment slack one window past the
    # buffer — windows beyond nb_pad hold nothing real, so clamping never
    # changes a rank or a match
    nwin = jnp.minimum(nwin, (jnp.int32(nb_pad) - wstart) // block_build)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((PROBE_SUBLANES, LANES), _tile_index),
            pl.BlockSpec(memory_space=pl.ANY),  # build stays in HBM
        ],
        out_specs=pl.BlockSpec((PROBE_SUBLANES, LANES), _tile_index),
        scratch_shapes=[
            pltpu.VMEM((2, block_build // LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_build=block_build),
        out_shape=jax.ShapeDtypeStruct((g * PROBE_SUBLANES, LANES), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(wstart, nwin, probe_tiles, build_pad.reshape(nb_pad // LANES, LANES))
    return out.reshape(-1)[:np_]
