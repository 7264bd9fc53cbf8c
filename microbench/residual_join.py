"""Residual semi/anti join microbench: the build of a key in order with dead
rows among the live ones, and the probe's key ranges, at TPC-H Q21's SF 10
shapes on the chip.

Q21's NOT EXISTS build is lineitem (60 x 2^20 slots, ``l_orderkey``
ascending, about four rows a key) under the lateness filter; its probe is a
Compact's 2^20 slots. Three ways to make the build, each timed to a ready
result:

- ``sort``: ``ops/join.py build_side`` with no presorted promise (the
  stable sort of every slot);
- ``compact``: an order-preserving compaction first (``ranks.true_positions``
  to the next power of two of the live rows, one gather of the key), then
  the presorted build over the live prefix;
- ``in_place``: ``join_ops.in_place_build`` (a running max, one reduction).

And two ways to find the probe's key ranges in a build in key order:
``merge`` (``join_ops.probe_counts``: merge ranks over the combined sort of
build and probe) and ``search`` (``join_ops.searched_counts``).

Then the expansion of the probe's ranges into pairs (2^20 probe slots,
720,000 live with 4 to 6 pairs each, 2^22 pair slots) and the any-reduce
of a flag over each probe row's pairs, each two ways:

- ``sorted``: the slot owners by merge ranks of the slot numbers against
  the ranges' ends (a combined sort of 2^20 + 2^22 rows) and the offsets by
  a gather of the owners' starts; the any-reduce by
  ``segments.monotonic_segment_sum`` over the owners (a second combined
  sort). The expansion is the one ``ops/join.py expand`` replaced, kept
  here as the baseline;
- ``scatter``: ``join_ops.expand`` (one scatter at the ranges' first
  slots, two running maxes) and ``join_ops.any_in_range`` (one prefix sum,
  two gathers at the ranges' bounds).

Run: python microbench/residual_join.py [out.json] [--only build|expand]
(TPU; a few minutes cold). Prints one JSON line: median seconds of five
runs per case.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trino_tpu  # noqa: E402,F401  (x64 on before any array)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from trino_tpu.ops import join as join_ops  # noqa: E402
from trino_tpu.ops import ranks  # noqa: E402
from trino_tpu.ops import scans  # noqa: E402
from trino_tpu.ops import segments as seg  # noqa: E402

BUILD_SLOTS = 62_914_560
LIVE_ROWS = 59_994_670
PROBE_SLOTS = 1_048_576
PROBE_LIVE = 720_000
PAIR_SLOTS = 4_194_304


def _data(seed: int = 7):
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, size=LIVE_ROWS // 3)   # 1..7 lines an order
    keys = np.repeat(np.arange(1, len(lines) + 1, dtype=np.int32) * 4,
                     lines)[:LIVE_ROWS]
    build_key = np.zeros(BUILD_SLOTS, np.int32)
    build_key[:LIVE_ROWS] = keys
    late = np.zeros(BUILD_SLOTS, bool)
    late[:LIVE_ROWS] = rng.random(LIVE_ROWS) < 0.5
    probe_key = rng.choice(keys, PROBE_SLOTS).astype(np.int32)
    probe_sel = np.zeros(PROBE_SLOTS, bool)
    probe_sel[:PROBE_LIVE] = True
    return [jax.device_put(a) for a in (build_key, late, probe_key, probe_sel)]


def _expand_sorted(counts, capacity):
    """(p, k, live): the expansion by merge ranks, as ``join_ops.expand``
    ran it before it scattered (int32 capacity)."""
    n = counts.shape[0]
    c64 = counts.astype(jnp.int64)
    offsets = scans.cumsum(c64)
    total = offsets[n - 1]
    starts = offsets - c64
    offs = jnp.clip(offsets, 0, 2**31 - 1).astype(jnp.int32)
    j = jnp.arange(capacity, dtype=jnp.int32)
    lo, eq = ranks.sorted_ranks([offs], [j])
    p = jnp.clip(lo + eq, 0, n - 1)
    k = j.astype(jnp.int64) - starts[p]
    live = j < jnp.minimum(total, capacity).astype(j.dtype)
    return p, k, live


def _pair_counts(seed: int = 11):
    """Q21's probe: 720,000 live rows of 2^20 with 4 to 6 pairs each, and a
    flag a pair (the residual), half of them set."""
    rng = np.random.default_rng(seed)
    counts = np.zeros(PROBE_SLOTS, np.int32)
    live = rng.choice(PROBE_SLOTS, PROBE_LIVE, replace=False)
    counts[live] = rng.integers(4, 7, PROBE_LIVE)
    flags = rng.random(PAIR_SLOTS) < 0.5
    return jax.device_put(counts), jax.device_put(flags)


def _median_s(fn, *args, reps: int = 5):
    """Median seconds of ``reps`` runs, or the error's first line where the
    case does not compile or run (one program holds every intermediate)."""
    try:
        jax.block_until_ready(fn(*args))        # compile and warm
    except Exception as e:  # noqa: BLE001 — reported in the line
        return str(e).splitlines()[0][:300]
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _build_cases():
    build_key, late, probe_key, probe_sel = _data()
    live = int(np.asarray(late).sum())
    capacity = 1 << (live - 1).bit_length()

    @jax.jit
    def sort(k, sel):
        b = join_ops.build_side([(k, None)], sel)
        return b.cols[0], b.rows, b.live

    @jax.jit
    def compact(k, sel):
        idx = ranks.true_positions(sel, capacity, 0)
        kept = jnp.arange(capacity) < jnp.sum(sel.astype(jnp.int32))
        b = join_ops.build_side([(k[idx], None)], kept, presorted=True)
        return b.cols[0], b.rows, b.live

    @jax.jit
    def in_place(k, sel):
        b = join_ops.in_place_build((k, None), sel)
        return b.cols[0], b.live

    @jax.jit
    def merge(k, sel, pk, psel):
        b = join_ops.in_place_build((k, None), sel)
        return join_ops.probe_counts(b, [(pk, None)], psel)

    @jax.jit
    def search(k, sel, pk, psel):
        b = join_ops.in_place_build((k, None), sel)
        return join_ops.searched_counts(b, [(pk, None)], psel)

    out = {
        "build_slots": BUILD_SLOTS, "build_live": live,
        "compact_capacity": capacity, "probe_slots": PROBE_SLOTS,
        "build_s": {name: _median_s(fn, build_key, late) for name, fn in
                    (("sort", sort), ("compact", compact),
                     ("in_place", in_place))},
        # each includes the in-place build, timed alone above
        "build_and_ranges_s": {
            name: _median_s(fn, build_key, late, probe_key, probe_sel)
            for name, fn in (("merge", merge), ("search", search))},
    }
    if all(isinstance(v, float) for v in out["build_and_ranges_s"].values()):
        # the two range finders agree
        m, s = merge(build_key, late, probe_key, probe_sel), search(
            build_key, late, probe_key, probe_sel)
        out["ranges_agree"] = all(np.array_equal(np.asarray(a), np.asarray(b))
                                  for a, b in zip(m, s))
    return out


def _expand_cases():
    counts, flags = _pair_counts()

    def expand_sorted(c):
        return _expand_sorted(c, PAIR_SLOTS)

    def expand_scatter(c):
        ex = join_ops.expand(c, PAIR_SLOTS)
        return ex.p, ex.k, ex.live

    def any_sorted(c, f):
        p, _k, live = _expand_sorted(c, PAIR_SLOTS)
        return seg.monotonic_segment_sum(
            (f & live).astype(jnp.int32), p, PROBE_SLOTS) > 0

    def any_scatter(c, f):
        ex = join_ops.expand(c, PAIR_SLOTS)
        return join_ops.any_in_range(ex, f & ex.live)

    def timed(fn, *args):
        # eager: one program a primitive, as the served tier runs them
        return {"eager": _median_s(fn, *args),
                "jit": _median_s(jax.jit(fn), *args)}

    out = {
        "probe_slots": PROBE_SLOTS, "probe_live": PROBE_LIVE,
        "pair_slots": PAIR_SLOTS, "pairs": int(np.asarray(counts).sum()),
        "expand_s": {"sorted": timed(expand_sorted, counts),
                     "scatter": timed(expand_scatter, counts)},
        # each includes its own expansion, timed alone above
        "expand_and_any_s": {"sorted": timed(any_sorted, counts, flags),
                             "scatter": timed(any_scatter, counts, flags)},
    }
    # the two agree at every live slot and on every probe row
    (p0, k0, l0), (p1, k1, l1) = expand_sorted(counts), expand_scatter(counts)
    live = np.asarray(l0)
    out["expand_agree"] = bool(
        np.array_equal(live, np.asarray(l1))
        and np.array_equal(np.asarray(p0)[live], np.asarray(p1)[live])
        and np.array_equal(np.asarray(k0)[live], np.asarray(k1)[live]))
    out["any_agree"] = bool(np.array_equal(
        np.asarray(any_sorted(counts, flags)),
        np.asarray(any_scatter(counts, flags))))
    return out


SECTIONS = {"build": _build_cases, "expand": _expand_cases}


def main(argv) -> int:
    args = list(argv[1:])
    only = None
    if "--only" in args:
        at = args.index("--only")
        only = args[at + 1]
        del args[at:at + 2]
    out = {"device": jax.devices()[0].device_kind}
    for name, cases in SECTIONS.items():
        if only in (None, name):
            out[name] = cases()
    line = json.dumps(out)
    print(line, flush=True)
    if args:
        with open(args[0], "w", encoding="utf-8") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
