"""A join's expansion without a sort (``ops/join.py expand``): each probe
row that owns pairs writes its index at its range's first slot and a
running max carries it over the range; the any-reduce over a row's pairs
is one prefix sum read at the range bounds (``any_in_range``). Both are
held to numpy references, the any-reduce also to the sort-based
``segments.monotonic_segment_sum`` it replaced in the join, kept here as a
reference. The metric file that reads the ``expansionSorts`` counter is
checked on its reader."""
import types

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu.ops import join as join_ops
from trino_tpu.ops import segments as seg

CASES = {
    # name -> (counts, capacity)
    "zero rows at the start, middle and end": (
        [0, 0, 3, 1, 0, 0, 2, 4, 0, 1, 0, 0], 16),
    "all counts zero": ([0, 0, 0, 0], 8),
    "zero-row probe": ([], 8),
    "total over the capacity": ([3, 0, 5, 4, 2, 6], 16),
    "total equals the capacity": ([2, 0, 5, 1, 0, 8], 16),
    "one row owns every slot": ([0, 0, 16, 0], 16),
    "one row owns more than every slot": ([0, 40, 0], 16),
    "int64 cumsum past 2^31, capacity under it": (
        [0, 3, 2**31 - 2, 2**31, 7, 0], 64),
    "random": (list(np.random.default_rng(42).integers(0, 6, 300)
                    * (np.random.default_rng(43).random(300) < 0.7)), 1024),
}


def _reference(counts, capacity):
    """(p, k, live, total, starts, ends) of a probe-major expansion by
    ``np.repeat``; a dead slot reads the last probe row."""
    c = np.asarray(counts, np.int64)
    n, total = len(c), int(c.sum())
    offsets = np.cumsum(c)
    starts = offsets - c
    m = min(total, capacity)
    p = np.repeat(np.arange(n), c.clip(0, capacity))[:m]
    k = np.arange(m) - starts[p]
    dead = np.arange(m, capacity)
    if n:
        p = np.concatenate([p, np.full(len(dead), n - 1)])
        k = np.concatenate([k, dead - starts[n - 1]])
    return (p, k, np.arange(capacity) < total, total,
            np.minimum(starts, capacity), np.minimum(offsets, capacity))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_expansion_equals_a_repeat_of_the_rows(case):
    counts, capacity = CASES[case]
    dtype = np.int64 if max(counts, default=0) >= 2**31 else np.int32
    ex = join_ops.expand(jnp.asarray(np.asarray(counts, dtype)), capacity)
    p, k, live, total, starts, ends = _reference(counts, capacity)
    assert int(ex.total) == total
    assert np.array_equal(np.asarray(ex.live), live)
    if not counts:
        assert not np.asarray(ex.live).any() and ex.starts.shape == (0,)
        return
    # identical at every slot, the dead ones included
    assert np.array_equal(np.asarray(ex.p), p)
    assert np.array_equal(np.asarray(ex.k), k)
    assert np.array_equal(np.asarray(ex.starts), starts)
    assert np.array_equal(np.asarray(ex.ends), ends)
    assert ex.p.dtype == ex.k.dtype == jnp.int32   # capacity under 2^31


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_ranged_any_reduce_equals_the_segment_sum(case):
    """Over the live slots, as every caller masks them: the any-reduce
    from the expansion's own ranges equals the sort-based segment sum over
    the slot owners and a numpy loop over each row's range."""
    counts, capacity = CASES[case]
    rng = np.random.default_rng(len(case))
    dtype = np.int64 if max(counts, default=0) >= 2**31 else np.int32
    ex = join_ops.expand(jnp.asarray(np.asarray(counts, dtype)), capacity)
    flags = jnp.asarray(rng.random(capacity) < 0.3) & ex.live
    got = np.asarray(join_ops.any_in_range(ex, flags))
    n = len(counts)
    assert got.shape == (n,)
    was = np.asarray(seg.monotonic_segment_sum(
        flags.astype(jnp.int32), ex.p, n) > 0)
    assert np.array_equal(got, was)
    f = np.asarray(flags)
    _p, _k, _live, _total, starts, ends = _reference(counts, capacity)
    assert [bool(f[s:e].any()) for s, e in zip(starts, ends)] == list(got)


# ------------------------------------------ the metric file, on its reader
METRIC = "join_expansion_sorts_per_stmt.analytic"


def _run(profiles):
    return types.SimpleNamespace(profiles=profiles)


def _row(operator, **fields):
    return dict({"operator": operator, "launches": 1}, **fields)


@pytest.mark.parametrize("profiles, want", [
    # a Q21 statement: two residual joins whose ranges are searched
    ({"q21": [_row("Join", expansionSorts=0), _row("Join", expansionSorts=0),
              _row("Aggregation", expansionSorts=0)]}, 0.0),
    # one statement whose two expansion joins merge-rank their ranges, one
    # with none
    ({"a": [_row("Join", expansionSorts=1), _row("Join", expansionSorts=1)],
      "b": [_row("Join", expansionSorts=0)]}, 1.0),
    # a program whose rows lack the field: nothing to read
    ({"a": [_row("Join"), _row("TableScan")]}, None),
    ({}, None),
])
def test_the_metric_file_drives_kernel_counter(profiles, want):
    from benchmark import spec
    from benchmark.readers import kernel_counter

    body = spec.load_layer_metric(METRIC)
    assert (body["reader"], body["field"], body["unit"]) == (
        "kernel_counter", "expansionSorts", "count")
    (entry,) = [m for m in spec.load_benchmark_json()["per_layer"]
                if m["name"] == METRIC]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        body["unit"], body["layer"], body["moves"])
    assert (entry["better"], entry["source"]) == ("lower", "program_counter")
    assert kernel_counter.read(body, _run(profiles)) == want
