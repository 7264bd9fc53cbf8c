"""Merge-based rank computation: the join-probe / segment-boundary substrate.

Reference role: the probe half of ``operator/join/`` (JoinProbe over
PagesHash) and the group-boundary lookups of FlatHash. The natural TPU
formulation of "find each query key's range in a sorted build" is NOT a
per-query binary search: ``jnp.searchsorted`` lowers to ~log2(n) dependent
random-gather passes over the whole query vector (measured 2.5 s for 6M
int64 probes into 1.5M keys on v5e — the round-1 engine's dominant cost).

Instead, ranks are computed by ONE combined stable sort of build keys and
query keys (builds first), followed by streaming prefix ops:

- at a query slot, every build key <= it sorts before it (builds win ties),
  so the inclusive build-count prefix IS the query's right rank
  (searchsorted side='right');
- the left rank is the build-count prefix at the start of the equal-key run,
  propagated across the run by a running max (prefixes are non-decreasing);
- results return to query order through the sort's inverted permutation
  (one int32 argsort + gather).

Everything index-typed is int32 (int64 gathers cost 3.7x on v5e).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from trino_tpu.ops import scans


def _iota32(n: int) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.int32)


# ------------------------------------------------------------- the one sort
# XLA's sort on the v5e compiles in time that grows steeply with the number
# of operands and the width of the comparator, and hardly with the row
# count above ~32 K (compiler runs, PR 25: stable (int32, int32) 22 s;
# (int64, int32) 55 s; 3 x int32 46 s; one key + 8 payloads 136 s; the 15
# operands of q3's ORDER BY ~660 s on the chip). So every sort of the
# engine is this ONE program per row bucket — a stable (int32 digit, int32
# row index) sort — run once per 32-bit digit of the packed keys, least
# significant first; the operands then follow the permutation by gather.
_SORT_BUCKET_MIN = 128


def _sort_bucket(n: int) -> int:
    """Rows the sort program is compiled for: the next power of two."""
    return max(_SORT_BUCKET_MIN, 1 << (n - 1).bit_length())


def _digitisable(dtype) -> bool:
    """Key dtypes with an order-preserving 32-bit digit form the chip's
    compiler accepts (not float64: it has no 64-bit bitcast there)."""
    return (dtype == jnp.bool_ or dtype == jnp.float32
            or jnp.issubdtype(dtype, jnp.integer))


def _key_fields(key: jnp.ndarray):
    """``key`` as [(uint32 field, bit width)], most significant first, whose
    unsigned lexicographic order is ``lax.sort``'s order of ``key``."""
    if key.dtype == jnp.bool_:
        return [(key.astype(jnp.uint32), 1)]
    if key.dtype == jnp.float32:
        # lax.sort's float order: -0 == +0, NaNs last; past that, IEEE total
        # order is the signed order of the sign-folded bit pattern
        key = jnp.where(key == 0, jnp.float32(0), key)
        key = jnp.where(jnp.isnan(key), jnp.float32(jnp.nan), key)
        bits = jax.lax.bitcast_convert_type(key, jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    width = key.dtype.itemsize * 8
    signed = jnp.issubdtype(key.dtype, jnp.signedinteger)
    if width == 64:
        hi = (key >> 32).astype(jnp.int32 if signed else jnp.uint32)
        return _key_fields(hi) + [((key & 0xFFFFFFFF).astype(jnp.uint32), 32)]
    if not signed:
        return [(key.astype(jnp.uint32), width)]
    # two's complement -> offset binary: flip the sign bit of the w-bit value
    if width == 32:
        biased = key ^ jnp.int32(-(1 << 31))
    else:
        biased = key.astype(jnp.int32) + jnp.int32(1 << (width - 1))
    return [(jax.lax.bitcast_convert_type(biased, jnp.uint32), width)]


@jax.jit
@jax.named_scope("sort_digits")
def _digits(sort_keys) -> Tuple[jnp.ndarray, ...]:
    """The keys as int32 digits, most significant first: consecutive fields
    packed into as few 32-bit words as hold them, rows padded to
    ``_sort_bucket`` with the largest digit (a stable sort then leaves the
    pad rows, which start last, at the end)."""
    words, acc, used = [], None, 0
    for key in sort_keys:
        for field, width in _key_fields(key):
            if acc is not None and used + width > 32:
                words.append(acc)
                acc, used = None, 0
            acc = field if acc is None else (acc << jnp.uint32(width)) | field
            used += width
    words.append(acc)
    n = words[0].shape[0]
    top = jnp.iinfo(jnp.int32).max
    return tuple(
        jnp.pad(jax.lax.bitcast_convert_type(w ^ jnp.uint32(1 << 31), jnp.int32),
                (0, _sort_bucket(n) - n), constant_values=top)
        for w in words)


@jax.jit
@jax.named_scope("sort_pass")
def _sort_pass(digit: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.sort((digit[perm], perm), num_keys=1, is_stable=True)[1]


@jax.named_scope("lex_argsort32")
def lex_argsort32(sort_keys: List[jnp.ndarray]) -> jnp.ndarray:
    """Stable lexicographic argsort (most significant key first), int32
    indices: one ``_sort_pass`` per digit, least significant first."""
    n = sort_keys[0].shape[0]
    if n <= 1:
        return _iota32(n)
    if not all(_digitisable(k.dtype) for k in sort_keys):
        out = jax.lax.sort(tuple(sort_keys) + (_iota32(n),),
                           num_keys=len(sort_keys), is_stable=True)
        return out[-1]
    digits = _digits(tuple(sort_keys))
    perm = _iota32(digits[0].shape[0])
    if len(digits) > 1 and isinstance(digits[0], jax.core.Tracer):
        # inside a jitted body every sort instruction is compiled again:
        # loop, so that all the passes share ONE
        stacked = jnp.stack(digits[::-1])
        perm = jax.lax.fori_loop(
            0, len(digits), lambda i, p: _sort_pass(stacked[i], p), perm)
    else:
        for digit in digits[::-1]:
            perm = _sort_pass(digit, perm)
    return perm[:n]


def argsort32(vals: jnp.ndarray) -> jnp.ndarray:
    """Stable argsort returning int32 indices (int64 index payloads slow
    every downstream gather 3.7x on v5e)."""
    return lex_argsort32([vals])


def stable_sort(operands, num_keys: int) -> List[jnp.ndarray]:
    """``lax.sort(operands, num_keys=num_keys, is_stable=True)``: every
    operand permuted by the stable lexicographic order of the first
    ``num_keys`` (see the note above: key digits sort, operands gather)."""
    operands = list(operands)
    return batched_gather(operands, lex_argsort32(operands[:num_keys]))


@jax.jit
@jax.named_scope("gather_all")
def _gather_all(arrays, idx):
    groups: dict = {}
    for i, a in enumerate(arrays):
        groups.setdefault(a.dtype, []).append(i)
    out: List = [None] * len(arrays)
    for idxs in groups.values():
        if len(idxs) == 1:
            out[idxs[0]] = arrays[idxs[0]][idx]
        else:
            cols = _row_gather(jnp.stack([arrays[i] for i in idxs], axis=1),
                               idx)
            for j, i in enumerate(idxs):
                out[i] = cols[j]
    return tuple(out)


# The [n, k] result of a row gather may be laid out [n, 128] on the v5e,
# whatever k is: 42.7x for three int32 columns, 16 GiB at n = 2^25 (TPC-H
# Q21's supplier join at SF 10, which the chip's compiler refused). A
# gather of more slots than ROW_GATHER_MAX goes by in blocks of
# ROW_GATHER_BLOCK, each block's columns taken apart before the next.
ROW_GATHER_BLOCK = 1 << 21
ROW_GATHER_MAX = 1 << 23


def _row_gather(stacked: jnp.ndarray, idx: jnp.ndarray) -> List[jnp.ndarray]:
    """The columns of ``stacked[idx]``."""
    n, k = idx.shape[0], stacked.shape[1]
    if n <= ROW_GATHER_MAX:
        g = stacked[idx]
        return [g[:, j] for j in range(k)]
    blocks = jnp.pad(idx, (0, -n % ROW_GATHER_BLOCK)).reshape(
        -1, ROW_GATHER_BLOCK)

    def block(b):
        g = stacked[b]
        return tuple(g[:, j] for j in range(k))

    return [c.reshape(-1)[:n] for c in jax.lax.map(block, blocks)]


def batched_gather(arrays: List[jnp.ndarray], idx: jnp.ndarray) -> List[jnp.ndarray]:
    """Gather many same-length arrays at the same indices in ONE random-HBM
    pass per dtype group. Separate gathers do not fuse when the index is
    computed (each costs ~40 ms per 6M rows on v5e); a [n, k] row-gather
    moves k columns for about the price of one. (One jitted program per
    call: the eager tier would otherwise compile the stack, the gather and
    every column slice apart.)"""
    return list(_gather_all(tuple(arrays), idx))


def _mask_words(flags: jnp.ndarray) -> jnp.ndarray:
    """``flags`` packed 32 to a uint32 word, row ``32 w + b`` in bit ``b``
    of word ``w`` (the tail padded with False)."""
    bits = jnp.pad(flags, (0, -flags.shape[0] % 32)).reshape(-1, 32)
    return jnp.sum(bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32),
                   axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("size", "fill"))
@jax.named_scope("true_positions")
def true_positions(flags: jnp.ndarray, size: int, fill: int = 0) -> jnp.ndarray:
    """int32[size]: slot ``j`` holds the index of the ``(j+1)``-th True of
    ``flags``; slots past the count hold ``fill`` (in bounds, so a gather
    at the result needs no clip). What ``argsort32(~flags)[:size]`` lists,
    with no sort: a comparison sort of n one-bit keys cost 2.1 s a q3 at
    n = 62.9 M on the v5e (ledger, PR 30). Here only n/32-sized scatters
    and ``size``-sized gathers touch memory at random; the rest streams.

    The mask is packed into 32-bit words; a prefix count over the words
    gives each word's first output slot; every non-empty word writes its
    index there and a running max spreads it over the word's slots; each
    slot then fetches its word and picks its set bit by bisecting on
    popcounts. One program a call (the eager tier would otherwise compile
    it primitive by primitive)."""
    n = flags.shape[0]
    if n == 0:
        return jnp.full((size,), fill, jnp.int32)
    words = _mask_words(flags)
    nw = words.shape[0]
    counts = jax.lax.population_count(words).astype(jnp.int32)
    ends = scans.cumsum(counts)
    first = ends - counts
    # non-empty words own distinct first slots (past ``size``: dropped);
    # empty words go to DISTINCT out-of-bounds slots, so ``unique_indices``
    # stays truthful (dense_unique_table's convention). 0 = not a first slot
    w = _iota32(nw)
    target = jnp.where(counts > 0, first, jnp.int32(max(32 * nw, size)) + w)
    mark = (jnp.zeros((size,), jnp.int32)
            .at[target].set(w + 1, mode="drop", unique_indices=True))
    slot = _iota32(size)
    # both ascend with the slot, so a running max spreads a word's index
    # and its first slot over the slots it owns
    owner = jnp.maximum(scans.cummax(mark) - 1, 0)
    base = scans.cummax(jnp.where(mark > 0, slot, 0))
    word = words[owner]
    k = slot - base  # this slot's set bit within its word, 0-based
    bit = jnp.zeros((size,), jnp.uint32)
    for step in (16, 8, 4, 2, 1):
        below = jax.lax.population_count(
            (word >> bit) & jnp.uint32((1 << step) - 1)).astype(jnp.int32)
        up = k >= below
        k = jnp.where(up, k - below, k)
        bit = jnp.where(up, bit + jnp.uint32(step), bit)
    return jnp.where(slot < ends[-1], owner * 32 + bit.astype(jnp.int32),
                     jnp.int32(fill))


def apply_inverse(perm: jnp.ndarray, payloads: List[jnp.ndarray]) -> List[jnp.ndarray]:
    """Return each payload re-ordered so slot perm[i] moves to slot i —
    i.e. payload[inverse_permutation(perm)] (sort by perm)."""
    return stable_sort((perm.astype(jnp.int32),) + tuple(payloads), 1)[1:]


def inverse_permutation(perm: jnp.ndarray) -> jnp.ndarray:
    """inv[perm[i]] = i, scatter-free (one int32 sort)."""
    return argsort32(perm.astype(jnp.int32))


def sorted_ranks(
    build_cols_sorted: List[jnp.ndarray],
    query_cols: List[jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per query row: (left_rank, match_count) against the lex-sorted build.

    ``left_rank`` = number of build tuples strictly less than the query
    (== searchsorted side='left'); ``match_count`` = number equal. Both
    int32, in original query order. Build columns must already be sorted
    lexicographically (most significant first); query columns are unordered.
    """
    nb = build_cols_sorted[0].shape[0]
    nq = query_cols[0].shape[0]
    n = nb + nq
    # combined STABLE sort with builds concatenated first: equal keys keep
    # builds before queries (no tag operand needed), payload = combined index
    operands = [
        jnp.concatenate([b, q]) if b.dtype == q.dtype
        else jnp.concatenate([
            b.astype(jnp.promote_types(b.dtype, q.dtype)),
            q.astype(jnp.promote_types(b.dtype, q.dtype)),
        ])
        for b, q in zip(build_cols_sorted, query_cols)
    ]
    idx_s = lex_argsort32(operands)
    sorted_cols = batched_gather(operands, idx_s)
    is_build = (idx_s < nb).astype(jnp.int32)
    prefix_incl = scans.cumsum(is_build, dtype=jnp.int32)
    prefix_excl = prefix_incl - is_build
    # equal-key run starts
    neq = jnp.zeros((max(n - 1, 0),), bool)
    for c in sorted_cols:
        neq = neq | (c[1:] != c[:-1])
    run_start = jnp.concatenate([jnp.ones((1,), bool), neq])
    # left rank for every slot of a run = build prefix at run start;
    # propagate by running max (prefixes are non-decreasing across runs)
    left_at_start = jnp.where(run_start, prefix_excl, jnp.int32(-1))
    # a two-level cummax, NOT associative_scan: the latter's unrolled
    # log-depth graph does not compile at multi-million rows on v5e
    left_all = scans.cummax(left_at_start)
    right_all = prefix_incl  # at query slots: builds <= query
    # back to query order (query i sits at combined index nb + i)
    left_o, right_o = apply_inverse(idx_s, [left_all, right_all])
    lo = left_o[nb:]
    counts = right_o[nb:] - lo
    return lo, counts

