"""Sort kernel: stable multi-key argsort with SQL null placement.

Reference: ``operator/OrderByOperator.java`` + ``sql/gen/OrderingCompiler``
(type-specialized comparators). Here: per-key transform to a sortable int64/
float array (descending = negation, NULLs = rank-prefix keys per
nulls_first), then ONE stable lexicographic argsort
(ops/ranks.lex_argsort32). Dead rows (selection mask false) always
sort last so LIMIT/host slicing sees live rows first.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp

from trino_tpu.ops import ranks

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]


def _sort_key(vals, valid, ascending: bool, nulls_first: Optional[bool]):
    """Produce (null_rank_key, value_key) so NULLs land per SQL defaults:
    NULLS LAST for ASC, NULLS FIRST for DESC, unless specified.

    Keys keep their PHYSICAL dtype (data/page.py Column): int32-narrowed
    keys sort ~2x faster than emulated int64 on TPU. Descending integers
    reverse via bitwise NOT (~v = -v-1: order-reversing for the full dtype
    range, no INT_MIN negation overflow)."""
    if nulls_first is None:
        nulls_first = not ascending
    v = vals
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int8)
    if not ascending:
        v = -v if jnp.issubdtype(v.dtype, jnp.floating) else ~v
    if valid is None:
        return [v]
    null_rank = valid.astype(jnp.int8) if nulls_first else (~valid).astype(jnp.int8)
    return [null_rank, jnp.where(valid, v, jnp.zeros((), v.dtype))]


def _sort_operands(
    keys: List[Tuple[Lowered, bool, Optional[bool]]],
    sel: Optional[jnp.ndarray],
) -> List[jnp.ndarray]:
    sort_keys: List[jnp.ndarray] = []
    if sel is not None:
        sort_keys.append(~sel)  # dead rows last
    for (vals, valid), asc, nf in keys:
        sort_keys.extend(_sort_key(vals, valid, asc, nf))
    return sort_keys


def sort_payloads(
    keys: List[Tuple[Lowered, bool, Optional[bool]]],
    sel: Optional[jnp.ndarray],
    payloads: List[jnp.ndarray],
) -> List[jnp.ndarray]:
    """Every payload array permuted into sort order (dead rows last): the
    keys' argsort, then one batched gather per dtype group. (Payloads do
    not ride the sort as operands: each one multiplies the v5e compiler's
    time for the sort program — see ops/ranks.py.)"""
    sort_keys = _sort_operands(keys, sel)
    if not sort_keys:
        return list(payloads)
    return ranks.batched_gather(list(payloads), ranks.lex_argsort32(sort_keys))
