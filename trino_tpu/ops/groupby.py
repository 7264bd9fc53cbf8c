"""Group-by kernel: sort/segment based, static shapes, scatter-free.

Reference algorithm being replaced: ``operator/FlatHash.java:42`` (SWAR
control-byte open addressing) + ``FlatHashStrategyCompiler``. On TPU, a
sort + segment formulation maps better onto the VPU than scatter-heavy
hashing (SURVEY.md §7.1): stable multi-key argsort, boundary detection,
dense group ids via cumsum. Exact (comparison-based, no hash collisions),
null-safe (NULL is its own group), and selection-mask aware (dead rows sort
last, into trailing groups past ``num_groups``).

All downstream consumption happens in *sorted space* through
ops/segments.GroupLayout — integer scatters never appear (measured ~50x
slower than streaming ops on v5e; see ops/segments.py).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from trino_tpu.ops import ranks, scans

Lowered = Tuple[jnp.ndarray, Optional[jnp.ndarray]]  # (vals, valid|None)


@jax.named_scope("group_plan")
def group_plan(
    keys: List[Lowered], sel: Optional[jnp.ndarray], payloads=()
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, List[jnp.ndarray]]:
    """Permute rows group-contiguous and assign dense group ids.

    Returns (order[n] int32, gid_sorted[n] int32 non-decreasing,
    num_groups scalar, sorted_payloads). Dead rows (sel false) sort last
    and receive group ids >= num_groups; NULL keys group together (their
    own group). ``payloads`` come back permuted into sorted (layout)
    space with the keys — aggregate arguments group-contiguous (see
    segments.seg_sum) — by ops/ranks.stable_sort: the keys' digits sort,
    keys and payloads follow by one batched gather per dtype group."""
    n = keys[0][0].shape[0]
    dead = jnp.zeros((n,), dtype=bool) if sel is None else ~sel
    sort_keys: List[jnp.ndarray] = [dead]
    for vals, valid in keys:
        if valid is not None:
            sort_keys.append(~valid)
            sort_keys.append(jnp.where(valid, vals, jnp.zeros((), vals.dtype)))
        else:
            sort_keys.append(vals)
    iota = jnp.arange(n, dtype=jnp.int32)
    nk = len(sort_keys)
    out = ranks.stable_sort(tuple(sort_keys) + (iota,) + tuple(payloads), nk)
    gathered = out[:nk]
    order = out[nk]
    sorted_payloads = list(out[nk + 1:])
    boundary = jnp.zeros((n,), dtype=bool)
    for g in gathered:
        boundary = boundary | jnp.concatenate([jnp.ones((1,), bool), g[1:] != g[:-1]])
    gid_sorted = scans.cumsum(boundary.astype(jnp.int32)) - 1
    dead_sorted = gathered[0]
    num_groups = jnp.sum(boundary & ~dead_sorted)
    return order, gid_sorted, num_groups, sorted_payloads


@jax.named_scope("gather_group_keys")
def gather_group_keys(keys: List[Lowered], rep: jnp.ndarray) -> List[Lowered]:
    """Group-key output columns: gather each key at the representative row
    (rep indexes original row order; empty slots carry rep == n, clipped).
    One batched HBM pass for all keys (ranks.batched_gather)."""
    n = keys[0][0].shape[0]
    safe = jnp.clip(rep, 0, n - 1)
    arrays = [vals for vals, _ in keys] + [
        valid for _, valid in keys if valid is not None
    ]
    gathered = ranks.batched_gather(arrays, safe)
    out = []
    vi = len(keys)
    for i, (_, valid) in enumerate(keys):
        if valid is None:
            out.append((gathered[i], None))
        else:
            out.append((gathered[i], gathered[vi]))
            vi += 1
    return out
