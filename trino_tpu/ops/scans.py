"""Prefix scans (cumsum / cummax / cummin) in two levels.

A flat ``lax.cumsum`` / ``lax.cummax`` over a long 1-D array is one XLA
reduce-window whose compile time on the v5e grows with its length and its
dtype (compiler runs, PR 25: ``cumsum(int32[524288])`` 11 s,
``cummax(int32[2097152])`` 30 s, ``cummax(int64[191593])`` 173 s on the
chip) — and the eager tier compiles one per shape. The same scan as
``[n / 1024, 1024]`` rows scanned along the minor axis, plus a scan of the
row totals carried into the next row, compiles in under a second at every
length, and is the same streaming work at run time. Integer results are
bit-identical (wraparound included); float sums associate differently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_BLOCK = 1024


def _two_level(scan, combine, identity, x, reverse: bool):
    n = x.shape[0]
    if n <= _BLOCK:
        return scan(x, axis=0, reverse=reverse)
    pad = -n % _BLOCK
    fill = jnp.full((pad,), identity, x.dtype)
    rows = jnp.concatenate([fill, x] if reverse else [x, fill]).reshape(
        -1, _BLOCK)
    inner = scan(rows, axis=1, reverse=reverse)
    totals = inner[:, 0] if reverse else inner[:, -1]
    outer = _two_level(scan, combine, identity, totals, reverse)
    ident = jnp.full((1,), identity, x.dtype)
    carry = jnp.concatenate(
        [outer[1:], ident] if reverse else [ident, outer[:-1]])
    out = combine(inner, carry[:, None]).reshape(-1)
    return out[pad:] if reverse else out[:n]


def _lowest(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _highest(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dtype).max


@jax.named_scope("scan_cumsum")
def cumsum(x: jnp.ndarray, dtype=None) -> jnp.ndarray:
    """``jnp.cumsum(x, dtype=dtype)`` of a 1-D array (same result dtype)."""
    out = jax.eval_shape(lambda a: jnp.cumsum(a, dtype=dtype),
                         jax.ShapeDtypeStruct((1,), x.dtype)).dtype
    return _two_level(jax.lax.cumsum, jnp.add, 0, x.astype(out), False)


@jax.named_scope("scan_cummax")
def cummax(x: jnp.ndarray) -> jnp.ndarray:
    return _two_level(jax.lax.cummax, jnp.maximum, _lowest(x.dtype), x, False)


@jax.named_scope("scan_cummin")
def cummin(x: jnp.ndarray, reverse: bool = False) -> jnp.ndarray:
    return _two_level(jax.lax.cummin, jnp.minimum, _highest(x.dtype), x,
                      reverse)
