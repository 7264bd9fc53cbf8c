"""``ops/ranks.stable_sort`` and ``ops/scans`` against the XLA primitives
they stand in for (``lax.sort``, ``jnp.cumsum``, ``lax.cummax/cummin``):
same rows, same dtypes, eagerly and under ``jit``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import ranks, scans


def _column(rng, kind: str, n: int) -> np.ndarray:
    if kind == "bool":
        return rng.integers(0, 2, n).astype(bool)
    if kind in ("float32", "float64"):
        x = rng.normal(size=n).astype(kind)
        for start, special in enumerate((0.0, -0.0, np.nan, np.inf, -np.inf)):
            x[start::11] = special
        return x
    info = np.iinfo(kind)
    x = rng.integers(info.min, info.max, n, dtype=kind, endpoint=True)
    x[::5], x[1::5], x[2::9] = info.min, info.max, 0
    return x


@pytest.mark.parametrize("n", [2, 127, 128, 129, 5000])
@pytest.mark.parametrize("kinds", [
    ("bool",), ("int8", "int8"), ("bool", "int8", "int64"), ("int32",),
    ("int64",), ("uint8", "uint32"), ("uint64",), ("float32",),
    ("bool", "int16", "int32", "int64", "bool"), ("int64", "int64"),
    ("bool", "float64"),  # float64 has no digit form: the lax.sort path
])
def test_stable_sort_is_lax_sort(kinds, n):
    rng = np.random.default_rng(n)
    keys = [jnp.asarray(_column(rng, k, n)) for k in kinds]
    # few distinct values in the leading key, so ties reach the later keys
    # and the stable order of the payloads
    keys[0] = keys[0] if kinds[0] == "bool" else (keys[0] % 3).astype(keys[0].dtype)
    payloads = [jnp.asarray(rng.integers(0, 100, n)),
                jnp.asarray(rng.normal(size=n))]
    operands = tuple(keys) + tuple(payloads)
    want = jax.lax.sort(operands, num_keys=len(keys), is_stable=True)
    eager = ranks.stable_sort(operands, len(keys))
    jitted = jax.jit(lambda ops: ranks.stable_sort(ops, len(keys)))(operands)
    for w, e, j in zip(want, eager, jitted):
        assert w.dtype == e.dtype == j.dtype
        np.testing.assert_array_equal(np.asarray(w), np.asarray(e))
        np.testing.assert_array_equal(np.asarray(w), np.asarray(j))


def test_sort_programs_are_shared_by_row_bucket():
    """Every length of one power-of-two bucket runs the same sort program."""
    assert {ranks._sort_bucket(n) for n in (499_146, 500_830, 524_288)} == {524_288}
    assert ranks._sort_bucket(524_289) == 1_048_576
    assert ranks._sort_bucket(3) == ranks._SORT_BUCKET_MIN


@pytest.mark.parametrize("n", [1, 5, 1024, 1025, 5000, 1_100_000])
@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float32, bool])
def test_two_level_scans_are_the_flat_scans(dtype, n):
    x = jnp.asarray(np.random.default_rng(n).integers(-50, 50, n).astype(dtype))
    for got, want in [
        (scans.cumsum(x), jnp.cumsum(x)),
        (scans.cumsum(x, dtype=jnp.int32), jnp.cumsum(x, dtype=jnp.int32)),
    ] + ([] if dtype is bool else [
        (scans.cummax(x), jax.lax.cummax(x)),
        (scans.cummin(x), jax.lax.cummin(x)),
        (scans.cummin(x, reverse=True), jax.lax.cummin(x, reverse=True)),
    ]):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
