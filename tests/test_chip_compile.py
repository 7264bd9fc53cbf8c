"""Compile for the chip without the chip.

The TPU's compiler is installed here and compiles for a DESCRIBED
``v5e:2x2`` topology (nothing runs, so these say nothing about results or
times — ``chip_smoke.py`` on the chip does). What they guard: the Pallas
merge kernel and the whole-query XLA bodies keep LOWERING for the v5e at
TPC-H sf1 widths, which interpret mode and the CPU backend cannot show.

This is the only file that describes a topology, and it does so inside a
fixture: only the xdist worker that RUNS this file may load libtpu (one
process at a time holds it), and every worker must collect the same tests.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from tests.tpch_sql import QUERIES as TPCH_SQL

SF1_ORDERS = 1_500_000
SF1_LINEITEM = 6_001_215


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("block_build", [2048, 256])
def test_merge_kernel_compiles_at_sf1_widths(one_chip, block_build):
    from trino_tpu.ops import merge_pallas

    build = jax.ShapeDtypeStruct((SF1_ORDERS,), jnp.int32, sharding=one_chip)
    probe = jax.ShapeDtypeStruct((SF1_LINEITEM,), jnp.int32,
                                 sharding=one_chip)
    compiled = merge_pallas.merge_unique_sorted.lower(
        build, probe, block_build=block_build, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in


@pytest.mark.parametrize("query", [1, 3], ids=["q1", "q3"])
def test_compiled_query_body_compiles(one_chip, query):
    """``CompiledQuery.raw_fn``, the whole-query XLA body, traced over
    tpch.tiny's staged shapes, lowered for the v5e."""
    from trino_tpu import Session
    from trino_tpu.exec.compiled import CompiledQuery
    from trino_tpu.exec.query import plan_sql

    session = Session()
    cq = CompiledQuery.build(session, plan_sql(session, TPCH_SQL[query]))
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in cq.input_arrays]
    compiled = jax.jit(cq.raw_fn).lower(shapes).compile()
    assert compiled.memory_analysis() is not None


def test_q1_partial_aggregation_compiles_as_one_program(one_chip, monkeypatch):
    """The served path's hottest body: q1's partial aggregation (eight
    aggregates, two long-decimal limb sums, capacity 6) over one staged
    split of 524,288 rows, the spec taken from tpch.tiny and the program
    lowered for the v5e."""
    from trino_tpu import Session
    from trino_tpu.exec import executor
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.sql.planner import plan as P

    session = Session()
    (agg,) = [n for n in P.walk_plan(plan_sql(session, TPCH_SQL[1]))
              if isinstance(n, P.AggregationNode)]
    partial = P.AggregationNode(
        agg.source, list(agg.group_channels), agg.aggregates, step="partial")
    program = executor.direct_aggregation
    seen = []
    monkeypatch.setattr(
        executor, "direct_aggregation",
        lambda spec, arrays: seen.append((spec, arrays)) or program(spec, arrays))
    ex = executor.Executor(session)
    ex.aggregate_partial(partial, ex.execute(agg.source))
    (spec, arrays), = seen
    shapes = [jax.ShapeDtypeStruct((524_288,), a.dtype, sharding=one_chip)
              for a in arrays]
    compiled = program.lower(spec, shapes).compile()
    assert compiled.memory_analysis() is not None


def test_wide_sort_compiles_as_one_two_operand_sort(one_chip):
    """q3's ORDER BY at sf1 — 6 keys and 9 payloads over 31,869 rows,
    which as ONE ``lax.sort`` took the v5e compiler ~660 s on the chip
    (PR 25) — through ``ranks.stable_sort``: a single (int32, int32) sort
    instruction in a loop over the key digits, whatever the operands."""
    from trino_tpu.ops import ranks

    b, i8, i32, i64 = jnp.bool_, jnp.int8, jnp.int32, jnp.int64
    operands = [jax.ShapeDtypeStruct((31_869,), dt, sharding=one_chip)
                for dt in (b, i8, i64, i8, i64, i8,
                           i32, i32, i64, b, i64, i32, b, i32, b)]
    compiled = jax.jit(lambda *ops: ranks.stable_sort(ops, 6)).lower(
        *operands).compile()
    sorts = [line for line in compiled.as_text().splitlines()
             if " sort(" in line]
    assert len(sorts) == 1 and sorts[0].count("s32[32768]") >= 2, sorts


@pytest.mark.parametrize("n,size", [(62_914_560, 2_097_152),
                                    (524_288, 16_384)])
def test_true_positions_compiles_without_a_sort(one_chip, n, size):
    """Fragment 0 of q3 at SF 10 compacts a 60 x 2^20 row page to 2^21
    slots (and a scan split of ``tpch.sf1`` to a few thousand): the
    positions of the kept rows lower for the v5e with no sort instruction,
    in seconds, and inside the chip's memory beside the page."""
    from trino_tpu.ops import ranks

    mask = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(
        lambda m: ranks.true_positions(m, size, 0)).lower(mask).compile()
    assert not [line for line in compiled.as_text().splitlines()
                if " sort(" in line]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("source_rows,columns", [(62_914_560, 4),
                                                  (15_728_640, 4)])
def test_the_compacted_joins_gathers_compile_at_q3s_shapes(
        one_chip, source_rows, columns):
    """q3's lineitem-orders join at SF 10, squeezed to its Compact's 2^21
    slots before its payloads move (``Executor.compacted_lookup_join``):
    the probe side's columns and matched row ids out of 60 x 2^20 slots,
    then the four ``orders`` columns out of 15 x 2^20, each ONE row gather
    of a stacked ``[rows, 4]`` operand that fits the chip beside the
    tables."""
    from trino_tpu.ops import ranks

    arrays = tuple(jax.ShapeDtypeStruct((source_rows,), jnp.int32,
                                        sharding=one_chip)
                   for _ in range(columns))
    idx = jax.ShapeDtypeStruct((2_097_152,), jnp.int32, sharding=one_chip)
    compiled = ranks._gather_all.lower(arrays, idx).compile()
    text = compiled.as_text()
    assert f"s32[2097152,{columns}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("what", ["sums", "counts"])
def test_the_run_layouts_scans_compile_at_q18s_shape(one_chip, what):
    """Q18's group-by of lineitem by ``l_orderkey`` at SF 10: 60 x 2^20
    slots, already group-contiguous, aggregated by prefix scans alone
    (``ops/segments.py`` run layout), with no gather in the program: one
    62.9 M-slot gather out of a 62.9 M-row array took 2 s on the chip."""
    from trino_tpu.ops import segments as seg

    n = 62_914_560
    flag = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    if what == "sums":
        arg = jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip)
        compiled = jax.jit(seg._run_sums).lower(flag, arg).compile()
    else:
        compiled = jax.jit(seg._run_counts).lower(flag, flag).compile()
    assert " gather(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_spmd_hash_partitioned_q3_compiles_with_all_to_all(topo):
    """The SPMD tier's promise — shuffles are ICI collectives — checked
    in the program the v5e compiler emits. ``DistributedQuery`` stages onto
    the mesh it is built with, and nothing can be put on a described
    device: build on four CPU devices, then re-jit the same body over the
    described mesh and lower it with shapes."""
    from trino_tpu import Session
    from trino_tpu.exec.query import plan_sql
    from trino_tpu.parallel.spmd import AXIS, DistributedQuery
    from trino_tpu.sql.planner import stats

    session = Session()
    saved = (stats.GATHER_AGG_MAX_ROWS_PER_DEVICE, stats.BROADCAST_BUILD_MAX)
    try:
        # low thresholds force the hash-partitioned plan; they are read
        # again while the body is traced, so they stay low through lower()
        stats.GATHER_AGG_MAX_ROWS_PER_DEVICE = 8
        stats.BROADCAST_BUILD_MAX = 8
        dq = DistributedQuery.build(
            session, plan_sql(session, TPCH_SQL[3]),
            Mesh(np.array(jax.devices()[:4]), (AXIS,)))
        assert any(k.startswith("xchg") for k in dq.capacity_hints)
        chip_mesh = Mesh(np.array(topo.devices), (AXIS,))
        dq.mesh = chip_mesh
        dq._jit()
        sharded = NamedSharding(chip_mesh, PartitionSpec(AXIS))
        shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharded)
                  for a in dq.inputs]
        assert "all-to-all" in dq.fn.lower(shapes).compile().as_text()
    finally:
        stats.GATHER_AGG_MAX_ROWS_PER_DEVICE, stats.BROADCAST_BUILD_MAX = saved


@pytest.mark.parametrize("what", ["dense probe", "composite keys",
                                  "key range"])
def test_q9s_joins_compile_at_sf10_shapes(one_chip, what):
    """TPC-H Q9 at SF 10 as PR 36 plans it. Its first join probes 60 x 2^20
    lineitem slots against the 42.8 K green parts through a direct-address
    table over ``p_partkey``'s 2 M-key range (the range measured from the
    build, which came through an exchange); its partsupp join is a fused
    sort-merge on two key columns over the Compact's 2^21 slots and a
    2^18-slot build; a build's key range is three reductions over the
    column (orders' 15 x 2^20 slots the largest)."""
    from trino_tpu.ops import fused_join, join as join_ops

    def arr(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    if what == "dense probe":
        def probe(build_key, build_sel, probe_key):
            table = join_ops.dense_unique_table(
                (build_key, None), build_sel, 1, 2_000_000)
            return join_ops.dense_probe_unique(table, (probe_key, None), 1)

        compiled = jax.jit(probe).lower(
            arr(65_536, jnp.int64), arr(65_536, jnp.bool_),
            arr(62_914_560, jnp.int32)).compile()
        assert " sort(" not in compiled.as_text()
    elif what == "composite keys":
        def probe(b0, b1, bsel, p0, p1):
            return fused_join.fused_probe_unique(
                [(b0, None), (b1, None)], bsel, [(p0, None), (p1, None)])

        compiled = jax.jit(probe).lower(
            arr(262_144, jnp.int64), arr(262_144, jnp.int64),
            arr(262_144, jnp.bool_),
            arr(2_097_152, jnp.int64), arr(2_097_152, jnp.int64)).compile()
    else:
        def measure(vals, live):
            info = jnp.iinfo(vals.dtype)
            return (jnp.sum(live.astype(jnp.int32)),
                    jnp.where(live, vals, info.max).min(),
                    jnp.where(live, vals, info.min).max())

        compiled = jax.jit(measure).lower(
            arr(15_728_640, jnp.int64), arr(15_728_640, jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("build", ["in place", "dead tail"])
def test_q21s_residual_join_ranges_compile_without_a_sort(one_chip, build):
    """TPC-H Q21 at SF 10: its NOT EXISTS build is lineitem's 60 x 2^20
    slots under the lateness filter, its EXISTS build the same slots with a
    dead tail, and its probe the 2^20 slots of a Compact. The build is
    taken as it lies (``join_ops.in_place_build``: a running max and one
    reduction; a dead tail masked) and the probe's key ranges found by
    binary search (``join_ops.searched_counts``): no sort instruction of
    either side in the program."""
    from trino_tpu.ops import join as join_ops

    def arr(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    def ranges(build_key, build_sel, probe_key, probe_sel):
        if build == "in place":
            b = join_ops.in_place_build((build_key, None), build_sel)
        else:
            b = join_ops.build_side([(build_key, None)], build_sel,
                                    presorted=True)
        return join_ops.searched_counts(b, [(probe_key, None)], probe_sel)

    compiled = jax.jit(ranges).lower(
        arr(62_914_560, jnp.int32), arr(62_914_560, jnp.bool_),
        arr(1_048_576, jnp.int32), arr(1_048_576, jnp.bool_)).compile()
    assert " sort(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_q21s_expansion_and_any_reduce_compile_without_a_sort(one_chip):
    """Q21 at SF 10 expands each residual join's 2^20 probe slots into 2^22
    pair slots and reduces a flag a pair back to the probe rows: the slot
    owners from one scatter and running maxes (``join_ops.expand``), the
    any-reduce from the ranges' bounds (``join_ops.any_in_range``). No sort
    instruction in the program, where the ranks it replaced sorted
    2^23 rows twice for each."""
    from trino_tpu.ops import join as join_ops

    def expand_and_reduce(counts, flags):
        ex = join_ops.expand(counts, 4_194_304)
        return ex.p, ex.k, join_ops.any_in_range(ex, flags & ex.live)

    compiled = jax.jit(expand_and_reduce).lower(
        jax.ShapeDtypeStruct((1_048_576,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((4_194_304,), jnp.bool_,
                             sharding=one_chip)).compile()
    assert " sort(" not in compiled.as_text()
    assert " scatter(" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_q21s_supplier_gather_compiles_in_blocks(one_chip):
    """Q21 at SF 10 joins supplier's 100,000 rows to the 2^25 slots the
    orders join kept, and gathers three int32 payload columns at every one:
    as one [2^25, 3] row gather the v5e compiler asked 16 GiB (the result
    laid out 128 lanes wide); in blocks of ``ROW_GATHER_BLOCK`` slots it
    fits beside the tables."""
    from trino_tpu.ops import ranks

    arrays = tuple(jax.ShapeDtypeStruct((100_000,), jnp.int32,
                                        sharding=one_chip) for _ in range(3))
    idx = jax.ShapeDtypeStruct((33_554_432,), jnp.int32, sharding=one_chip)
    compiled = ranks._gather_all.lower(arrays, idx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# TPC-H Q13 at SF 10: customer's 1.5 M keys probe the 14.8 M orders that the
# ON clause keeps (``special requests``, the widest exclusion), and the plain
# LEFT join emits each match plus a null-extended row for each customer
# with none, 15.3 M rows at a capacity of 2^24
Q13_CUSTOMERS = 1_500_000
Q13_KEPT_ORDERS = 14_780_421
Q13_PAIRS = 16_777_216


@pytest.mark.parametrize("what", ["build sort", "expansion", "probe gather",
                                  "build gather"])
def test_q13s_outer_expansion_compiles_at_sf10_shapes(one_chip, what):
    """Each program of Q13's LEFT join at SF 10 in the eager tier
    (``Executor.expand_join``): the build's sort pass at 2^24 slots, the
    plain-outer expansion of the probe rows' counts into 2^24 slots, the
    probe rows' columns gathered to them in ONE batched gather, and the
    build's row ids and payload gathered there, in blocks over
    ``ROW_GATHER_MAX`` (as one ``[2^25, 3]`` row gather the compiler asked
    16 GiB: TPC-H Q21's supplier join). Each fits the chip beside the
    tables."""
    from trino_tpu.ops import join as join_ops, ranks

    def arr(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    idx = arr(Q13_PAIRS, jnp.int32)
    if what == "build sort":
        compiled = ranks._sort_pass.lower(
            arr(Q13_PAIRS, jnp.int32), arr(Q13_PAIRS, jnp.int32)).compile()
        assert " sort(" in compiled.as_text()
    elif what == "expansion":
        def outer(counts, live):
            emit = jnp.where(live, jnp.maximum(counts, 1), 0)
            ex = join_ops.expand(emit, Q13_PAIRS)
            return ex.p, ex.k, ex.live, ex.total

        compiled = jax.jit(outer).lower(
            arr(Q13_CUSTOMERS, jnp.int32),
            arr(Q13_CUSTOMERS, jnp.bool_)).compile()
        assert " sort(" not in compiled.as_text()
    elif what == "probe gather":
        # lo, counts and c_custkey
        arrays = tuple(arr(Q13_CUSTOMERS, jnp.int32) for _ in range(3))
        compiled = ranks._gather_all.lower(arrays, idx).compile()
    else:
        # the sorted build's row ids, then o_orderkey and o_custkey
        rows = jax.jit(lambda r, i: r[i]).lower(
            arr(Q13_PAIRS, jnp.int32), idx).compile()
        assert rows.memory_analysis().temp_size_in_bytes < 2 << 30
        arrays = tuple(arr(Q13_KEPT_ORDERS, jnp.int32) for _ in range(2))
        compiled = ranks._gather_all.lower(arrays, idx).compile()
        assert "s32[2097152,2]" in compiled.as_text()   # in blocks
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
