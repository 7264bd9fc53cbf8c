"""Expression IR -> jax lowering: the query-time "compiler".

Reference role: ``sql/gen/ExpressionCompiler.java`` + ``PageFunctionCompiler
.java`` (bytecode-generates fused PageFilter/PageProjection over blocks) and
the ~40 per-op generators in ``sql/gen/*CodeGenerator.java``. Here the same
job is done by *tracing*: each expression lowers to jax ops over whole column
arrays; ``jax.jit`` + XLA fusion produce the fused filter/project kernel
(SURVEY.md §7.1 "kernels replace codegen").

Conventions:
- A lowered value is ``LoweredVal(vals, valid, dictionary)``:
  ``vals`` is a jax array (codes for varchar), ``valid`` is a bool array or
  None (= all valid), ``dictionary`` only for varchar.
- Three-valued logic: comparisons/arithmetic are null-strict; AND/OR are
  Kleene; see each op. (Reference: three-valued logic is threaded through the
  bytecode generators via "wasNull" slots; here it's an explicit mask.)
- Data-dependent runtime errors (division by zero, numeric overflow) cannot
  throw inside a compiled program; they are collected as error flags on the
  context and checked host-side after kernel execution (reference throws
  TrinoException synchronously — same user-visible outcome, deferred).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.data.dictionary import NULL_CODE, Dictionary
from trino_tpu.data.page import Column
from trino_tpu.ops import datetime_ops as dt
from trino_tpu.ops import expr_key
from trino_tpu.sql import ir

DIVISION_BY_ZERO = "DIVISION_BY_ZERO"
DECIMAL_OVERFLOW = "DECIMAL_OVERFLOW"
NUMERIC_OVERFLOW = "NUMERIC_VALUE_OUT_OF_RANGE"


@dataclasses.dataclass
class LoweredVal:
    vals: jnp.ndarray
    valid: Optional[jnp.ndarray]  # bool array; None = all valid
    dictionary: Optional[Dictionary] = None
    # Static bound on |stored value| (Python int; None = unknown), from
    # connector column stats (data/page.py Column.vrange) propagated by
    # interval arithmetic. Lets decimal ops skip the int128 limb path when
    # the range proves every intermediate fits int64 — the value-range
    # analog of the reference's precision-based short/long decimal split
    # (Int128Math vs long arithmetic).
    bound: Optional[int] = None
    # Nested (array/map) values: ``vals`` holds per-row int32 lengths and
    # ``children`` the flattened element LoweredVals (array: [elements],
    # map: [keys, values]) — mirroring data/page.py Column.children.
    children: Optional[List["LoweredVal"]] = None
    # Long-decimal high limb (data/page.py Column.hi): present -> ``vals``
    # is the low 64-bit pattern of an int128 value
    hi: Optional[jnp.ndarray] = None


class LowerCtx:
    """Lowering context: input columns, the page's selection mask, and
    collected error conditions. Errors only fire for rows that are both
    valid (non-NULL inputs) and selected (survived upstream filters) —
    matching the reference's semantics where filtered-out rows are never
    evaluated."""

    def __init__(self, columns: List[Column], num_rows: int, sel: Optional[jnp.ndarray] = None,
                 literals: Tuple = ()):
        self.columns = columns
        self.num_rows = num_rows
        self.sel = sel
        # the values of the expression's ops/expr_key.Literal stand-ins:
        # the scalar arguments of a program (exec/executor.py
        # expression_program)
        self.literals = literals
        self.errors: List[Tuple[str, jnp.ndarray]] = []

    def add_error(self, code: str, cond: jnp.ndarray, live: Optional[jnp.ndarray]):
        if live is not None:
            cond = cond & live
        if self.sel is not None:
            cond = cond & self.sel
        self.errors.append((code, jnp.any(cond)))


def and_valid(a: Optional[jnp.ndarray], b: Optional[jnp.ndarray]) -> Optional[jnp.ndarray]:
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def lower(expr: ir.Expr, ctx: LowerCtx) -> LoweredVal:
    if isinstance(expr, ir.ColumnRef):
        col = ctx.columns[expr.index]
        valid = None if col.nulls is None else ~col.nulls
        bound = None
        if col.vrange is not None and not jnp.issubdtype(col.values.dtype, jnp.floating):
            bound = expr_key.vrange_bound(col.vrange)
        children = None
        if col.children is not None:
            children = [
                LoweredVal(k.values, None if k.nulls is None else ~k.nulls, k.dictionary)
                for k in col.children
            ]
        return LoweredVal(col.values, valid, col.dictionary, bound, children, hi=col.hi)
    if isinstance(expr, ir.Constant):
        return _lower_constant(expr, ctx)
    if isinstance(expr, ir.Cast):
        return _lower_cast(expr, ctx)
    if isinstance(expr, ir.Case):
        return _lower_case(expr, ctx)
    if isinstance(expr, ir.Call):
        fn = FUNCTIONS.get(expr.name)
        if fn is None:
            raise NotImplementedError(f"scalar function not implemented: {expr.name}")
        return fn(ctx, expr)
    raise TypeError(f"unexpected IR node: {expr!r}")


def _const_array(ctx: LowerCtx, dtype, value) -> jnp.ndarray:
    return jnp.full((ctx.num_rows,), value, dtype=dtype)


def _lower_constant(expr: ir.Constant, ctx: LowerCtx) -> LoweredVal:
    t = expr.type
    if expr.value is None:
        dtype = t.np_dtype if t.np_dtype is not None else np.dtype(np.int32)
        children = None
        if t.is_nested:
            children = [
                LoweredVal(jnp.zeros((0,), ct.np_dtype or np.dtype(np.int64)), None,
                           Dictionary([]) if ct.is_varchar else None)
                for ct in T.type_children(t)
            ]
        return LoweredVal(
            _const_array(ctx, dtype, 0), jnp.zeros((ctx.num_rows,), dtype=bool), None,
            children=children,
        )
    if t.is_varchar:
        d = Dictionary([expr.value])
        return LoweredVal(_const_array(ctx, np.int32, 0), None, d)
    if isinstance(expr.value, expr_key.Literal):
        # a program's literal (or column-free subtree folded on the host):
        # its value is a scalar argument, its bound a term the program's
        # key answers questions of
        lit = expr.value
        value = ctx.literals[lit.index]
        for code in lit.errors:  # the folded subtree's, none of which fired
            ctx.errors.append((code, jnp.zeros((), dtype=bool)))
        bound = expr_key.Sym(("lit", lit.index)) if lit.bounded else None
        return LoweredVal(_const_array(ctx, value.dtype, value), None, None, bound)
    bound = abs(int(expr.value)) if has_bound(t) else None
    return LoweredVal(_const_array(ctx, t.np_dtype, expr.value), None, None, bound)


def has_bound(t: T.Type) -> bool:
    """Whether a literal of type ``t`` carries its magnitude as its bound."""
    return not (t.is_floating or t == T.BOOLEAN)


# ---------------------------------------------------------------------------
# varchar comparison support: align two lowered varchar values onto comparable
# integer code spaces (dictionaries are order-preserving, data/dictionary.py).
# ---------------------------------------------------------------------------


def _vocab_table(name: tuple, build, *dictionaries) -> jnp.ndarray:
    """``build(*dictionaries)``, a numpy table over the dictionaries' words,
    on the device. Inside a program (exec/executor.py expression_program) a
    page's dictionary is a stand-in with a channel and a size and no words:
    the table is then one of the program's arguments, asked for under
    ``name`` (ops/expr_key.table) and built by its caller from the page."""
    if all(isinstance(d, Dictionary) for d in dictionaries):
        return jnp.asarray(build(*dictionaries))
    return expr_key.table(name, build, dictionaries)


def _union_recode(d: Dictionary, other: Dictionary) -> np.ndarray:
    """``d``'s codes in the union of ``d``'s and ``other``'s words."""
    t = np.asarray(d.recode_table(d.merge(other)))
    return t if len(t) else np.array([NULL_CODE], np.int32)


def _align_varchar(a: LoweredVal, b: LoweredVal) -> Tuple[jnp.ndarray, jnp.ndarray]:
    da, db = a.dictionary, b.dictionary
    assert da is not None and db is not None
    if da is db or (isinstance(da, Dictionary) and isinstance(db, Dictionary)
                    and da.values == db.values):
        return a.vals, b.vals
    ta = _vocab_table(("union-recode",), _union_recode, da, db)
    tb = _vocab_table(("union-recode",), _union_recode, db, da)
    av = jnp.where(a.vals >= 0, ta[jnp.clip(a.vals, 0)], NULL_CODE)
    bv = jnp.where(b.vals >= 0, tb[jnp.clip(b.vals, 0)], NULL_CODE)
    return av, bv


def _comparison(op: Callable, negate_eq: bool = False) -> Callable:
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        b = lower(expr.args[1], ctx)
        at, bt = expr.args[0].type, expr.args[1].type
        if at.is_array and bt.is_array:
            out = _array_equal(a, b, at, bt)
            if negate_eq:
                return LoweredVal(~out.vals, out.valid, None)
            return out
        if a.hi is not None or b.hi is not None:
            # two-limb operand(s): compare as int128 at the common scale;
            # op applied to the {-1,0,1} comparator output vs 0 reproduces
            # every comparison operator (reference: Int128.compareTo)
            from trino_tpu.ops import int128 as i128

            if at.is_floating or bt.is_floating:
                fa = _to_float128(a, at)
                fb = _to_float128(b, bt)
                return LoweredVal(op(fa, fb), and_valid(a.valid, b.valid), None)
            s = max(_scale_of(at), _scale_of(bt))
            a128 = i128.rescale(as_i128(a), _scale_of(at), s)
            b128 = i128.rescale(as_i128(b), _scale_of(bt), s)
            cmp = i128.compare(a128, b128)
            return LoweredVal(
                op(cmp, jnp.zeros((), cmp.dtype)), and_valid(a.valid, b.valid), None
            )
        if at.is_varchar and bt.is_varchar:
            av, bv = _align_varchar(a, b)
        else:
            av, bv = _numeric_align(a.vals, at, b.vals, bt)
        return LoweredVal(op(av, bv), and_valid(a.valid, b.valid), None)

    return fn


def _array_equal(a: LoweredVal, b: LoweredVal, at, bt) -> LoweredVal:
    """SQL array equality (reference: ArrayDistinctFromOperator family):
    length mismatch -> false; any definite element mismatch -> false; else
    NULL if any compared element pair involves a NULL; else true. Runs over
    the LEFT flat layout with guarded gathers into the right's."""
    from trino_tpu.ops import array_ops as A

    a_len = a.vals.astype(jnp.int32)
    b_len = b.vals.astype(jnp.int32)
    a_off = A.offsets_from_lengths(a_len)
    b_off = A.offsets_from_lengths(b_len)
    ae, be = a.children[0], b.children[0]
    av, bv = ae.vals, be.vals
    if ae.dictionary is not None and be.dictionary is not None:
        av, bv = _align_varchar(
            LoweredVal(av, None, ae.dictionary), LoweredVal(bv, None, be.dictionary)
        )
    flat_n = int(av.shape[0])
    lens_eq = a_len == b_len
    if flat_n == 0:
        vals = lens_eq
        return LoweredVal(vals, and_valid(a.valid, b.valid), None)
    rowid = A.rowid_of_flat(a_off, flat_n)
    pos = jnp.arange(flat_n, dtype=jnp.int32) - a_off[rowid]
    active = (pos < a_len[rowid]) & lens_eq[rowid]
    bn = max(int(bv.shape[0]), 1)
    b_safe = bv if bv.shape[0] else jnp.zeros((1,), bv.dtype)
    b_idx = jnp.clip(b_off[rowid] + pos, 0, bn - 1)
    b_at = b_safe[b_idx]
    a_ok = ae.valid if ae.valid is not None else jnp.ones((flat_n,), bool)
    b_ok = (
        (be.valid if be.valid.shape[0] else jnp.zeros((1,), bool))[b_idx]
        if be.valid is not None
        else jnp.ones((flat_n,), bool)
    )
    if av.dtype != b_at.dtype:
        dt = jnp.promote_types(av.dtype, b_at.dtype)
        av, b_at = av.astype(dt), b_at.astype(dt)
    mismatch = active & a_ok & b_ok & (av != b_at)
    nullpair = active & (~a_ok | ~b_ok)
    any_mismatch = A.count_in_ranges(a_off, mismatch) > 0
    any_nullpair = A.count_in_ranges(a_off, nullpair) > 0
    vals = lens_eq & ~any_mismatch
    indeterminate = lens_eq & ~any_mismatch & any_nullpair
    valid = and_valid(and_valid(a.valid, b.valid), ~indeterminate)
    return LoweredVal(vals, valid, None)


def as_i128(lv: LoweredVal):
    """LoweredVal -> (hi, lo) int128 limbs (sign-extending when narrow)."""
    lo = lv.vals.astype(jnp.int64)
    hi = lv.hi if lv.hi is not None else (lo >> 63)
    return hi, lo


def _to_float128(lv: LoweredVal, t: T.Type) -> jnp.ndarray:
    """Two-limb (or plain) numeric value -> float64 at its decimal scale."""
    if lv.hi is None:
        v = lv.vals.astype(jnp.float64)
    else:
        ulo = lv.vals.astype(jnp.uint64).astype(jnp.float64)
        v = lv.hi.astype(jnp.float64) * float(2**64) + ulo
    if t.is_decimal:
        v = v / (10.0 ** _scale_of(t))
    return v


def _numeric_align(av, at: T.Type, bv, bt: T.Type):
    """Bring two numeric/date arrays to a common comparable representation."""
    if at.is_timestamp or bt.is_timestamp:
        # timestamps compare at the MAX precision; DATE promotes to the
        # other side's timestamp unit (UTC midnight)
        pa = at.precision if isinstance(at, T.TimestampType) else None
        pb = bt.precision if isinstance(bt, T.TimestampType) else None
        p = max(x for x in (pa, pb) if x is not None)

        def up(v, t):
            if t == T.DATE:
                return v.astype(jnp.int64) * (86_400 * 10**p)
            assert isinstance(t, T.TimestampType)
            return v.astype(jnp.int64) * (10 ** (p - t.precision))

        return up(av, at), up(bv, bt)
    if at.is_decimal or bt.is_decimal:
        sa = at.scale if isinstance(at, T.DecimalType) else 0
        sb = bt.scale if isinstance(bt, T.DecimalType) else 0
        if at.is_floating or bt.is_floating:
            fa = av / (10.0**sa) if at.is_decimal else av
            fb = bv / (10.0**sb) if bt.is_decimal else bv
            return fa.astype(jnp.float64), fb.astype(jnp.float64)
        s = max(sa, sb)
        return (
            av.astype(jnp.int64) * (10 ** (s - sa)),
            bv.astype(jnp.int64) * (10 ** (s - sb)),
        )
    if at.is_floating != bt.is_floating:
        return av.astype(jnp.float64), bv.astype(jnp.float64)
    return av, bv


def _rescale_decimal(v: jnp.ndarray, from_scale: int, to_scale: int) -> jnp.ndarray:
    if to_scale == from_scale:
        return v
    if to_scale > from_scale:
        return v * (10 ** (to_scale - from_scale))
    # round half-up toward +/- infinity (Trino decimal rescale semantics)
    div = 10 ** (from_scale - to_scale)
    q = jnp.floor_divide(jnp.abs(v) + div // 2, div)
    return jnp.sign(v) * q


def _scale_of(t: T.Type) -> int:
    return t.scale if isinstance(t, T.DecimalType) else 0


def _prec_of(t: T.Type) -> int:
    if isinstance(t, T.DecimalType):
        return t.precision
    return {"tinyint": 3, "smallint": 5, "integer": 10}.get(t.name, 19)


def _finish128(ctx, out128, valid, rt: T.Type, bound=None) -> LoweredVal:
    """Finish an int128 arithmetic result: flag DECIMAL_OVERFLOW past the
    result precision's 10^p cap (reference: Int128Math overflow checks /
    DecimalOperators rescale throws), then store two-limb for p > 18
    results and narrow to int64 for short ones (where |v| < 10^18 always
    fits). Reference: the short/long decimal storage split of
    spi/type/Int128.java, decided here by result type."""
    from trino_tpu.ops import int128 as i128

    p = min(_prec_of(rt), 38)
    limit = 10**p
    (ahi, alo), _ = i128.abs128(out128)
    lo_bits = limit & (2**64 - 1)
    lo_signed = lo_bits - 2**64 if lo_bits >= 2**63 else lo_bits
    lim = (jnp.full_like(ahi, limit >> 64), jnp.full_like(alo, lo_signed))
    over = i128.compare((ahi, alo), lim) >= 0
    ctx.add_error(DECIMAL_OVERFLOW, over, valid)
    if p > 18:
        return LoweredVal(out128[1], valid, None, bound, hi=out128[0])
    return LoweredVal(i128.to_int64(out128), valid, None, bound)


def _rescaled_bound(bound: int, from_scale: int, to_scale: int) -> int:
    """Bound on |v| after rescaling from from_scale to to_scale."""
    if to_scale >= from_scale:
        return bound * 10 ** (to_scale - from_scale)
    return bound // 10 ** (from_scale - to_scale) + 1


_INT64_SAFE = 2**62  # int128-skip threshold: proven intermediates below this


def _arith(name: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        b = lower(expr.args[1], ctx)
        at, bt, rt = expr.args[0].type, expr.args[1].type, expr.type
        valid = and_valid(a.valid, b.valid)
        av, bv = a.vals, b.vals
        ba, bb = a.bound, b.bound
        have_bounds = ba is not None and bb is not None
        out_bound = None
        if rt.is_decimal and not (at.is_floating or bt.is_floating):
            from trino_tpu.ops import int128 as i128

            rs = _scale_of(rt)
            sa, sb = _scale_of(at), _scale_of(bt)
            pa, pb = _prec_of(at), _prec_of(bt)
            two_limb_in = a.hi is not None or b.hi is not None
            if two_limb_in:
                have_bounds = False  # bounds never cover two-limb values
            if name in ("add", "sub"):
                # int128 path when a rescaled operand or the result can
                # exceed 18 digits (reference: Int128Math add/subtract) —
                # UNLESS static bounds prove an int64 fit (the value-range
                # analog of the short/long decimal split)
                need128 = two_limb_in or max(pa + (rs - sa), pb + (rs - sb)) > 18
                if need128 and have_bounds:
                    s = _rescaled_bound(ba, sa, rs) + _rescaled_bound(bb, sb, rs)
                    if s < _INT64_SAFE:
                        need128 = False
                        out_bound = s
                elif not need128 and have_bounds:
                    out_bound = _rescaled_bound(ba, sa, rs) + _rescaled_bound(bb, sb, rs)
                if need128:
                    a128, ova = i128.rescale_checked(as_i128(a), sa, rs)
                    b128, ovb = i128.rescale_checked(as_i128(b), sb, rs)
                    ctx.add_error(DECIMAL_OVERFLOW, ova | ovb, valid)
                    out128 = i128.add(a128, b128) if name == "add" else i128.sub(a128, b128)
                    return _finish128(ctx, out128, valid, rt)
                av = _rescale_decimal(av.astype(jnp.int64), sa, rs)
                bv = _rescale_decimal(bv.astype(jnp.int64), sb, rs)
                out = av + bv if name == "add" else av - bv
            elif name == "mul":
                need128 = two_limb_in or pa + pb + 1 > 18
                if have_bounds:
                    prod_bound = ba * bb * (10 ** max(rs - sa - sb, 0))
                    if need128 and prod_bound < _INT64_SAFE:
                        need128 = False
                    if prod_bound < _INT64_SAFE:
                        out_bound = _rescaled_bound(ba * bb, sa + sb, rs)
                if need128:
                    if two_limb_in:
                        prod, ovm = i128.mul_checked(as_i128(a), as_i128(b))
                        ctx.add_error(DECIMAL_OVERFLOW, ovm, valid)
                    else:
                        prod = i128.mul_int64(av.astype(jnp.int64), bv.astype(jnp.int64))
                    return _finish128(ctx, i128.rescale(prod, sa + sb, rs), valid, rt)
                out = _rescale_decimal(av.astype(jnp.int64) * bv.astype(jnp.int64), sa + sb, rs)
            elif name == "div":
                if b.hi is not None:
                    # two-limb divisor: full 128/128 long division, half-up
                    bh, bl = as_i128(b)
                    is_zero = (bh == 0) & (bl == 0)
                    ctx.add_error(DIVISION_BY_ZERO, is_zero, valid)
                    shift = rs - sa + sb
                    num128, ovn = i128.rescale_checked(as_i128(a), 0, shift)
                    ctx.add_error(DECIMAL_OVERFLOW, ovn, valid)
                    nabs, nneg = i128.abs128(num128)
                    dabs, dneg = i128.abs128((bh, jnp.where(is_zero, 1, bl)))
                    q, r = i128.divmod_u128(nabs, dabs)
                    # round half away from zero: 2r >= d
                    r2 = i128.add(r, r)
                    r2h = r2[0].astype(jnp.uint64)
                    dh = dabs[0].astype(jnp.uint64)
                    up = (r2h > dh) | ((r2h == dh) & (
                        r2[1].astype(jnp.uint64) >= dabs[1].astype(jnp.uint64)))
                    q = i128.add(q, (jnp.zeros_like(q[0]), up.astype(jnp.int64)))
                    negq = i128.neg(q)
                    flip = nneg ^ dneg
                    out128 = (jnp.where(flip, negq[0], q[0]),
                              jnp.where(flip, negq[1], q[1]))
                    return _finish128(ctx, out128, valid, rt)
                ctx.add_error(DIVISION_BY_ZERO, bv == 0, valid)
                shift = rs - sa + sb
                den64 = jnp.where(bv == 0, 1, bv.astype(jnp.int64))
                need128 = two_limb_in or pa + shift > 18
                if need128 and have_bounds and ba * 10 ** max(shift, 0) < _INT64_SAFE:
                    need128 = False
                    out_bound = ba * 10 ** max(shift, 0)
                if need128:
                    # 128-bit numerator / 64-bit divisor, half-up
                    num128, ovn = i128.rescale_checked(as_i128(a), 0, shift)
                    ctx.add_error(DECIMAL_OVERFLOW, ovn, valid)
                    (nhi, nlo), nneg = i128.abs128(num128)
                    dabs = jnp.abs(den64).astype(jnp.uint64)
                    q, r = i128.divmod_u64_arr((nhi, nlo), dabs)
                    up = r * 2 >= dabs
                    q = i128.add(q, (jnp.zeros_like(q[0]), up.astype(jnp.int64)))
                    negq = i128.neg(q)
                    flip = nneg ^ (den64 < 0)
                    out128 = (jnp.where(flip, negq[0], q[0]), jnp.where(flip, negq[1], q[1]))
                    return _finish128(ctx, out128, valid, rt)
                num = av.astype(jnp.int64) * (10 ** shift)
                q = jnp.floor_divide(jnp.abs(num) + jnp.abs(den64) // 2, jnp.abs(den64))
                out = jnp.sign(num) * jnp.sign(den64) * q
            elif name == "mod":
                if two_limb_in:
                    # no limb kernel: degrade to the low words with the
                    # deferred overflow check (pre-limb-storage contract)
                    for opnd in (a, b):
                        if opnd.hi is not None:
                            lo64 = opnd.vals.astype(jnp.int64)
                            ctx.add_error(
                                DECIMAL_OVERFLOW, opnd.hi != (lo64 >> 63), valid)
                    av = a.vals
                    bv = b.vals
                s = max(sa, sb)
                av = _rescale_decimal(av.astype(jnp.int64), sa, s)
                bv = _rescale_decimal(bv.astype(jnp.int64), sb, s)
                ctx.add_error(DIVISION_BY_ZERO, bv == 0, valid)
                bv = jnp.where(bv == 0, 1, bv)
                out = jnp.sign(av) * jnp.mod(jnp.abs(av), jnp.abs(bv))
                out = _rescale_decimal(out, s, rs)
                if have_bounds:
                    bound_s = min(_rescaled_bound(ba, sa, s), _rescaled_bound(bb, sb, s))
                    out_bound = _rescaled_bound(bound_s, s, rs)
            else:
                raise AssertionError(name)
            return LoweredVal(out, valid, None, out_bound)
        if rt.is_floating:
            fa = av.astype(jnp.float64) / (10.0 ** _scale_of(at)) if at.is_decimal else av
            fb = bv.astype(jnp.float64) / (10.0 ** _scale_of(bt)) if bt.is_decimal else bv
            fa = fa.astype(jnp.float64 if rt == T.DOUBLE else jnp.float32)
            fb = fb.astype(jnp.float64 if rt == T.DOUBLE else jnp.float32)
            if name == "add":
                out = fa + fb
            elif name == "sub":
                out = fa - fb
            elif name == "mul":
                out = fa * fb
            elif name == "div":
                out = fa / fb
            elif name == "mod":
                out = jnp.where(fb != 0, fa - fb * jnp.trunc(fa / fb), jnp.nan)
            else:
                raise AssertionError(name)
            return LoweredVal(out, valid, None)
        # integer kinds (and date +/- integer days)
        av = av.astype(rt.np_dtype)
        bv = bv.astype(rt.np_dtype)
        if name == "add":
            out = av + bv
            out_bound = ba + bb if have_bounds else None
        elif name == "sub":
            out = av - bv
            out_bound = ba + bb if have_bounds else None
        elif name == "mul":
            out = av * bv
            out_bound = ba * bb if have_bounds else None
        elif name == "div":
            ctx.add_error(DIVISION_BY_ZERO, bv == 0, valid)
            den = jnp.where(bv == 0, 1, bv)
            out = jnp.sign(av) * jnp.sign(den) * jnp.floor_divide(jnp.abs(av), jnp.abs(den))
            out_bound = ba if have_bounds else None
        elif name == "mod":
            ctx.add_error(DIVISION_BY_ZERO, bv == 0, valid)
            den = jnp.where(bv == 0, 1, bv)
            out = jnp.sign(av) * jnp.mod(jnp.abs(av), jnp.abs(den))
            out_bound = min(ba, bb) if have_bounds else None
        else:
            raise AssertionError(name)
        return LoweredVal(out, valid, None, out_bound)

    return fn


def _lower_and(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """Kleene AND: FALSE dominates NULL."""
    a = lower(expr.args[0], ctx)
    b = lower(expr.args[1], ctx)
    if a.valid is None and b.valid is None:
        return LoweredVal(a.vals & b.vals, None, None)
    a_valid = a.valid if a.valid is not None else jnp.ones_like(a.vals)
    b_valid = b.valid if b.valid is not None else jnp.ones_like(b.vals)
    known_false = ((~a.vals) & a_valid) | ((~b.vals) & b_valid)
    return LoweredVal(
        (a.vals | ~a_valid) & (b.vals | ~b_valid),  # unknown -> TRUE for the value
        known_false | (a_valid & b_valid),
        None,
    )


def _lower_or(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """Kleene OR: TRUE dominates NULL."""
    a = lower(expr.args[0], ctx)
    b = lower(expr.args[1], ctx)
    if a.valid is None and b.valid is None:
        return LoweredVal(a.vals | b.vals, None, None)
    a_valid = a.valid if a.valid is not None else jnp.ones_like(a.vals)
    b_valid = b.valid if b.valid is not None else jnp.ones_like(b.vals)
    known_true = (a.vals & a_valid) | (b.vals & b_valid)
    return LoweredVal(known_true, known_true | (a_valid & b_valid), None)


def _lower_not(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    return LoweredVal(~a.vals, a.valid, None)


def _lower_is_null(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    if a.valid is None:
        return LoweredVal(jnp.zeros((ctx.num_rows,), dtype=bool), None, None)
    return LoweredVal(~a.valid, None, None)


def _lower_between(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x, lo, hi = expr.args
    ge = lower(ir.Call(T.BOOLEAN, "ge", (x, lo)), ctx)
    le = lower(ir.Call(T.BOOLEAN, "le", (x, hi)), ctx)
    return LoweredVal(ge.vals & le.vals, and_valid(ge.valid, le.valid), None)


def _lower_in_list(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """x IN (c1, ..., cn) — SQL semantics: TRUE if any match; NULL if no
    match and (x is NULL or any list item is NULL); else FALSE."""
    hits = None
    any_null_item = False
    x = expr.args[0]
    for item in expr.args[1:]:
        if isinstance(item, ir.Constant) and item.value is None:
            any_null_item = True
            continue
        eq = lower(ir.Call(T.BOOLEAN, "eq", (x, item)), ctx)
        h = eq.vals if eq.valid is None else eq.vals & eq.valid
        hits = h if hits is None else hits | h
    if hits is None:
        hits = jnp.zeros((ctx.num_rows,), dtype=bool)
    xl = lower(x, ctx)
    x_null = jnp.zeros((ctx.num_rows,), dtype=bool) if xl.valid is None else ~xl.valid
    unknown = (~hits) & (x_null | any_null_item)
    return LoweredVal(hits, ~unknown if (any_null_item or xl.valid is not None) else None, None)


def _lower_like(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """LIKE on dictionary-coded varchar: evaluate the pattern host-side over
    the vocabulary once, then gather the boolean LUT by code on device.

    Reference: ``operator/scalar/likematcher`` (Joni/RE2J DFA per pattern) —
    the dictionary makes it a O(|vocab|) host precompute instead.
    """
    x = lower(expr.args[0], ctx)
    pat = expr.args[1]
    assert isinstance(pat, ir.Constant), "LIKE pattern must be a literal (round 1)"
    assert x.dictionary is not None
    rx = re.compile(_like_to_regex(pat.value), re.S)

    def matches(d: Dictionary) -> np.ndarray:
        lut = np.array([rx.fullmatch(v) is not None for v in d.values], dtype=bool)
        return lut if len(lut) else np.zeros((1,), dtype=bool)

    lut = _vocab_table(("like", pat.value), matches, x.dictionary)
    n = len(x.dictionary)
    out = jnp.where(x.vals >= 0, lut[jnp.clip(x.vals, 0, max(n - 1, 0))], False)
    return LoweredVal(out, x.valid, None)


def _like_to_regex(pattern: str, escape: Optional[str] = None) -> str:
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if escape and c == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return "".join(out)


def _vocab_transform(ctx: LowerCtx, x: LoweredVal, fn) -> LoweredVal:
    """Apply a host-side string->string function over the dictionary
    vocabulary once, rebuild an (order-preserving) dictionary, and recode on
    device — the dictionary-first analog of Trino's per-row scalar string
    functions (operator/scalar/StringFunctions.java)."""
    assert x.dictionary is not None
    mapped = [fn(v) for v in x.dictionary.values]
    d_new = Dictionary.build(mapped)
    lut = np.array([d_new.code_of(m) for m in mapped], dtype=np.int32)
    lut_dev = jnp.asarray(lut) if len(lut) else jnp.zeros((1,), dtype=np.int32)
    out = jnp.where(
        x.vals >= 0, lut_dev[jnp.clip(x.vals, 0, max(len(lut) - 1, 0))], NULL_CODE
    )
    return LoweredVal(out, x.valid, d_new)


def _sql_substring(v: str, start: int, length: Optional[int]) -> str:
    """Trino substr semantics (StringFunctions.substr): 1-based; start 0 or
    out of range yields ''; negative start counts from the end; the optional
    length bounds the window from the (normalized) start."""
    n = len(v)
    if start == 0:
        return ""
    if start > 0:
        if start > n:
            return ""
        i = start - 1
    else:
        if -start > n:
            return ""
        i = n + start
    end = n if length is None else min(n, i + max(length, 0))
    return v[i:end]


def _lower_substring(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    start_e = expr.args[1]
    len_e = expr.args[2] if len(expr.args) > 2 else None
    assert isinstance(start_e, ir.Constant), "substring start must be a literal"
    start = int(start_e.value)
    length = None
    if len_e is not None:
        assert isinstance(len_e, ir.Constant), "substring length must be a literal"
        length = int(len_e.value)
    return _vocab_transform(ctx, x, lambda v: _sql_substring(v, start, length))


def _lower_str_fn(pyfn) -> Callable:
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        x = lower(expr.args[0], ctx)
        return _vocab_transform(ctx, x, pyfn)

    return fn


def _const_str_args(expr: ir.Call, start: int) -> List[str]:
    out = []
    for a in expr.args[start:]:
        assert isinstance(a, ir.Constant) and isinstance(a.value, str), (
            f"{expr.name}: pattern arguments must be varchar literals")
        out.append(a.value)
    return out


def _lower_replace(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    frm, to = (_const_str_args(expr, 1) + [""])[:2] if len(expr.args) == 2 \
        else _const_str_args(expr, 1)
    return _vocab_transform(ctx, x, lambda v: v.replace(frm, to))


def _lower_reverse(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    return _vocab_transform(ctx, x, lambda v: v[::-1])


def _vocab_lut(ctx: LowerCtx, x: LoweredVal, pyfn, np_dtype) -> LoweredVal:
    """varchar -> scalar via a per-vocab-entry lookup table (the
    dictionary-first analog of per-row scalar evaluation)."""
    assert x.dictionary is not None
    lut = np.array([pyfn(v) for v in x.dictionary.values], dtype=np_dtype)
    lut_dev = jnp.asarray(lut) if len(lut) else jnp.zeros((1,), dtype=np_dtype)
    out = jnp.where(
        x.vals >= 0,
        lut_dev[jnp.clip(x.vals, 0, max(len(lut) - 1, 0))],
        jnp.zeros((), np_dtype),
    )
    return LoweredVal(out, x.valid, None)


def _lower_strpos(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    (sub,) = _const_str_args(expr, 1)
    return _vocab_lut(ctx, x, lambda v: v.find(sub) + 1, np.int64)


def _lower_starts_with(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    (prefix,) = _const_str_args(expr, 1)
    return _vocab_lut(ctx, x, lambda v: v.startswith(prefix), np.bool_)


def _lower_binary_fn(kind: str):
    """varbinary scalar family over the hex-string dictionary (reference:
    operator/scalar/VarbinaryFunctions.java): to_hex/from_hex/to_utf8/
    from_utf8/md5/sha256 are all vocabulary transforms."""
    import hashlib

    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        x = lower(expr.args[0], ctx)
        if kind == "to_hex":
            return _vocab_transform(ctx, x, lambda h: h.upper())
        if kind == "from_hex":
            # dictionary-wide evaluation sees vocab entries of rows the
            # query may never touch: an invalid entry must not abort the
            # host transform. Invalid codes become NULL slots and a
            # deferred INVALID_FUNCTION_ARGUMENT fires iff a LIVE row
            # actually references one (correct-or-error, never silent).
            vocab = x.dictionary.values if x.dictionary is not None else []
            bad_codes = []
            mapped = []
            for i, s in enumerate(vocab):
                try:
                    mapped.append(bytes.fromhex(s).hex())
                except ValueError:
                    mapped.append("")
                    bad_codes.append(i)
            out = _vocab_transform(
                ctx, x, lambda s, _m=dict(zip(vocab, mapped)): _m.get(s, ""))
            if bad_codes:
                bad = jnp.isin(x.vals, jnp.asarray(np.array(bad_codes, np.int32)))
                ctx.add_error(INVALID_FUNCTION_ARGUMENT, bad, x.valid)
                valid = (x.valid if x.valid is not None
                         else jnp.ones(ctx.num_rows, bool)) & ~bad
                out = LoweredVal(out.vals, valid, out.dictionary)
            return out
        if kind == "to_utf8":
            return _vocab_transform(ctx, x, lambda s: s.encode().hex())
        if kind == "from_utf8":
            return _vocab_transform(
                ctx, x, lambda h: bytes.fromhex(h).decode(errors="replace"))
        digest = {"md5": hashlib.md5, "sha256": hashlib.sha256}[kind]
        return _vocab_transform(
            ctx, x, lambda h: digest(bytes.fromhex(h)).hexdigest())

    return fn


def _lower_row_ctor(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """ROW(a, b, ...): one child column per field, same row count as the
    parent (reference: RowBlock — field blocks share positions). The row
    value itself is non-null; field nulls live in the children."""
    items = [lower(a, ctx) for a in expr.args]
    return LoweredVal(
        jnp.zeros((ctx.num_rows,), jnp.int8), None, None, children=items)


def _lower_row_field(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """row[i] field access (1-based constant ordinal). A NULL row makes
    every field NULL (reference: DereferenceExpression null semantics)."""
    base = lower(expr.args[0], ctx)
    idx_e = expr.args[1]
    assert isinstance(idx_e, ir.Constant)
    field = base.children[int(idx_e.value) - 1]
    valid = and_valid(base.valid, field.valid)
    return LoweredVal(field.vals, valid, field.dictionary,
                      children=field.children, hi=field.hi)


def _lower_length(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    if expr.args[0].type.is_varbinary:
        # dictionary entries are hex: two hex digits per byte
        return _vocab_lut(ctx, x, lambda s: len(s) // 2, np.int64)
    return _vocab_lut(ctx, x, len, np.int64)


def _lower_concat(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """concat where at most one argument is a column (vocab transform);
    general column||column needs a pairwise dictionary product (not yet implemented)."""
    col_args = [a for a in expr.args if not isinstance(a, ir.Constant)]
    # SQL semantics: concat with a NULL argument yields NULL for every row
    # (reference: operator/scalar/ConcatFunction).
    if any(isinstance(a, ir.Constant) and a.value is None for a in expr.args):
        d = Dictionary([""])
        return LoweredVal(
            _const_array(ctx, np.int32, 0),
            jnp.zeros((ctx.num_rows,), dtype=bool),
            d,
        )
    if not col_args:
        s = "".join(_concat_text(a) for a in expr.args)
        d = Dictionary([s])
        return LoweredVal(_const_array(ctx, np.int32, 0), None, d)
    if len(col_args) > 1:
        raise NotImplementedError("concat of multiple varchar columns")
    (col_e,) = col_args
    x = lower(col_e, ctx)
    pre = "".join(
        _concat_text(a) for a in expr.args[: expr.args.index(col_e)]
    )
    post = "".join(
        _concat_text(a) for a in expr.args[expr.args.index(col_e) + 1 :]
    )
    return _vocab_transform(ctx, x, lambda v: pre + v + post)


def _concat_text(a: ir.Constant) -> str:
    """Render a constant concat argument as its cast-to-varchar text,
    decoding the STORAGE repr by type: scaled ints print as decimals,
    epoch days as ISO dates (reference: operator/scalar cast-to-varchar
    semantics, not Python repr of the storage value)."""
    if isinstance(a.value, str):
        return a.value
    if isinstance(a.value, bool):
        return "true" if a.value else "false"
    t = a.type
    if t.is_decimal:
        from decimal import Decimal

        return str(Decimal(int(a.value)).scaleb(-t.scale))
    if t == T.DATE:
        import datetime

        return (datetime.date(1970, 1, 1)
                + datetime.timedelta(days=int(a.value))).isoformat()
    return str(a.value)


def _lower_coalesce(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    acc = lower(expr.args[0], ctx)
    for nxt_expr in expr.args[1:]:
        if acc.valid is None:
            return acc
        nxt = lower(nxt_expr, ctx)
        hi = None
        if acc.hi is not None or nxt.hi is not None:
            ah, al = as_i128(acc)
            bh, bl = as_i128(nxt)
            vals = jnp.where(acc.valid, al, bl)
            hi = jnp.where(acc.valid, ah, bh)
        else:
            vals = jnp.where(acc.valid, acc.vals, nxt.vals)
        nxt_valid = nxt.valid if nxt.valid is not None else jnp.ones_like(acc.valid)
        acc = LoweredVal(vals, acc.valid | nxt_valid,
                         acc.dictionary or nxt.dictionary, hi=hi)
    return acc


def _ts_split(vals, t: T.Type):
    """Timestamp storage -> (epoch days, in-day unit remainder, unit/sec).
    Floor semantics keep pre-epoch instants on the correct day."""
    assert isinstance(t, T.TimestampType)
    unit = 10 ** t.precision
    day = 86_400 * unit
    v = vals.astype(jnp.int64)
    days = jnp.floor_divide(v, day)
    rem = v - days * day
    return days.astype(jnp.int32), rem, unit


def _lower_extract(field: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        t = expr.args[0].type
        if isinstance(t, T.TimestampType):
            days, rem, unit = _ts_split(a.vals, t)
            if field in ("hour", "minute", "second"):
                secs = rem // unit
                out = {"hour": secs // 3600,
                       "minute": (secs // 60) % 60,
                       "second": secs % 60}[field].astype(jnp.int64)
                return LoweredVal(out, a.valid, None)
            out = getattr(dt, f"extract_{field}")(days)
            return LoweredVal(out, a.valid, None)
        if field in ("hour", "minute", "second"):
            raise NotImplementedError(f"extract({field}) over {t}")
        out = getattr(dt, f"extract_{field}")(a.vals)
        return LoweredVal(out, a.valid, None)

    return fn


def _lower_date_add_months(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    n = lower(expr.args[1], ctx)
    t = expr.args[0].type
    if isinstance(t, T.TimestampType):
        # shift the DAY part through the calendar; the in-day time-of-day
        # remainder is calendar-invariant
        days, rem, unit = _ts_split(a.vals, t)
        new_days = dt.add_months(days, n.vals).astype(jnp.int64)
        out = new_days * (86_400 * unit) + rem
        return LoweredVal(out, and_valid(a.valid, n.valid), None)
    out = dt.add_months(a.vals, n.vals).astype(jnp.int32)
    return LoweredVal(out, and_valid(a.valid, n.valid), None)


def _lower_date_diff_days(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    b = lower(expr.args[1], ctx)
    per = int(expr.args[2].value)
    d = (b.vals.astype(jnp.int64) - a.vals.astype(jnp.int64))
    # truncate toward zero in day units (reference diffDate semantics)
    q = jnp.sign(d) * (jnp.abs(d) // per)
    return LoweredVal(q.astype(jnp.int64), and_valid(a.valid, b.valid), None)


def _lower_ts_diff_units(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    b = lower(expr.args[1], ctx)
    per = int(expr.args[2].value)
    d = b.vals.astype(jnp.int64) - a.vals.astype(jnp.int64)
    q = jnp.sign(d) * (jnp.abs(d) // per)
    return LoweredVal(q.astype(jnp.int64), and_valid(a.valid, b.valid), None)


def _lower_months_between(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """Whole calendar months from a to b, truncating partial months, then
    divided by the unit multiplier (12 for years) — reference
    DateTimeFunctions.diffDate month/year semantics."""
    a = lower(expr.args[0], ctx)
    b = lower(expr.args[1], ctx)
    mul = int(expr.args[2].value)
    ya, ma, da = dt.extract_year(a.vals), dt.extract_month(a.vals), dt.extract_day(a.vals)
    yb, mb, db = dt.extract_year(b.vals), dt.extract_month(b.vals), dt.extract_day(b.vals)
    months = (yb - ya) * 12 + (mb - ma)
    # partial trailing month doesn't count — but the day-of-month compare
    # CLAMPS to each end's month length, so Jan 31 -> Feb 29 is one full
    # month (consistent with add_months' month-end clamp and the
    # reference's Joda-style diffDate)
    da_in_b = jnp.minimum(da, dt.days_in_month(yb, mb))
    db_in_a = jnp.minimum(db, dt.days_in_month(ya, ma))
    months = months - jnp.where((months > 0) & (db < da_in_b), 1, 0)
    months = months + jnp.where((months < 0) & (db_in_a > da), 1, 0)
    q = jnp.sign(months) * (jnp.abs(months) // mul)
    return LoweredVal(q.astype(jnp.int64), and_valid(a.valid, b.valid), None)


def _lower_seconds_to_ts3(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    ms = a.vals.astype(jnp.float64) * 1000.0
    v = (jnp.sign(ms) * jnp.floor(jnp.abs(ms) + 0.5)).astype(jnp.int64)
    return LoweredVal(v, a.valid, None)


def _lower_date_trunc(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    unit_e = expr.args[0]
    assert isinstance(unit_e, ir.Constant) and isinstance(unit_e.value, str), (
        "date_trunc unit must be a varchar literal")
    a = lower(expr.args[1], ctx)
    out = dt.trunc_date(a.vals, unit_e.value.lower()).astype(jnp.int32)
    return LoweredVal(out, a.valid, None)


def _lower_atan2(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = _arg_double(ctx, expr.args[0])
    b = _arg_double(ctx, expr.args[1])
    return LoweredVal(jnp.arctan2(a.vals, b.vals), and_valid(a.valid, b.valid), None)


def _lower_truncate(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """truncate(x[, d]): drop digits past d decimal places, toward zero
    (reference: MathFunctions.truncate both arities)."""
    a = lower(expr.args[0], ctx)
    t = expr.args[0].type
    d = 0
    if len(expr.args) == 2:
        d_e = expr.args[1]
        assert isinstance(d_e, ir.Constant)
        d = int(d_e.value)
    if t.is_floating:
        p = 10.0 ** d
        return LoweredVal(jnp.trunc(a.vals * p) / p, a.valid, None)
    if t.is_decimal:
        keep = max(t.scale - d, 0)
        p = 10 ** keep
        v = a.vals
        return LoweredVal(jnp.where(v >= 0, v // p, -((-v) // p)) * p, a.valid, None)
    return LoweredVal(a.vals, a.valid, None)


def _arg_double(ctx: LowerCtx, arg: ir.Expr) -> LoweredVal:
    a = lower(arg, ctx)
    t = arg.type
    v = a.vals.astype(jnp.float64)
    if t.is_decimal:
        v = v / (10.0 ** t.scale)
    return LoweredVal(v, a.valid, None)


def _lower_math1(op):
    """Unary double math (sqrt/ln/exp/...): decimal args convert through
    their scale; domain violations produce NaN/inf like the reference's
    double semantics."""

    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = _arg_double(ctx, expr.args[0])
        return LoweredVal(op(a.vals), a.valid, None)

    return fn


def _lower_log10(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """log10 — reference MathFunctions.log10 delegates to Math.log10,
    which is correctly rounded on exact powers of ten. jnp.log10 lowers to
    the ln(x)·log10(e) composition, which drifts a ULP (log10(1000) =
    2.9999999999999996) and fails exact comparisons. Concrete (eager-tier)
    inputs take the host np.log10 path; traced values (jit/shard_map
    tiers) stay on-device with the jnp composition."""
    import jax

    a = _arg_double(ctx, expr.args[0])
    if isinstance(a.vals, jax.core.Tracer):
        return LoweredVal(jnp.log10(a.vals), a.valid, None)
    # domain violations produce NaN/-inf like the device op — silently
    # (numpy warns where jnp does not; NULL slots carry garbage backing
    # values that must not spam stderr per scan batch)
    with np.errstate(divide="ignore", invalid="ignore"):
        out = np.log10(np.asarray(a.vals))
    return LoweredVal(jnp.asarray(out), a.valid, None)


def _lower_log_b(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """log(base, x) — reference MathFunctions.log(double, double)."""
    b = _arg_double(ctx, expr.args[0])
    x = _arg_double(ctx, expr.args[1])
    return LoweredVal(
        jnp.log(x.vals) / jnp.log(b.vals), and_valid(b.valid, x.valid), None
    )


def _lower_power(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = _arg_double(ctx, expr.args[0])
    b = _arg_double(ctx, expr.args[1])
    return LoweredVal(jnp.power(a.vals, b.vals), and_valid(a.valid, b.valid), None)


def _lower_sign(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    t = expr.args[0].type
    if t.is_floating:
        return LoweredVal(jnp.sign(a.vals.astype(jnp.float64)), a.valid, None)
    return LoweredVal(jnp.sign(a.vals).astype(jnp.int64), a.valid, None)


def _round_half_away(x: jnp.ndarray, factor) -> jnp.ndarray:
    """Round to ``d`` decimal places, half away from zero (reference:
    MathFunctions.round double semantics)."""
    scaled = x * factor
    return jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5) / factor


def _lower_round(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    t = expr.args[0].type
    d = 0
    if len(expr.args) > 1:
        dc = expr.args[1]
        if not isinstance(dc, ir.Constant):
            raise NotImplementedError("round() digits must be a literal")
        d = int(dc.value)
    if t.is_floating:
        return LoweredVal(_round_half_away(a.vals.astype(jnp.float64), 10.0 ** d), a.valid, None)
    if t.is_decimal:
        s_ = t.scale
        if d >= s_:
            return a
        div = 10 ** (s_ - d)
        v = a.vals.astype(jnp.int64)
        q = jnp.sign(v) * jnp.floor_divide(jnp.abs(v) + div // 2, div)
        return LoweredVal(q * div, a.valid, None)
    if d >= 0:
        return a  # integers: already whole
    div = 10 ** (-d)  # round(1234, -2) = 1200, half away from zero
    v = a.vals.astype(jnp.int64)
    q = jnp.sign(v) * jnp.floor_divide(jnp.abs(v) + div // 2, div)
    return LoweredVal((q * div).astype(a.vals.dtype), a.valid, None)


def _lower_ceil_floor(is_ceil: bool):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        t = expr.args[0].type
        if t.is_floating:
            op = jnp.ceil if is_ceil else jnp.floor
            return LoweredVal(op(a.vals.astype(jnp.float64)), a.valid, None)
        if t.is_decimal and t.scale > 0:
            div = 10 ** t.scale
            v = a.vals.astype(jnp.int64)
            if is_ceil:
                q = -jnp.floor_divide(-v, div)
            else:
                q = jnp.floor_divide(v, div)
            return LoweredVal(q * div, a.valid, None)
        return a

    return fn


def _lower_extremum(is_greatest: bool):
    """greatest/least: NULL if ANY argument is NULL (reference semantics).
    Varchar operands align onto one merged dictionary first (codes are
    order-consistent because dictionaries are sorted, data/dictionary.py)."""

    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        parts = [lower(a, ctx) for a in expr.args]
        op = jnp.maximum if is_greatest else jnp.minimum
        if expr.type.is_varchar:
            acc = parts[0]
            for p in parts[1:]:
                av, bv = _align_varchar(acc, p)
                merged = (
                    acc.dictionary
                    if acc.dictionary.values == p.dictionary.values
                    else acc.dictionary.merge(p.dictionary)
                )
                acc = LoweredVal(op(av, bv), and_valid(acc.valid, p.valid), merged)
            return acc
        out = parts[0].vals
        valid = parts[0].valid
        for p in parts[1:]:
            out = op(out, p.vals)
            valid = and_valid(valid, p.valid)
        return LoweredVal(out, valid, None)

    return fn


def _lower_negate(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    if a.hi is not None:
        from trino_tpu.ops import int128 as i128

        nhi, nlo = i128.neg(as_i128(a))
        return LoweredVal(nlo, a.valid, None, hi=nhi)
    return LoweredVal(-a.vals, a.valid, None)


def _lower_abs(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    if a.hi is not None:
        from trino_tpu.ops import int128 as i128

        (ahi, alo), _ = i128.abs128(as_i128(a))
        return LoweredVal(alo, a.valid, None, hi=ahi)
    return LoweredVal(jnp.abs(a.vals), a.valid, None)


def _lower_nullif(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    eq = lower(ir.Call(T.BOOLEAN, "eq", (expr.args[0], expr.args[1])), ctx)
    hit = eq.vals if eq.valid is None else eq.vals & eq.valid
    valid = (~hit) if a.valid is None else (a.valid & ~hit)
    return LoweredVal(a.vals, valid, a.dictionary, hi=a.hi)


def _unify_branch_dicts(branches):
    """Recode dictionary-coded branch values onto ONE merged vocabulary
    (CASE/coalesce-style multi-branch varchar results must agree on codes;
    branch dictionaries differ whenever literals mix with columns).
    Returns (recoded branches, merged dictionary)."""
    merged = None
    for v in branches:
        if v is None or v.dictionary is None:
            continue
        if merged is None:
            merged = v.dictionary
        elif merged.values != v.dictionary.values:
            merged = merged.merge(v.dictionary)
    if merged is None:
        return branches, None

    def recode(v):
        if v is None or v.dictionary is None \
                or v.dictionary.values == merged.values:
            return v
        tbl = jnp.asarray(
            np.asarray(v.dictionary.recode_table(merged), dtype=np.int32))
        nv = jnp.where(v.vals >= 0, tbl[jnp.clip(v.vals, 0)],
                       jnp.int32(NULL_CODE))
        return LoweredVal(nv, v.valid, merged, children=v.children, hi=v.hi)

    return [recode(v) for v in branches], merged


def _lower_case(expr: ir.Case, ctx: LowerCtx) -> LoweredVal:
    """Searched CASE: first WHEN whose condition is TRUE wins."""
    dtype = expr.type.np_dtype
    vals = jnp.zeros((ctx.num_rows,), dtype=dtype)
    valid = jnp.zeros((ctx.num_rows,), dtype=bool)
    decided = jnp.zeros((ctx.num_rows,), dtype=bool)
    dictionary = None
    hi = None  # grows when any branch carries a two-limb long decimal
    conds = [lower(c, ctx) for c, _ in expr.whens]
    branch_vals = [lower(v, ctx) for _, v in expr.whens]
    default_l = lower(expr.default, ctx) if expr.default is not None else None
    if expr.type.is_varchar:
        unified, dictionary = _unify_branch_dicts(branch_vals + [default_l])
        branch_vals, default_l = unified[:-1], unified[-1]
    for c, v in zip(conds, branch_vals):
        cv = c.vals if c.valid is None else c.vals & c.valid
        take = cv & ~decided
        if v.hi is not None and hi is None:
            hi = vals.astype(jnp.int64) >> 63  # promote accumulated branches
        if hi is not None:
            vh, vl = as_i128(v)
            vals = jnp.where(take, vl, vals.astype(jnp.int64))
            hi = jnp.where(take, vh, hi)
        else:
            vals = jnp.where(take, v.vals.astype(dtype), vals)
        valid = jnp.where(take, v.valid if v.valid is not None else True, valid)
        decided = decided | take
    if default_l is not None:
        d = default_l
        if d.hi is not None and hi is None:
            hi = vals.astype(jnp.int64) >> 63
        if hi is not None:
            dh, dl = as_i128(d)
            vals = jnp.where(decided, vals.astype(jnp.int64), dl)
            hi = jnp.where(decided, hi, dh)
        else:
            vals = jnp.where(decided, vals, d.vals.astype(dtype))
        valid = jnp.where(decided, valid, d.valid if d.valid is not None else True)
    return LoweredVal(vals, valid, dictionary, hi=hi)


def _lower_cast(expr: ir.Cast, ctx: LowerCtx) -> LoweredVal:
    a = lower(expr.value, ctx)
    ft, tt = expr.value.type, expr.type
    if ft == tt:
        return a
    if ft == T.UNKNOWN:
        # typed NULL: every row invalid, representation per target type
        dtype = tt.np_dtype if tt.np_dtype is not None else np.dtype(np.int32)
        if tt.is_nested:
            def null_child(ct: T.Type, n: int) -> LoweredVal:
                cd = ct.np_dtype if ct.np_dtype is not None else np.dtype(np.int32)
                # a ROW child's fields share its row count; array/map
                # children have zero flat elements (lengths are all 0)
                kids = ([null_child(k, n if isinstance(ct, T.RowType) else 0)
                         for k in T.type_children(ct)]
                        if ct.is_nested else None)
                vals = (jnp.full((n,), NULL_CODE, jnp.int32) if ct.is_varchar
                        else jnp.zeros((n,), cd))
                return LoweredVal(
                    vals, jnp.zeros((n,), bool),
                    Dictionary([]) if ct.is_varchar else None, children=kids)

            n = ctx.num_rows
            flat_n = n if isinstance(tt, T.RowType) else 0
            kids = [null_child(k, flat_n) for k in T.type_children(tt)]
            return LoweredVal(
                _const_array(ctx, dtype, 0),
                jnp.zeros((n,), bool), None, children=kids)
        return LoweredVal(
            _const_array(ctx, dtype, 0),
            jnp.zeros((ctx.num_rows,), bool),
            Dictionary([]) if tt.is_varchar else None,
        )
    if isinstance(tt, T.TimestampType):
        if isinstance(ft, T.TimestampType):
            # precision rescale (round half up, like decimal rescale —
            # reference TimestampType cast semantics); the with-time-zone
            # flip is representation-free (UTC storage both sides)
            v = _rescale_decimal(
                a.vals.astype(jnp.int64), ft.precision, tt.precision)
            return LoweredVal(v.astype(jnp.int64), a.valid, None)
        if ft == T.DATE:
            return LoweredVal(
                a.vals.astype(jnp.int64) * (86_400 * 10**tt.precision),
                a.valid, None)
        raise NotImplementedError(f"cast {ft} -> {tt}")
    if tt == T.DATE and isinstance(ft, T.TimestampType):
        unit = 86_400 * 10**ft.precision
        return LoweredVal(
            jnp.floor_divide(a.vals.astype(jnp.int64), unit).astype(jnp.int32),
            a.valid, None)
    if tt.is_floating:
        if a.hi is not None:
            return LoweredVal(_to_float128(a, ft).astype(tt.np_dtype), a.valid, None)
        v = a.vals.astype(jnp.float64)
        if ft.is_decimal:
            v = v / (10.0 ** _scale_of(ft))
        return LoweredVal(v.astype(tt.np_dtype), a.valid, None)
    if tt.is_decimal:
        rs = _scale_of(tt)
        if a.hi is not None:
            from trino_tpu.ops import int128 as i128

            out128, ov = i128.rescale_checked(as_i128(a), _scale_of(ft), rs)
            ctx.add_error(DECIMAL_OVERFLOW, ov, a.valid)
            return _finish128(ctx, out128, a.valid, tt)
        if ft.is_floating:
            scaled = a.vals.astype(jnp.float64) * (10.0**rs)
            # half away from zero (reference DecimalCasts), not jnp.round's
            # half-to-even
            v = (jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5)).astype(jnp.int64)
            bound = None
        elif ft.is_decimal:
            v = _rescale_decimal(a.vals.astype(jnp.int64), _scale_of(ft), rs)
            bound = None if a.bound is None else _rescaled_bound(a.bound, _scale_of(ft), rs)
        else:
            v = a.vals.astype(jnp.int64) * (10**rs)
            bound = None if a.bound is None else a.bound * 10**rs
        return LoweredVal(v, a.valid, None, bound)
    if tt.is_integer_kind:
        if ft.is_decimal:
            if a.hi is not None:
                from trino_tpu.ops import int128 as i128

                out128 = i128.rescale(as_i128(a), _scale_of(ft), 0)
                ctx.add_error(NUMERIC_OVERFLOW, ~i128.fits_int64(out128), a.valid)
                return LoweredVal(
                    i128.to_int64(out128).astype(tt.np_dtype), a.valid, None
                )
            v = _rescale_decimal(a.vals.astype(jnp.int64), _scale_of(ft), 0)
            bound = None if a.bound is None else _rescaled_bound(a.bound, _scale_of(ft), 0)
        elif ft.is_floating:
            v = jnp.sign(a.vals) * jnp.floor(jnp.abs(a.vals) + 0.5)
            bound = None
        else:
            v = a.vals
            bound = a.bound
        return LoweredVal(v.astype(tt.np_dtype), a.valid, None, bound)
    if tt == T.DATE and ft.is_varchar:
        raise NotImplementedError("cast(varchar as date) lowering: not yet supported")
    if tt.is_varchar:
        # varbinary and varchar share the dictionary layout but NOT the
        # encoding (hex vs text): cast re-encodes through the vocabulary
        # (reference: VarbinaryFunctions' varchar<->varbinary casts = utf8)
        if ft.is_varchar and ft.is_varbinary and not tt.is_varbinary:
            return _vocab_transform(
                ctx, a, lambda h: bytes.fromhex(h).decode(errors="replace"))
        if ft.is_varchar and not ft.is_varbinary and tt.is_varbinary:
            return _vocab_transform(ctx, a, lambda s: s.encode().hex())
        if ft.is_varchar:  # varchar(n) <-> varchar: same codes/dictionary
            return LoweredVal(a.vals, a.valid, a.dictionary)
        raise NotImplementedError("cast to varchar lowering: not yet supported")
    return LoweredVal(a.vals.astype(tt.np_dtype), a.valid, a.dictionary)


# --- scalar breadth: regexp / JSON / datetime strings / bitwise ----------
# Varchar functions are DICTIONARY TRANSFORMS: the host applies the Python
# implementation once per vocab entry, the device gathers codes through a
# lookup table (_vocab_transform/_vocab_lut) — O(vocab) host work replaces
# O(rows) per-row evaluation (reference: operator/scalar/StringFunctions,
# JoniRegexpFunctions, JsonFunctions evaluate per row).


def _lower_regexp(kind: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        x = lower(expr.args[0], ctx)
        pat_e = expr.args[1]
        assert isinstance(pat_e, ir.Constant) and isinstance(pat_e.value, str), (
            "regexp pattern must be a varchar literal")
        pattern = re.compile(pat_e.value)
        if kind == "like":
            return _vocab_lut(ctx, x, lambda v: pattern.search(v) is not None, np.bool_)
        if kind == "count":
            return _vocab_lut(
                ctx, x, lambda v: len(pattern.findall(v)), np.int64)
        if kind == "extract":
            group = 0
            if len(expr.args) == 3:
                a2 = expr.args[2]
                assert isinstance(a2, ir.Constant), "regexp group must be a literal"
                group = int(a2.value)

            def ext(v):
                m = pattern.search(v)
                return m.group(group) if m else ""

            # NULL result when no match (Trino returns NULL, not ''):
            has = _vocab_lut(ctx, x, lambda v: pattern.search(v) is not None, np.bool_)
            out = _vocab_transform(ctx, x, ext)
            return LoweredVal(out.vals, and_valid(out.valid, has.vals), out.dictionary)
        # replace
        repl = _const_str_args(expr, 2)[0] if len(expr.args) == 3 else ""
        repl_py = re.sub(r"\$(\d+)", r"\\\1", repl)  # $1 -> \1 (Trino syntax)
        return _vocab_transform(ctx, x, lambda v: pattern.sub(repl_py, v))

    return fn


def _lower_pad(left: bool):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        x = lower(expr.args[0], ctx)
        size_e = expr.args[1]
        assert isinstance(size_e, ir.Constant), "pad size must be a literal"
        size = int(size_e.value)
        pad = _const_str_args(expr, 2)[0] if len(expr.args) == 3 else " "

        def dopad(v):
            if len(v) >= size:
                return v[:size]
            fill = (pad * size)[: size - len(v)]
            return fill + v if left else v + fill

        return _vocab_transform(ctx, x, dopad)

    return fn


def _lower_split_part(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    delim_e = expr.args[1]
    assert isinstance(delim_e, ir.Constant) and isinstance(delim_e.value, str), (
        "split_part delimiter must be a varchar literal")
    delim = delim_e.value
    idx_e = expr.args[2]
    assert isinstance(idx_e, ir.Constant), "split_part index must be a literal"
    idx = int(idx_e.value)

    def part(v):
        parts = v.split(delim)
        return parts[idx - 1] if 1 <= idx <= len(parts) else ""

    # out-of-range index -> NULL (Trino)
    has = _vocab_lut(
        ctx, x, lambda v: 1 <= idx <= len(v.split(delim)), np.bool_)
    out = _vocab_transform(ctx, x, part)
    return LoweredVal(out.vals, and_valid(out.valid, has.vals), out.dictionary)


def _lower_translate(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    frm, to = _const_str_args(expr, 1)
    table = {ord(f): (to[i] if i < len(to) else None) for i, f in enumerate(frm)}
    return _vocab_transform(ctx, x, lambda v: v.translate(table))


def _lower_repeat_str(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    n_e = expr.args[1]
    assert isinstance(n_e, ir.Constant), "repeat count must be a literal"
    k = int(n_e.value)
    return _vocab_transform(ctx, x, lambda v: v * k)


def _lower_chr(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = expr.args[0]
    if isinstance(a, ir.Constant):
        d = Dictionary([chr(int(a.value))])
        return LoweredVal(_const_array(ctx, np.int32, 0), None, d)
    raise NotImplementedError("chr() over a column (value-dependent vocabulary)")


def _lower_codepoint(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    return _vocab_lut(ctx, x, lambda v: ord(v[0]) if v else 0, np.int64)


def _lower_str_distance(kind: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        x = lower(expr.args[0], ctx)
        other = _const_str_args(expr, 1)[0]
        if kind == "hamming":
            def dist(v):
                if len(v) != len(other):
                    return -1
                return sum(a != b for a, b in zip(v, other))
        else:
            def dist(v):
                # classic O(nm) DP over the (small) vocab
                if not v:
                    return len(other)
                prev = list(range(len(other) + 1))
                for i, cv in enumerate(v, 1):
                    cur = [i]
                    for j, co in enumerate(other, 1):
                        cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                                       prev[j - 1] + (cv != co)))
                    prev = cur
                return prev[-1]

        out = _vocab_lut(ctx, x, dist, np.int64)
        if kind == "hamming":
            bad = out.vals < 0
            ctx.add_error(INVALID_FUNCTION_ARGUMENT, bad, out.valid)
        return out

    return fn


def _lower_json_extract_scalar(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    import json

    x = lower(expr.args[0], ctx)
    path = _const_str_args(expr, 1)[0]
    steps = _parse_json_path(path)

    def ext(v):
        try:
            cur = json.loads(v)
        except (ValueError, TypeError):
            return None
        for s in steps:
            if isinstance(s, int):
                if not isinstance(cur, list) or not -len(cur) <= s < len(cur):
                    return None
                cur = cur[s]
            else:
                if not isinstance(cur, dict) or s not in cur:
                    return None
                cur = cur[s]
        if cur is None or isinstance(cur, (dict, list)):
            return None  # json_extract_scalar: scalars only
        if isinstance(cur, bool):
            return "true" if cur else "false"
        return str(cur)

    has = _vocab_lut(ctx, x, lambda v: ext(v) is not None, np.bool_)
    out = _vocab_transform(ctx, x, lambda v: ext(v) or "")
    return LoweredVal(out.vals, and_valid(out.valid, has.vals), out.dictionary)


def _parse_json_path(path: str):
    """Subset of the JSON path language: $.a.b[0]['c'] (reference:
    JsonPath — the lax default mode's field/subscript steps)."""
    steps = []
    s = path.strip()
    if not s.startswith("$"):
        raise NotImplementedError(f"json path must start with $: {path!r}")
    s = s[1:]
    token = re.compile(r"\.(\w+)|\[(\d+)\]|\['([^']*)'\]|\[\"([^\"]*)\"\]")
    pos = 0
    while pos < len(s):
        m = token.match(s, pos)
        if not m:
            raise NotImplementedError(f"unsupported json path step at {s[pos:]!r}")
        if m.group(1) is not None:
            steps.append(m.group(1))
        elif m.group(2) is not None:
            steps.append(int(m.group(2)))
        else:
            steps.append(m.group(3) if m.group(3) is not None else m.group(4))
        pos = m.end()
    return steps


def _lower_json_array_length(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    import json

    x = lower(expr.args[0], ctx)

    def ln(v):
        try:
            arr = json.loads(v)
        except (ValueError, TypeError):
            return -1
        return len(arr) if isinstance(arr, list) else -1

    out = _vocab_lut(ctx, x, ln, np.int64)
    return LoweredVal(out.vals, and_valid(out.valid, out.vals >= 0), None)


_MYSQL_FMT = {  # date_format uses MySQL-style specifiers (reference:
    # DateTimeFunctions.dateFormat)
    "%Y": "%Y", "%y": "%y", "%m": "%m", "%d": "%d", "%e": "%-d",
    "%H": "%H", "%i": "%M", "%s": "%S", "%f": "%f", "%W": "%A",
    "%a": "%a", "%b": "%b", "%M": "%B", "%j": "%j", "%%": "%%",
}


def _mysql_to_py_fmt(fmt: str) -> str:
    out = []
    i = 0
    while i < len(fmt):
        if fmt[i] == "%" and i + 1 < len(fmt):
            spec = fmt[i : i + 2]
            out.append(_MYSQL_FMT.get(spec, spec))
            i += 2
        else:
            out.append(fmt[i])
            i += 1
    return "".join(out)


def _date_lut(ctx: LowerCtx, x: LoweredVal, pyfn, fallback_range=(-25567, 47847)):
    """date (epoch days) -> string via a day-indexed lookup table bounded by
    the column's static value range (Column.vrange via LoweredVal.bound) or
    a 1900..2100 fallback — the numeric->varchar analog of the vocab
    transform: the VALUE is the code."""
    import datetime

    lo, hi = fallback_range
    if x.bound is not None:
        lo, hi = -x.bound, x.bound
        lo, hi = max(lo, fallback_range[0]), min(hi, fallback_range[1])
    epoch = datetime.date(1970, 1, 1)
    strings = [
        pyfn(epoch + datetime.timedelta(days=d)) for d in range(lo, hi + 1)
    ]
    d_new = Dictionary.build(strings)
    lut = np.array([d_new.code_of(sv) for sv in strings], dtype=np.int32)
    idx = jnp.clip(x.vals.astype(jnp.int32) - lo, 0, len(lut) - 1)
    in_range = (x.vals >= lo) & (x.vals <= hi)
    # out-of-range dates fail LOUDLY (deferred error) rather than silently
    # returning NULL — the window is an implementation bound, not semantics
    ctx.add_error(INVALID_FUNCTION_ARGUMENT, ~in_range, x.valid)
    out = jnp.asarray(lut)[idx]
    return LoweredVal(out, and_valid(x.valid, in_range), d_new)


def _lower_date_format(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    fmt = _mysql_to_py_fmt(_const_str_args(expr, 1)[0])
    if expr.args[0].type == T.TIMESTAMP:
        raise NotImplementedError("date_format over timestamps (use a date)")
    return _date_lut(ctx, x, lambda d: d.strftime(fmt))


def _lower_date_parse(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    import datetime

    x = lower(expr.args[0], ctx)
    fmt = _mysql_to_py_fmt(_const_str_args(expr, 1)[0])

    def parse(v):
        try:
            d = datetime.datetime.strptime(v, fmt).date()
        except ValueError:
            return -(10**9)
        return (d - datetime.date(1970, 1, 1)).days

    out = _vocab_lut(ctx, x, parse, np.int32)
    bad = out.vals == -(10**9)
    ctx.add_error(INVALID_FUNCTION_ARGUMENT, bad, out.valid)
    return LoweredVal(out.vals, out.valid, None)


def _lower_day_name(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    return _date_lut(ctx, x, lambda d: d.strftime("%A"))


def _lower_month_name(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    return _date_lut(ctx, x, lambda d: d.strftime("%B"))


def _lower_last_day_of_month(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    x = lower(expr.args[0], ctx)
    y = dt.extract_year(x.vals)
    m = dt.extract_month(x.vals)
    d = dt.extract_day(x.vals)
    days = x.vals.astype(jnp.int32)
    last = days - d.astype(jnp.int32) + dt.days_in_month(y, m).astype(jnp.int32)
    return LoweredVal(last, x.valid, None)


def _lower_bitwise(op: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        av = a.vals.astype(jnp.int64)
        if op == "not":
            return LoweredVal(~av, a.valid, None)
        b = lower(expr.args[1], ctx)
        bv = b.vals.astype(jnp.int64)
        valid = and_valid(a.valid, b.valid)
        if op == "and":
            return LoweredVal(av & bv, valid, None)
        if op == "or":
            return LoweredVal(av | bv, valid, None)
        if op == "xor":
            return LoweredVal(av ^ bv, valid, None)
        # shift >= 64 yields 0 (reference BitwiseFunctions); negative
        # shift amounts are invalid arguments
        ctx.add_error(INVALID_FUNCTION_ARGUMENT, bv < 0, valid)
        in_range = (bv >= 0) & (bv < 64)
        sh = jnp.clip(bv, 0, 63)
        if op == "lshift":
            out = jnp.where(in_range, av << sh, jnp.int64(0))
            return LoweredVal(out, valid, None)
        shifted = (av.astype(jnp.uint64) >> sh.astype(jnp.uint64)).astype(jnp.int64)
        return LoweredVal(jnp.where(in_range, shifted, jnp.int64(0)), valid, None)

    return fn


def _lower_bit_count(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    v = a.vals.astype(jnp.int64).astype(jnp.uint64)
    cnt = jnp.zeros(v.shape, jnp.int64)
    lut = jnp.asarray(np.array([bin(i).count("1") for i in range(256)], np.int64))
    for shift in range(0, 64, 8):
        byte = (v >> jnp.uint64(shift)) & jnp.uint64(0xFF)
        cnt = cnt + lut[byte.astype(jnp.int32)]
    return LoweredVal(cnt, a.valid, None)


def _lower_float_class(kind: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        v = a.vals.astype(jnp.float64)
        if kind == "nan":
            out = jnp.isnan(v)
        elif kind == "finite":
            out = jnp.isfinite(v)
        else:
            out = jnp.isinf(v)
        return LoweredVal(out, a.valid, None)

    return fn


# --- array / map lowering (ops/array_ops.py kernels; reference:
# operator/scalar/Array*/Map* + spi/block/ArrayBlock traversals) ---

INVALID_FUNCTION_ARGUMENT = "INVALID_FUNCTION_ARGUMENT"


def _lower_array_ctor(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    k = len(expr.args)
    n = ctx.num_rows
    lengths = jnp.full((n,), k, jnp.int32)
    if k == 0:
        elem = LoweredVal(jnp.zeros((0,), jnp.int64), None, None)
        return LoweredVal(lengths, None, children=[elem])
    items = [lower(a, ctx) for a in expr.args]
    if any(it.children is not None for it in items):
        raise NotImplementedError("nested array constructors not supported")
    dicts = [it.dictionary for it in items]
    d = None
    if any(dc is not None for dc in dicts):
        # NULL literals lower with no dictionary — they contribute no vocab
        # and their (all-invalid) codes recode to NULL_CODE below
        present = [dc for dc in dicts if dc is not None]
        d = present[0]
        for dc in present[1:]:
            if dc.values != d.values:
                d = d.merge(dc)
        items = [
            it
            if it.dictionary is not None and it.dictionary.values == d.values
            else LoweredVal(
                jnp.where(
                    (it.vals >= 0)
                    & (it.valid if it.valid is not None else True),
                    jnp.asarray(
                        (it.dictionary.recode_table(d) if it.dictionary is not None
                         else np.array([NULL_CODE], np.int32))
                    )[jnp.clip(it.vals, 0)],
                    NULL_CODE,
                ),
                it.valid,
                d,
            )
            for it in items
        ]
    if d is None and getattr(expr.type, "element", None) is not None and expr.type.element.is_varchar:
        d = Dictionary([])  # all-NULL varchar array literal
    dt = items[0].vals.dtype
    for it in items[1:]:
        dt = jnp.promote_types(dt, it.vals.dtype)
    # row-major flattening: row i's elements are contiguous
    flat = jnp.stack([it.vals.astype(dt) for it in items], axis=1).reshape(-1)
    if all(it.valid is None for it in items):
        fvalid = None
    else:
        fvalid = jnp.stack(
            [
                it.valid if it.valid is not None else jnp.ones((n,), bool)
                for it in items
            ],
            axis=1,
        ).reshape(-1)
    return LoweredVal(lengths, None, children=[LoweredVal(flat, fvalid, d)])


def _nested_parts(a: LoweredVal):
    from trino_tpu.ops import array_ops as A

    # raw lengths: they describe the flat child layout even under NULL rows
    # (data/page.py offsets() invariant); null handling rides validity masks
    lens = a.vals.astype(jnp.int32)
    offsets = A.offsets_from_lengths(lens)
    return A, lens, offsets


def _lower_cardinality(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    return LoweredVal(a.vals.astype(jnp.int64), a.valid, None)


def _lower_subscript(strict: bool, is_map: bool):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        key = lower(expr.args[1], ctx)
        A, lens, offsets = _nested_parts(a)
        if is_map:
            kflat = a.children[0]
            vflat = a.children[1]
            flat_n = int(kflat.vals.shape[0])
            rowid = A.rowid_of_flat(offsets, flat_n)
            kv = key.vals
            if key.dictionary is not None and kflat.dictionary is not None:
                if key.dictionary.values != kflat.dictionary.values:
                    kv = jnp.where(
                        kv >= 0,
                        jnp.asarray(
                            key.dictionary.recode_table(kflat.dictionary)
                        )[jnp.clip(kv, 0)],
                        NULL_CODE,
                    )
            match = kflat.vals == (
                kv[rowid] if flat_n else jnp.zeros((0,), kv.dtype)
            )
            idx1 = A.first_match_index(offsets, match)
            found = idx1 > 0
            vals, _ = A.gather_at(offsets, lens, vflat.vals, idx1)
            evalid = None
            if vflat.valid is not None:
                ev, _ = A.gather_at(offsets, lens, vflat.valid, idx1)
                evalid = ev
            valid = and_valid(and_valid(a.valid, key.valid), and_valid(found, evalid))
            if strict:
                missing = ~found
                base_ok = a.valid if a.valid is not None else jnp.ones_like(missing)
                kok = key.valid if key.valid is not None else jnp.ones_like(missing)
                ctx.add_error(INVALID_FUNCTION_ARGUMENT, missing & base_ok & kok, None)
            return LoweredVal(vals, valid, vflat.dictionary)
        eflat = a.children[0]
        vals, in_bounds = A.gather_at(offsets, lens, eflat.vals, key.vals)
        evalid = None
        if eflat.valid is not None:
            evalid, _ = A.gather_at(offsets, lens, eflat.valid, key.vals)
        valid = and_valid(and_valid(a.valid, key.valid), and_valid(in_bounds, evalid))
        if strict:
            oob = ~in_bounds
            base_ok = a.valid if a.valid is not None else jnp.ones_like(oob)
            kok = key.valid if key.valid is not None else jnp.ones_like(oob)
            ctx.add_error(INVALID_FUNCTION_ARGUMENT, oob & base_ok & kok, None)
        return LoweredVal(vals, valid, eflat.dictionary)

    return fn


def _lower_contains(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    x = lower(expr.args[1], ctx)
    A, lens, offsets = _nested_parts(a)
    eflat = a.children[0]
    flat_n = int(eflat.vals.shape[0])
    rowid = A.rowid_of_flat(offsets, flat_n)
    xv = x.vals
    if x.dictionary is not None and eflat.dictionary is not None:
        if x.dictionary.values != eflat.dictionary.values:
            xv = jnp.where(
                xv >= 0,
                jnp.asarray(x.dictionary.recode_table(eflat.dictionary))[
                    jnp.clip(xv, 0)
                ],
                NULL_CODE,
            )
    target = xv[rowid] if flat_n else jnp.zeros((0,), xv.dtype)
    evalid = eflat.valid
    match = eflat.vals == target
    if evalid is not None:
        match = match & evalid
    found = A.count_in_ranges(offsets, match) > 0
    # SQL semantics (reference ArrayContains): found -> true; not found but
    # a NULL element present -> NULL; else false.
    if evalid is not None:
        has_null_elem = A.count_in_ranges(offsets, ~evalid) > 0
        valid = and_valid(and_valid(a.valid, x.valid), found | ~has_null_elem)
    else:
        valid = and_valid(a.valid, x.valid)
    return LoweredVal(found, valid, None)


def _lower_array_position(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    a = lower(expr.args[0], ctx)
    x = lower(expr.args[1], ctx)
    A, lens, offsets = _nested_parts(a)
    eflat = a.children[0]
    flat_n = int(eflat.vals.shape[0])
    rowid = A.rowid_of_flat(offsets, flat_n)
    xv = x.vals
    if x.dictionary is not None and eflat.dictionary is not None:
        if x.dictionary.values != eflat.dictionary.values:
            xv = jnp.where(
                xv >= 0,
                jnp.asarray(x.dictionary.recode_table(eflat.dictionary))[
                    jnp.clip(xv, 0)
                ],
                NULL_CODE,
            )
    target = xv[rowid] if flat_n else jnp.zeros((0,), xv.dtype)
    match = eflat.vals == target
    if eflat.valid is not None:
        match = match & eflat.valid
    idx1 = A.first_match_index(offsets, match)
    return LoweredVal(idx1.astype(jnp.int64), and_valid(a.valid, x.valid), None)


def _lower_array_reduce(kind: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        A, lens, offsets = _nested_parts(a)
        eflat = a.children[0]
        empty = lens == 0
        if kind == "sum":
            x = eflat.vals
            if eflat.valid is not None:
                x = jnp.where(eflat.valid, x, jnp.zeros((), x.dtype))
            out = A.segment_reduce_by_range(offsets, x)
            valid = and_valid(a.valid, ~empty)
            return LoweredVal(out, valid, None)
        # min/max via sorted-per-row trick is overkill; flat cummin over a
        # reversed/forward pass needs segment boundaries — use the
        # first_match-style suffix scan on transformed values instead:
        # sort-free per-row min = -segmented-max(-x); implement via
        # double-cumulative difference is wrong for min/max, so fall back
        # to a masked segment reduction using jax.ops (fine at array scale).
        import jax

        flat_n = int(eflat.vals.shape[0])
        rowid = A.rowid_of_flat(offsets, flat_n)
        x = eflat.vals
        mask_valid = eflat.valid
        if jnp.issubdtype(x.dtype, jnp.floating):
            sentinel = jnp.inf if kind == "min" else -jnp.inf
        else:
            info = jnp.iinfo(x.dtype)
            sentinel = info.max if kind == "min" else info.min
        if mask_valid is not None:
            x = jnp.where(mask_valid, x, sentinel)
        seg = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
        n = ctx.num_rows
        out = (
            seg(x, rowid, num_segments=n)
            if flat_n
            else jnp.full((n,), sentinel, x.dtype)
        )
        has_valid = (
            A.count_in_ranges(offsets, mask_valid) > 0
            if mask_valid is not None
            else ~empty
        )
        return LoweredVal(out, and_valid(a.valid, has_valid), eflat.dictionary)

    return fn


def _lower_map_part(which: int):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        a = lower(expr.args[0], ctx)
        lens = a.vals.astype(jnp.int32)
        return LoweredVal(lens, a.valid, children=[a.children[which]])

    return fn


def _lower_lambda_over_flat(ctx: LowerCtx, arr: LoweredVal, lam: "ir.Lambda",
                            elem_type) -> LoweredVal:
    """Evaluate a lambda body over an array's FLATTENED child: the element
    column becomes channel 0 of a fresh lowering context whose row space is
    the flat space — one vectorized pass over all elements of all rows
    (reference evaluates the lambda per element via generated bytecode)."""
    child = arr.children[0]
    flat_n = int(child.vals.shape[0])
    elem_col = Column(
        elem_type,
        child.vals if flat_n else jnp.zeros((1,), child.vals.dtype),
        None if child.valid is None else (
            ~child.valid if flat_n else jnp.zeros((1,), bool)),
        child.dictionary,
    )
    inner = LowerCtx([elem_col], max(flat_n, 1))
    out = lower(lam.body, inner)
    ctx.errors.extend(inner.errors)
    if flat_n == 0:
        out = LoweredVal(out.vals[:0], None if out.valid is None else out.valid[:0],
                         out.dictionary)
    return out


def _lower_transform(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    arr = lower(expr.args[0], ctx)
    lam = expr.args[1]
    elem_t = expr.args[0].type.element
    out = _lower_lambda_over_flat(ctx, arr, lam, elem_t)
    return LoweredVal(
        arr.vals.astype(jnp.int32), arr.valid,
        children=[LoweredVal(out.vals, out.valid, out.dictionary)],
    )


def _lower_match(kind: str):
    def fn(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
        arr = lower(expr.args[0], ctx)
        lam = expr.args[1]
        elem_t = expr.args[0].type.element
        out = _lower_lambda_over_flat(ctx, arr, lam, elem_t)
        A, lens, offsets = _nested_parts(arr)
        flat_true = out.vals
        flat_known = out.valid
        if flat_known is not None:
            flat_true = flat_true & flat_known
        n_true = A.count_in_ranges(offsets, flat_true)
        n_unknown = (
            A.count_in_ranges(offsets, ~flat_known)
            if flat_known is not None
            else None
        )
        # SQL three-valued semantics (reference Array*MatchFunction):
        # any_match: true if any true; null if none true but some unknown
        # all_match: false if any false; null if rest unknown; else true
        # none_match: !any_match
        if kind in ("any", "none"):
            hit = n_true > 0
            if n_unknown is not None:
                valid = and_valid(arr.valid, hit | (n_unknown == 0))
            else:
                valid = arr.valid
            vals = hit if kind == "any" else ~hit
            return LoweredVal(vals, valid, None)
        flat_false = ~out.vals
        if flat_known is not None:
            flat_false = flat_false & flat_known
        n_false = A.count_in_ranges(offsets, flat_false)
        any_false = n_false > 0
        if n_unknown is not None:
            valid = and_valid(arr.valid, any_false | (n_unknown == 0))
        else:
            valid = arr.valid
        return LoweredVal(~any_false, valid, None)

    return fn


def _lower_map_ctor(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    ka = lower(expr.args[0], ctx)
    va = lower(expr.args[1], ctx)
    mismatch = ka.vals.astype(jnp.int32) != va.vals.astype(jnp.int32)
    ctx.add_error(
        INVALID_FUNCTION_ARGUMENT, mismatch, and_valid(ka.valid, va.valid)
    )
    return LoweredVal(
        ka.vals.astype(jnp.int32),
        and_valid(ka.valid, va.valid),
        children=[ka.children[0], va.children[0]],
    )


def _lower_random(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """random() -> double in [0, 1): host RNG, one draw per row. Under the
    compiled tier the draws are baked at trace time (a re-run of a cached
    executable would repeat them) — which is why the cache layer marks
    random() uncachable rather than relying on per-run freshness."""
    vals = jnp.asarray(np.random.random(ctx.num_rows))
    return LoweredVal(vals, None, None)


def _lower_now(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    """now() -> timestamp(3): one instant per evaluation (the reference
    pins now() to the query start; per-evaluation is the coarser but
    cache-equivalent behavior — both vary across queries)."""
    import time as _time

    v = int(_time.time() * 1000)
    return LoweredVal(_const_array(ctx, np.int64, v), None, None, abs(v))


def _lower_current_date(ctx: LowerCtx, expr: ir.Call) -> LoweredVal:
    import time as _time

    days = int(_time.time() // 86_400)
    return LoweredVal(_const_array(ctx, np.int32, days), None, None, days)


FUNCTIONS: Dict[str, Callable[..., LoweredVal]] = {
    "random": _lower_random,
    "now": _lower_now,
    "current_date": _lower_current_date,
    "eq": _comparison(lambda a, b: a == b),
    "ne": _comparison(lambda a, b: a != b, negate_eq=True),
    "lt": _comparison(lambda a, b: a < b),
    "le": _comparison(lambda a, b: a <= b),
    "gt": _comparison(lambda a, b: a > b),
    "ge": _comparison(lambda a, b: a >= b),
    "add": _arith("add"),
    "sub": _arith("sub"),
    "mul": _arith("mul"),
    "div": _arith("div"),
    "mod": _arith("mod"),
    "negate": _lower_negate,
    "abs": _lower_abs,
    "and": _lower_and,
    "or": _lower_or,
    "not": _lower_not,
    "is_null": _lower_is_null,
    "between": _lower_between,
    "in_list": _lower_in_list,
    "like": _lower_like,
    "coalesce": _lower_coalesce,
    "nullif": _lower_nullif,
    "substring": _lower_substring,
    "lower": _lower_str_fn(str.lower),
    "upper": _lower_str_fn(str.upper),
    "trim": _lower_str_fn(str.strip),
    "ltrim": _lower_str_fn(str.lstrip),
    "rtrim": _lower_str_fn(str.rstrip),
    "length": _lower_length,
    "row_ctor": _lower_row_ctor,
    "row_field": _lower_row_field,
    "to_hex": _lower_binary_fn("to_hex"),
    "from_hex": _lower_binary_fn("from_hex"),
    "to_utf8": _lower_binary_fn("to_utf8"),
    "from_utf8": _lower_binary_fn("from_utf8"),
    "md5": _lower_binary_fn("md5"),
    "sha256": _lower_binary_fn("sha256"),
    "concat": _lower_concat,
    "sqrt": _lower_math1(jnp.sqrt),
    "cbrt": _lower_math1(jnp.cbrt),
    "ln": _lower_math1(jnp.log),
    "log_b": _lower_log_b,
    "log2": _lower_math1(jnp.log2),
    "log10": _lower_log10,
    "exp": _lower_math1(jnp.exp),
    "power": _lower_power,
    "sign": _lower_sign,
    "round": _lower_round,
    "ceil": _lower_ceil_floor(True),
    "ceiling": _lower_ceil_floor(True),
    "floor": _lower_ceil_floor(False),
    "greatest": _lower_extremum(True),
    "least": _lower_extremum(False),
    "extract_year": _lower_extract("year"),
    "extract_month": _lower_extract("month"),
    "extract_hour": _lower_extract("hour"),
    "extract_minute": _lower_extract("minute"),
    "extract_second": _lower_extract("second"),
    "extract_day": _lower_extract("day"),
    "extract_quarter": _lower_extract("quarter"),
    "extract_dow": _lower_extract("dow"),
    "extract_doy": _lower_extract("doy"),
    "extract_week": _lower_extract("week"),
    "date_add_months": _lower_date_add_months,
    "date_diff_days": _lower_date_diff_days,
    "ts_diff_units": _lower_ts_diff_units,
    "months_between": _lower_months_between,
    "seconds_to_ts3": _lower_seconds_to_ts3,
    "date_trunc": _lower_date_trunc,
    "replace": _lower_replace,
    "reverse": _lower_reverse,
    "strpos": _lower_strpos,
    "starts_with": _lower_starts_with,
    "sin": _lower_math1(jnp.sin),
    "cos": _lower_math1(jnp.cos),
    "tan": _lower_math1(jnp.tan),
    "asin": _lower_math1(jnp.arcsin),
    "acos": _lower_math1(jnp.arccos),
    "atan": _lower_math1(jnp.arctan),
    "sinh": _lower_math1(jnp.sinh),
    "cosh": _lower_math1(jnp.cosh),
    "tanh": _lower_math1(jnp.tanh),
    "degrees": _lower_math1(jnp.degrees),
    "radians": _lower_math1(jnp.radians),
    "atan2": _lower_atan2,
    "truncate": _lower_truncate,
    "regexp_like": _lower_regexp("like"),
    "regexp_extract": _lower_regexp("extract"),
    "regexp_replace": _lower_regexp("replace"),
    "regexp_count": _lower_regexp("count"),
    "lpad": _lower_pad(True),
    "rpad": _lower_pad(False),
    "split_part": _lower_split_part,
    "translate": _lower_translate,
    "repeat_str": _lower_repeat_str,
    "chr": _lower_chr,
    "codepoint": _lower_codepoint,
    "hamming_distance": _lower_str_distance("hamming"),
    "levenshtein_distance": _lower_str_distance("levenshtein"),
    "json_extract_scalar": _lower_json_extract_scalar,
    "json_array_length": _lower_json_array_length,
    "date_format": _lower_date_format,
    "date_parse": _lower_date_parse,
    "day_name": _lower_day_name,
    "month_name": _lower_month_name,
    "last_day_of_month": _lower_last_day_of_month,
    "bitwise_and": _lower_bitwise("and"),
    "bitwise_or": _lower_bitwise("or"),
    "bitwise_xor": _lower_bitwise("xor"),
    "bitwise_not": _lower_bitwise("not"),
    "bitwise_left_shift": _lower_bitwise("lshift"),
    "bitwise_right_shift": _lower_bitwise("rshift"),
    "bit_count": _lower_bit_count,
    "is_nan": _lower_float_class("nan"),
    "is_finite": _lower_float_class("finite"),
    "is_infinite": _lower_float_class("inf"),
    "array_ctor": _lower_array_ctor,
    "cardinality": _lower_cardinality,
    "subscript": _lower_subscript(strict=True, is_map=False),
    "element_at": _lower_subscript(strict=False, is_map=False),
    "map_subscript": _lower_subscript(strict=True, is_map=True),
    "map_element_at": _lower_subscript(strict=False, is_map=True),
    "contains": _lower_contains,
    "array_position": _lower_array_position,
    "array_min": _lower_array_reduce("min"),
    "array_max": _lower_array_reduce("max"),
    "array_sum": _lower_array_reduce("sum"),
    "map_keys": _lower_map_part(0),
    "map_values": _lower_map_part(1),
    "map_ctor": _lower_map_ctor,
    "transform": _lower_transform,
    "any_match": _lower_match("any"),
    "all_match": _lower_match("all"),
    "none_match": _lower_match("none"),
}
