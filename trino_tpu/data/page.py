"""Page/Column: the device-resident columnar batch.

Reference: ``core/trino-spi/.../spi/Page.java:31`` (Page = Block[] +
positionCount) and the Block hierarchy ``spi/block/`` (LongArrayBlock,
IntArrayBlock, VariableWidthBlock, DictionaryBlock, null masks per block).

TPU-first differences (SURVEY.md §7.1):
- A Column is a struct-of-arrays: ``values: jax.Array`` (+ optional
  ``nulls: jax.Array`` of bool, True = NULL) instead of a class hierarchy.
- Varchar values are int32 dictionary codes; the Dictionary lives host-side.
- A Page may carry a *selection mask* (``sel``) instead of being compacted:
  filters AND into ``sel`` so shapes stay static for XLA (no data-dependent
  compaction inside jit).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.data.dictionary import NULL_CODE, Dictionary
from trino_tpu.obs.devprofiler import host_read, host_read_all


@dataclasses.dataclass
class Column:
    """``values.dtype`` is the column's PHYSICAL dtype and may be narrower
    than ``type.np_dtype`` (the logical width) for integer-kind, date, and
    decimal columns whose value range provably fits — the TPU analog of the
    reference's type-specialized codegen (``FlatHashStrategyCompiler``):
    int64 is emulated 2x int32 on TPU, so keys/dates that fit int32 sort,
    join, and group ~2x faster and cost half the HBM traffic. Arithmetic
    re-widens explicitly (ops/expr_lower casts operands to the result
    type's compute dtype), so narrowing never changes results.

    ``vrange`` is an optional static (min, max) bound on the stored values
    (storage repr — scaled ints for decimals, epoch days for dates), from
    connector stats. It licenses narrowing and lets the expression lowering
    skip int128 paths when interval arithmetic proves an int64 fit."""

    type: T.Type
    values: jnp.ndarray  # device array; int32 codes when type.is_varchar
    nulls: Optional[jnp.ndarray] = None  # bool[n], True where NULL; None = no nulls
    dictionary: Optional[Dictionary] = None  # required when type.is_varchar
    vrange: Optional[tuple] = None  # static (min, max) of values, Python ints
    # values are non-decreasing in row order (connector sort order, kept by
    # order-preserving ops: filter masks, stable compaction, probe-major
    # join expansion). Licenses the sort-free group/join fast paths —
    # lax.sort is the engine's dominant cost at scale, and TPC-H fact
    # tables arrive sorted by their join key (reference: LocalProperties
    # driving e.g. streaming aggregations).
    ascending: bool = False
    # Nested (array/map/row) columns: ``values`` holds per-row int32 element
    # counts (rows for RowType ignore it) and ``children`` the flattened
    # child columns — array: [elements], map: [keys, values], row: fields.
    # Reference: spi/block/ArrayBlock.java / MapBlock.java (offsets + child
    # blocks); lengths instead of offsets keep every row-parallel kernel
    # (sel/null masks) shape-compatible with scalar columns.
    children: Optional[List["Column"]] = None
    # Long-decimal (p > 18) high limb (reference: spi/type/Int128.java —
    # two-longs-per-position flat storage). Present when the column holds
    # (or, for unproven arithmetic results, MAY hold) values beyond int64:
    # ``values`` is then the low 64-bit pattern and ``hi`` the signed high
    # limb. Absent (None) = every value provably fits int64 and the column
    # rides the narrow single-array layout — the adaptive analog of the
    # reference's short/long decimal split, chosen from data/stats instead
    # of per type. Consumers without limb kernels degrade via
    # Executor._narrowed_or_flag (low word + deferred overflow check).
    hi: Optional[jnp.ndarray] = None

    def __post_init__(self):
        if self.type.is_varchar and self.dictionary is None:
            raise ValueError("varchar column requires a dictionary")
        if self.type.is_nested and self.children is None:
            raise ValueError(f"nested column {self.type} requires children")

    def __len__(self) -> int:
        return int(self.values.shape[0])

    def offsets(self) -> np.ndarray:
        """Host-side int64 offsets[n+1] derived from the stored lengths.

        Invariant: lengths always describe the flat child layout — a NULL
        row may still own flat elements (produced by device kernels whose
        null masks arrive after the fact); they are simply never read."""
        lens = np.asarray(self.values, dtype=np.int64)
        return np.concatenate([np.zeros(1, np.int64), np.cumsum(lens)])

    @classmethod
    def from_python(cls, typ: T.Type, data: Sequence) -> "Column":
        """Build a column from Python values (None = NULL). Host -> device."""
        n = len(data)
        has_null = any(v is None for v in data)
        nulls = (
            jnp.asarray(np.array([v is None for v in data], dtype=np.bool_))
            if has_null
            else None
        )
        if typ.is_varchar:
            if typ.is_varbinary:
                # bytes ride the dictionary as hex strings (hex order ==
                # unsigned-byte order, so comparisons/sorts agree)
                data = [v.hex() if isinstance(v, (bytes, bytearray)) else v
                        for v in data]
            d = Dictionary.build(data)
            codes = d.encode(list(data))
            return cls(typ, jnp.asarray(codes), nulls, d)
        if typ.is_nested:
            return cls._nested_from_python(typ, data, nulls)
        np_dtype = typ.np_dtype
        assert np_dtype is not None, f"unsupported type {typ}"
        fill = 0
        reprs = [fill if v is None else _to_repr(typ, v) for v in data]
        if typ.is_decimal and any(
            isinstance(r, int) and not -(2**63) <= r < 2**63 for r in reprs
        ):
            # long decimal beyond int64: two-limb storage (Int128.java)
            lo = np.array([r & (2**64 - 1) for r in reprs], dtype=np.uint64)
            hi = np.array([r >> 64 for r in reprs], dtype=np.int64)
            return cls(
                typ, jnp.asarray(lo.view(np.int64)), nulls, None, hi=jnp.asarray(hi)
            )
        arr = np.array(reprs, dtype=np_dtype)
        if n == 0:
            arr = np.empty(0, dtype=np_dtype)
        return cls(typ, jnp.asarray(arr), nulls, None)

    @classmethod
    def _nested_from_python(cls, typ: T.Type, data: Sequence, nulls) -> "Column":
        n = len(data)
        if isinstance(typ, T.RowType):
            kids = []
            for i, ft in enumerate(typ.field_types):
                kids.append(cls.from_python(ft, [None if r is None else r[i] for r in data]))
            return cls(typ, jnp.zeros((n,), jnp.int8), nulls, None, children=kids)
        if isinstance(typ, T.MapType):
            rows = [[] if m is None else sorted(m.items(), key=lambda kv: str(kv[0])) for m in data]
            lens = np.array([len(r) for r in rows], dtype=np.int32)
            keys = [k for r in rows for k, _ in r]
            vals = [v for r in rows for _, v in r]
            kids = [cls.from_python(typ.key, keys), cls.from_python(typ.value, vals)]
            return cls(typ, jnp.asarray(lens), nulls, None, children=kids)
        assert isinstance(typ, T.ArrayType)
        rows = [[] if a is None else list(a) for a in data]
        lens = np.array([len(r) for r in rows], dtype=np.int32)
        flat = [v for r in rows for v in r]
        return cls(
            typ, jnp.asarray(lens), nulls, None,
            children=[cls.from_python(typ.element, flat)],
        )

    def to_python(self) -> List:
        """Device -> host, decoding reprs back to Python values."""
        if self.type.is_nested:
            return self._nested_to_python()
        site = "result-rows"
        if self.hi is not None:
            his = host_read(self.hi, site).tolist()
            los = host_read(self.values, site).view(np.uint64).tolist()
            nulls = (host_read(self.nulls, site).tolist()
                     if self.nulls is not None else None)
            out = [
                _from_repr(self.type, (h << 64) | l) for h, l in zip(his, los)
            ]
            if nulls is not None:
                out = [None if isnull else v for v, isnull in zip(out, nulls)]
            return out
        vals = host_read(self.values, site)
        nulls = host_read(self.nulls, site) if self.nulls is not None else None
        if self.type.is_varchar:
            assert self.dictionary is not None
            out = self.dictionary.decode(vals)
            if self.type.is_varbinary:
                out = [bytes.fromhex(v) if v is not None else v for v in out]
            if nulls is not None:
                out = [None if isnull else v for v, isnull in zip(out, nulls)]
            return out
        out = [_from_repr(self.type, v) for v in vals.tolist()]
        if nulls is not None:
            out = [None if isnull else v for v, isnull in zip(out, nulls)]
        return out

    def _nested_to_python(self) -> List:
        nulls = (host_read(self.nulls, "result-rows")
                 if self.nulls is not None else None)
        if isinstance(self.type, T.RowType):
            fields = [c.to_python() for c in self.children]
            out = [tuple(f[i] for f in fields) for i in range(len(self))]
        else:
            off = self.offsets()
            kids = [c.to_python() for c in self.children]
            if isinstance(self.type, T.MapType):
                keys, vals = kids
                out = [
                    dict(zip(keys[off[i] : off[i + 1]], vals[off[i] : off[i + 1]]))
                    for i in range(len(self))
                ]
            else:
                (flat,) = kids
                out = [flat[off[i] : off[i + 1]] for i in range(len(self))]
        if nulls is not None:
            out = [None if isnull else v for v, isnull in zip(out, nulls)]
        return out


def _column_leaves(c: Column, out: list) -> None:
    """The arrays of ``c``'s tree (values, nulls, hi, children) appended to
    ``out``, absent ones as None, in the order ``_column_from_leaves``
    takes them back."""
    out.extend((c.values, c.nulls, c.hi))
    for k in c.children or ():
        _column_leaves(k, out)


def _column_from_leaves(c: Column, leaves) -> Column:
    """``c`` with its arrays replaced by the next ones of ``leaves``."""
    values, nulls, hi = next(leaves), next(leaves), next(leaves)
    kids = (None if c.children is None
            else [_column_from_leaves(k, leaves) for k in c.children])
    return dataclasses.replace(c, values=values, nulls=nulls, hi=hi,
                               children=kids)


def fits_int32(vrange) -> bool:
    """True when a (min, max) range can ride int32 physically. The bounds
    are strict: the dtype max stays free for join sentinels and the min
    stays negation-safe for descending sort keys."""
    if vrange is None:
        return False
    lo, hi = vrange
    return -(2**31) < lo and hi < 2**31 - 1


def merge_vrange(a, b):
    """Union of two optional (min, max) ranges; None dominates (unknown)."""
    if a is None or b is None:
        return None
    return (min(a[0], b[0]), max(a[1], b[1]))


def _to_repr(typ: T.Type, v):
    """Python value -> device representation (int days, scaled int, ...)."""
    if isinstance(typ, T.TimestampType):
        import datetime

        unit = 10 ** typ.precision
        if isinstance(v, str):
            v = datetime.datetime.fromisoformat(v)
        if isinstance(v, datetime.datetime):
            if v.tzinfo is not None:
                v = v.astimezone(datetime.timezone.utc).replace(tzinfo=None)
            epoch = datetime.datetime(1970, 1, 1)
            delta = v - epoch
            micros = (delta.days * 86_400_000_000
                      + delta.seconds * 1_000_000 + delta.microseconds)
            return micros * unit // 1_000_000
        if isinstance(v, datetime.date):
            return (v - datetime.date(1970, 1, 1)).days * 86_400 * unit
        return int(v)
    if typ == T.DATE:
        if isinstance(v, str):
            import datetime

            d = datetime.date.fromisoformat(v)
            return (d - datetime.date(1970, 1, 1)).days
        import datetime

        if isinstance(v, datetime.date):
            return (v - datetime.date(1970, 1, 1)).days
        return int(v)
    if typ.is_decimal:
        assert isinstance(typ, T.DecimalType)
        import decimal
        from decimal import Decimal

        with decimal.localcontext() as ctx:
            ctx.prec = 60  # p=38 plus headroom: scaleb must not round
            return int(Decimal(str(v)).scaleb(typ.scale).to_integral_value())
    if typ == T.BOOLEAN:
        return bool(v)
    if typ.is_floating:
        return float(v)
    return int(v)


def _from_repr(typ: T.Type, r):
    if isinstance(typ, T.TimestampType):
        import datetime

        unit = 10 ** typ.precision
        micros = int(r) * 1_000_000 // unit
        base = datetime.datetime(
            1970, 1, 1,
            tzinfo=datetime.timezone.utc if typ.with_tz else None)
        return base + datetime.timedelta(microseconds=micros)
    if typ == T.DATE:
        import datetime

        return datetime.date(1970, 1, 1) + datetime.timedelta(days=int(r))
    if typ.is_decimal:
        assert isinstance(typ, T.DecimalType)
        import decimal
        from decimal import Decimal

        with decimal.localcontext() as ctx:
            ctx.prec = 60
            return Decimal(r).scaleb(-typ.scale)
    if typ == T.BOOLEAN:
        return bool(r)
    if typ.is_floating:
        return float(r)
    return int(r)


def _concat_cols(cols: Sequence[Column]) -> Column:
    """Row-wise concatenation of one channel across pages — ONE
    ``jnp.concatenate`` per array however many pages there are (a pairwise
    chain is a new shape, so in the eager tier a new XLA program, for every
    page: 34 exchange chunks of one q18 input made 371 of them on the
    v5e, PR 25)."""
    first = cols[0]
    if first.type.is_nested:
        # lengths concatenate; children are flat, so their rows concatenate
        # too (offsets re-derive from the combined lengths, which by the
        # offsets() invariant describe the flat layout even for null rows).
        kids = [_concat_cols(ks) for ks in zip(*(c.children for c in cols))]
        return Column(first.type, jnp.concatenate([c.values for c in cols]),
                      _concat_nulls(cols), None, children=kids)
    dt = functools.reduce(jnp.promote_types, (c.values.dtype for c in cols))
    vals = [c.values.astype(dt) for c in cols]  # mixed widths: promote
    d = first.dictionary
    dicts = [c.dictionary for c in cols]
    if all(x is not None for x in dicts) and any(
            x is not d and x.values != d.values for x in dicts[1:]):
        offsets = _consecutive_vocabularies(dicts)
        if offsets is not None:
            # each page's vocabulary lies wholly after the one before it
            # (the chunks of one key-ordered page): the merged vocabulary
            # is their concatenation and a code moves by its page's offset
            d = Dictionary([s for x in dicts for s in x.values])
            # (an all-NULL page has an empty vocabulary and whatever codes
            # under its null mask: they become NULL_CODE, as below)
            vals = [jnp.full_like(v, NULL_CODE) if not x.values
                    else jnp.where(v >= 0, v + off, NULL_CODE) if off else v
                    for v, off, x in zip(vals, offsets, dicts)]
        else:
            d = Dictionary(sorted(set().union(*(x.values for x in dicts))))

            def recode(v, src_dict):
                t = np.asarray(src_dict.recode_table(d))
                # an all-NULL side has an empty vocab: pad so the gather
                # below stays in range (its codes are all NULL_CODE anyway)
                t = jnp.asarray(
                    t if len(t) else np.array([NULL_CODE], np.int32))
                return jnp.where(v >= 0, t[jnp.clip(v, 0)], NULL_CODE)

            vals = [recode(v, x) for v, x in zip(vals, dicts)]
    hi = None
    if any(c.hi is not None for c in cols):
        # a missing hi limb is the sign extension of the low word
        hi = jnp.concatenate([
            c.hi if c.hi is not None else (v.astype(jnp.int64) >> 63)
            for c, v in zip(cols, vals)])
    vr = None if hi is not None else functools.reduce(
        merge_vrange, (c.vrange for c in cols))
    return Column(first.type, jnp.concatenate(vals), _concat_nulls(cols), d,
                  vr, hi=hi)


def _consecutive_vocabularies(dicts) -> Optional[List[int]]:
    """Each dictionary's code offset in the concatenation of all of them,
    where every vocabulary's strings sort after the previous one's (empty
    ones anywhere); None where they interleave or repeat."""
    offsets, last, total = [], None, 0
    for d in dicts:
        offsets.append(total)
        if not d.values:
            continue
        if last is not None and not last < d.values[0]:
            return None
        last = d.values[-1]
        total += len(d.values)
    return offsets


def _concat_nulls(cols: Sequence[Column]):
    if all(c.nulls is None for c in cols):
        return None
    return jnp.concatenate([
        c.nulls if c.nulls is not None else jnp.zeros((len(c),), bool)
        for c in cols])


def host_take(c: Column, idx: np.ndarray, device: bool = True,
              site: str = "compact") -> Column:
    """Row gather on the HOST (numpy). The one gather path that supports
    nested columns: child segments are re-flattened by explicit offsets —
    a data-dependent-shape operation jit'd device code cannot express.

    ``device=False`` keeps the gathered arrays as numpy (no device_put):
    the host-consumption paths (``to_pylist`` — result rows headed
    straight to Python) would otherwise pay one device round trip per
    column just to read them back. ``site`` labels the device->host reads
    of the column's arrays in the kernel ledger."""
    up = jnp.asarray if device else np.asarray
    if c.type.is_nested:
        nulls = host_read(c.nulls, site)[idx] if c.nulls is not None else None
        if isinstance(c.type, T.RowType):
            kids = [host_take(k, idx, device=device, site=site)
                    for k in c.children]
            vals = host_read(c.values, site)[idx]
        else:
            off = c.offsets()
            lens = host_read(c.values, site).astype(np.int64)
            vals = lens[idx].astype(np.int32)
            if len(idx):
                child_idx = np.concatenate(
                    [np.arange(off[i], off[i + 1], dtype=np.int64) for i in idx]
                )
            else:
                child_idx = np.zeros(0, np.int64)
            kids = [host_take(k, child_idx, device=device, site=site)
                    for k in c.children]
        return Column(
            c.type, up(vals),
            up(nulls) if nulls is not None else None,
            None, None, children=kids,
        )
    # the sorted flag survives only order-preserving gathers (compact /
    # slice pass monotone indices; arbitrary permutations must drop it)
    monotone = bool(c.ascending) and (len(idx) < 2 or bool(np.all(np.diff(idx) >= 0)))
    return Column(
        c.type,
        up(host_read(c.values, site)[idx]),
        up(host_read(c.nulls, site)[idx]) if c.nulls is not None else None,
        c.dictionary,
        c.vrange,
        ascending=monotone,
        hi=up(host_read(c.hi, site)[idx]) if c.hi is not None else None,
    )


# a column of at least this many slots whose live rows are under an eighth
# of them is gathered on the device before it is read (``to_pylist``)
DEVICE_TAKE_MIN_ROWS = 1 << 16


def _live_rows_to_host(columns: Sequence[Column], idx: np.ndarray,
                       site: str) -> List[Column]:
    """The rows ``idx`` (ascending) of ``columns`` as host columns. A large
    flat device column with few live rows (a point lookup keeps one row of
    four 1.5 M-row columns: 24 MB to read, 7.75 ms of a 35 ms statement) is
    gathered ON the device at ``idx`` padded to a power of two (one gather
    program a bucket, not one a result length), and all those gathers are
    read in one batch; anything else goes through ``host_take`` as before."""
    n = len(idx)
    on_device = [
        i for i, c in enumerate(columns)
        if not c.type.is_nested and not isinstance(c.values, np.ndarray)
        and len(c) >= DEVICE_TAKE_MIN_ROWS and 8 * n <= len(c)]
    out = [None if i in on_device
           else host_take(c, idx, device=False, site=site)
           for i, c in enumerate(columns)]
    if on_device:
        padded = np.zeros(1 << max(n - 1, 0).bit_length(), idx.dtype)
        padded[:n] = idx
        take = jnp.asarray(padded)

        def gather(x):
            return None if x is None else jnp.take(x, take, mode="clip")

        taken = Page([
            dataclasses.replace(c, values=gather(c.values),
                                nulls=gather(c.nulls), hi=gather(c.hi))
            for c in (columns[i] for i in on_device)])
        for i, c in zip(on_device,
                        taken.to_host(site).slice_rows(0, n).columns):
            out[i] = c
    return out


@dataclasses.dataclass
class Page:
    """A batch of rows: one Column per channel + optional selection mask.

    ``sel`` (bool[n], True = row is live) realizes filtering without
    compaction — XLA-friendly static shapes (SURVEY.md §7.3 item 1). ``None``
    means all rows live.

    ``replicated``: under SPMD execution (parallel/spmd.py), True means every
    device holds the same rows (post-broadcast/gather); False means this is a
    per-device shard. Purely host-side bookkeeping (not traced).
    """

    columns: List[Column]
    sel: Optional[jnp.ndarray] = None
    replicated: bool = False
    # sel (when present) is a LIVE PREFIX: rows [0, k) live, [k, n) dead —
    # the shape compact_to produces. Lets sorted-input fast paths treat
    # ascending columns as dead-tail-sorted without inspecting the mask.
    live_prefix: bool = False

    @property
    def num_rows(self) -> int:
        return 0 if not self.columns else len(self.columns[0])

    @property
    def channel_count(self) -> int:
        return len(self.columns)

    @classmethod
    def from_pydict(cls, schema: Dict[str, T.Type], data: Dict[str, Sequence]) -> "Page":
        return cls([Column.from_python(t, data[name]) for name, t in schema.items()])

    @staticmethod
    def concat_all(pages: Sequence["Page"]) -> "Page":
        """Row-wise concatenation (static shapes: the sum of the pages').
        Dictionaries are merged host-side with device recode gathers when
        they differ."""
        if len(pages) == 1:
            return pages[0]
        cols = [_concat_cols(cs) for cs in zip(*(p.columns for p in pages))]
        sel = jnp.concatenate([
            p.sel if p.sel is not None else jnp.ones((p.num_rows,), bool)
            for p in pages])
        return Page(cols, sel, all(p.replicated for p in pages))

    @staticmethod
    def concat_pages(a: "Page", b: "Page") -> "Page":
        return Page.concat_all([a, b])

    @staticmethod
    def all_dead(types: Sequence[T.Type]) -> "Page":
        """One all-dead row of the given types — the canonical empty page
        (zero-length arrays break downstream gathers: joins index
        counts[p], build.rows, etc., so 'empty' is 1 row with sel=False)."""
        def col_of(t: T.Type, nrows: int) -> Column:
            kids = (
                [col_of(ct, nrows if t.is_row else 0) for ct in T.type_children(t)]
                if t.is_nested
                else None
            )
            return Column(
                t,
                jnp.zeros((nrows,), t.np_dtype or np.dtype(np.int64)),
                None,
                Dictionary([""]) if t.is_varchar else None,
                children=kids,
            )

        return Page([col_of(t, 1) for t in types], jnp.zeros((1,), bool))

    def to_host(self, site: str) -> "Page":
        """This page with every array of its tree (``sel``, each column's
        values, nulls, hi limb and nested children) as numpy, fetched in
        ONE batched device -> host read counted once under ``site``
        (``devprofiler.host_read_all``). A page that is already on the
        host is returned as it is. What works on the copy (``compact``,
        ``slice_rows``, the serde, ``host_take(..., device=False)``) moves
        nothing in either direction."""
        leaves = [self.sel]
        for c in self.columns:
            _column_leaves(c, leaves)
        if all(x is None or isinstance(x, np.ndarray) for x in leaves):
            return self
        fetched = iter(host_read_all(leaves, site))
        sel = next(fetched)
        return dataclasses.replace(
            self, sel=sel,
            columns=[_column_from_leaves(c, fetched) for c in self.columns])

    def compact(self, device: bool = True) -> "Page":
        """Drop dead rows (host-side gather). Used at wire boundaries: the
        serde (data/serde.py) carries no selection mask, so pages compact
        once before serialization — the DCN tier's analog of the reference
        compacting pages into the PartitionedOutputBuffer.
        ``device=False`` leaves the gathered columns on the host (the
        output path, which compacts its one host copy: ``to_host``)."""
        if self.sel is None:
            return self
        live = host_read(self.sel, "compact")
        idx = np.nonzero(live)[0]
        return Page([host_take(c, idx, device=device) for c in self.columns],
                    None, self.replicated)

    def slice_rows(self, lo: int, hi: int) -> "Page":
        """Row-range view [lo, hi) of a compacted page (sel must be None) —
        the producer-side page chunker of the streaming output path. A
        slice lives where its page does: of a host copy it is numpy views."""
        assert self.sel is None, "slice_rows requires a compacted page"
        cols = [
            host_take(c, np.arange(lo, min(hi, len(c)), dtype=np.int64),
                      device=not isinstance(c.values, np.ndarray))
            if c.type.is_nested
            else Column(
                c.type,
                c.values[lo:hi],
                c.nulls[lo:hi] if c.nulls is not None else None,
                c.dictionary,
                c.vrange,
                ascending=c.ascending,
                hi=c.hi[lo:hi] if c.hi is not None else None,
            )
            for c in self.columns
        ]
        return Page(cols, None, self.replicated)

    def row_byte_estimate(self) -> int:
        """Rough serialized bytes per row (dtype widths; dictionaries are
        amortized) — sizes output chunks. From the arrays' metadata: a
        device array states its dtype without a read (until PR 35 each
        column was fetched whole to learn it: 24 MB a point lookup)."""
        total = 0
        for c in self.columns:
            total += c.values.dtype.itemsize
            if c.nulls is not None:
                total += 1
            if c.children is not None and self.num_rows:
                # amortize flattened children over the parent row count
                for k in c.children:
                    total += max(
                        1, (len(k) * k.values.dtype.itemsize)
                        // self.num_rows)
        return max(total, 1)

    def live_count(self, site: str = "live-count") -> int:
        """Live rows. With a selection mask on the device this is a
        blocking device->host read of the whole mask, counted in the
        kernel ledger under ``site``."""
        if self.sel is None:
            return self.num_rows
        # host count: the mask is a bool vector headed for one scalar —
        # a jnp.sum here pays a device dispatch per call, and this is
        # called several times per query on the serving path
        return int(np.count_nonzero(host_read(self.sel, site)))

    def to_pylist(self) -> List[tuple]:
        """Materialize live rows as Python tuples (host side, test/CLI path).
        Compacts FIRST so per-row Python decode touches only live rows — a
        TopN page carries its full input capacity with a tiny live prefix,
        and decoding millions of dead slots would dwarf the query itself.
        The compacted intermediates stay on the HOST: the very next step
        is Python decode, so the device upload ``compact()`` pays at wire
        boundaries would be a per-column round trip bought for nothing
        (measured ~0.7ms per point query on the serving path)."""
        if self.sel is not None:
            idx = np.nonzero(host_read(self.sel, "result-rows"))[0]
            page = Page(_live_rows_to_host(self.columns, idx, "result-rows"),
                        None, self.replicated)
        else:
            page = self
        cols = [c.to_python() for c in page.columns]
        return [tuple(col[i] for col in cols) for i in range(page.num_rows)]
