"""Plain numpy reference for the benchmark's TPC-H templates, with TPC-H's
substitution parameters.

A copy of the repo's ``tpch_reference.py`` (PR 25) whose literals are
arguments. Independent of the engine: the only repo code it touches is the
TPC-H generator (the data source, which takes the place of weights here)
and the schema-name -> scale-factor table. Decimals are the generator's
scaled int64s and every sum is carried in exact integer arithmetic (numpy
int64 inside one chunk, whose bound is asserted, Python ints across
chunks), so rows compare EQUAL to the engine's, not close.

Every function takes the schema and a LIST of bindings and returns one
list of rows per binding, in the statement protocol's JSON vocabulary
(:func:`wire_rows`): the columns of a chunk are generated once for all
bindings, which is most of the cost at sf10.

Lineitem is generated by ORDER-index range, so all lines of an order sit
in one chunk: per-order aggregates (q3, q18) finish inside a chunk and only
the top-k candidates cross chunks. That bounds host memory at sf10.

``dtype`` is the CONTROL's switch (``benchmark/control.py``): with
``numpy.float32`` the sums and products are carried in float32, the
precision below the exact decimals the configurations state. The
benchmark's own runs never pass it.
"""
from __future__ import annotations

import datetime
from decimal import Decimal
from typing import Dict, Iterator, List, Tuple

import numpy as np

from trino_tpu.connector.tpch import generator as gen
from trino_tpu.connector.tpch.connector import schema_scale_factor

CHUNK_ORDERS = 1_500_000
_EPOCH = datetime.date(1970, 1, 1)
_INT64_MAX = np.iinfo(np.int64).max
_EPRICE_MAX = 50 * 209900  # max quantity x max retail price, scaled by 100


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _date(days: int) -> datetime.date:
    return _EPOCH + datetime.timedelta(days=int(days))


def _dec(scaled, scale: int) -> Decimal:
    return Decimal(int(scaled)).scaleb(-scale)


def _order_chunks(sf: float) -> Iterator[Tuple[int, int]]:
    n = gen.table_row_count("orders", sf)
    for lo in range(0, n, CHUNK_ORDERS):
        yield lo, min(n, lo + CHUNK_ORDERS)


def _cols(table: str, sf: float, lo: int, hi: int,
          names: List[str]) -> Dict[str, np.ndarray]:
    data = gen.generate(table, sf, lo, hi, names)
    return {n: np.asarray(data[n].values) for n in names}


def _sum(values: np.ndarray, bound_per_row: int, dtype) -> int:
    """Exact int64 sum of one chunk, refused if the chunk could overflow;
    the control sums in its float type instead."""
    if dtype is not None:
        return int(round(float(values.astype(dtype).sum(dtype=dtype))))
    if len(values) * bound_per_row > _INT64_MAX:
        raise OverflowError("chunk too large for an exact int64 sum")
    return int(values.sum(dtype=np.int64))


def _mul(a: np.ndarray, b: np.ndarray, dtype) -> np.ndarray:
    if dtype is not None:
        return a.astype(dtype) * b.astype(dtype)
    return a * b


def q1(schema: str, bindings: List[dict], dtype=None) -> List[List[list]]:
    sf = schema_scale_factor(schema)
    cutoffs = [_days("1998-12-01") - int(b["delta"]) for b in bindings]
    names = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
             "l_discount", "l_tax", "l_shipdate"]
    # per binding: group -> [sum_qty, sum_base, sum_disc_price, sum_charge,
    # sum_discount, n]
    groups: List[Dict[Tuple[str, str], List[int]]] = [{} for _ in bindings]
    for lo, hi in _order_chunks(sf):
        data = gen.generate("lineitem", sf, lo, hi, names)
        rf_vocab = data["l_returnflag"].dictionary.values
        ls_vocab = data["l_linestatus"].dictionary.values
        c = {n: np.asarray(data[n].values) for n in names}
        key = (c["l_returnflag"].astype(np.int64) * len(ls_vocab)
               + c["l_linestatus"])
        for g_of, cutoff in zip(groups, cutoffs):
            keep = c["l_shipdate"] <= cutoff
            for k in np.unique(key[keep]):
                m = keep & (key == k)
                ep, disc, tax = (c["l_extendedprice"][m], c["l_discount"][m],
                                 c["l_tax"][m])
                disc_price = _mul(ep, 100 - disc, dtype)
                part = [
                    _sum(c["l_quantity"][m], 5000, dtype),
                    _sum(ep, _EPRICE_MAX, dtype),
                    _sum(disc_price, _EPRICE_MAX * 100, dtype),
                    _sum(_mul(disc_price, 100 + tax, dtype),
                         _EPRICE_MAX * 100 * 108, dtype),
                    _sum(disc, 10, dtype),
                    int(m.sum()),
                ]
                g = g_of.setdefault(
                    (rf_vocab[int(k) // len(ls_vocab)],
                     ls_vocab[int(k) % len(ls_vocab)]), [0] * 6)
                for i, v in enumerate(part):
                    g[i] += v

    def avg(total: int, n: int) -> Decimal:
        # decimal avg keeps the input scale and rounds half up
        return _dec((2 * total + n) // (2 * n), 2)

    return [wire_rows([
        (rf, ls, _dec(g[0], 2), _dec(g[1], 2), _dec(g[2], 4), _dec(g[3], 6),
         avg(g[0], g[5]), avg(g[1], g[5]), avg(g[4], g[5]), g[5])
        for (rf, ls), g in sorted(g_of.items())]) for g_of in groups]


def q6(schema: str, bindings: List[dict], dtype=None) -> List[List[list]]:
    sf = schema_scale_factor(schema)
    totals = [0] * len(bindings)
    for lo, hi in _order_chunks(sf):
        c = _cols("lineitem", sf, lo, hi, ["l_extendedprice", "l_discount",
                                           "l_quantity", "l_shipdate"])
        for i, b in enumerate(bindings):
            year = int(b["year"])
            lo_d, hi_d = _days(f"{year}-01-01"), _days(f"{year + 1}-01-01")
            disc = round(float(b["discount"]) * 100)
            m = ((c["l_shipdate"] >= lo_d) & (c["l_shipdate"] < hi_d)
                 & (c["l_discount"] >= disc - 1) & (c["l_discount"] <= disc + 1)
                 & (c["l_quantity"] < int(b["quantity"]) * 100))
            totals[i] += _sum(
                _mul(c["l_extendedprice"][m], c["l_discount"][m], dtype),
                _EPRICE_MAX * 10, dtype)
    return [wire_rows([(_dec(t, 4),)]) for t in totals]


def _per_order_sum(order_pos: np.ndarray, values: np.ndarray,
                   n_orders: int, dtype) -> np.ndarray:
    out = np.zeros(n_orders, dtype=dtype or np.int64)
    np.add.at(out, order_pos, values)
    return out


def q3(schema: str, bindings: List[dict], dtype=None,
       limit: int = 10) -> List[List[list]]:
    sf = schema_scale_factor(schema)
    n_cust = gen.table_row_count("customer", sf)
    cust = gen.generate("customer", sf, 0, n_cust,
                        ["c_custkey", "c_mktsegment"])
    seg = cust["c_mktsegment"]
    ckeys, codes = np.asarray(cust["c_custkey"].values), np.asarray(seg.values)
    in_segment = []
    for b in bindings:
        flag = np.zeros(n_cust + 1, dtype=bool)
        flag[ckeys[codes == seg.dictionary.code_of(b["segment"])]] = True
        in_segment.append(flag)
    cuts = [_days(b["date"]) for b in bindings]
    # per binding: (-revenue, date, orderkey, shippriority)
    best: List[List[tuple]] = [[] for _ in bindings]
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, ["o_orderkey", "o_custkey",
                                         "o_orderdate", "o_shippriority"])
        li = _cols("lineitem", sf, lo, hi, ["l_orderkey", "l_extendedprice",
                                            "l_discount", "l_shipdate"])
        at = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
        for i, cut in enumerate(cuts):
            o_ok = in_segment[i][o["o_custkey"]] & (o["o_orderdate"] < cut)
            l_ok = (li["l_shipdate"] > cut) & o_ok[at]
            pos = at[l_ok]
            revenue = _per_order_sum(
                pos, _mul(li["l_extendedprice"][l_ok],
                          100 - li["l_discount"][l_ok], dtype),
                len(o["o_orderkey"]), dtype)
            if dtype is not None:
                revenue = np.rint(revenue).astype(np.int64)
            hit = np.zeros(len(o["o_orderkey"]), dtype=bool)
            hit[pos] = True
            idx = np.flatnonzero(hit)
            order = np.lexsort((o["o_orderkey"][idx], o["o_orderdate"][idx],
                                -revenue[idx]))[:limit]
            best[i].extend(
                (-int(revenue[j]), int(o["o_orderdate"][j]),
                 int(o["o_orderkey"][j]), int(o["o_shippriority"][j]))
                for j in idx[order])
    return [wire_rows([(okey, _dec(-neg_rev, 4), _date(day), prio)
                       for neg_rev, day, okey, prio in sorted(b)[:limit]])
            for b in best]


def q18(schema: str, bindings: List[dict], dtype=None,
        limit: int = 100) -> List[List[list]]:
    sf = schema_scale_factor(schema)
    floors = [int(b["quantity"]) * 100 for b in bindings]
    # per binding: (-totalprice, date, orderkey, custkey, sum_qty)
    best: List[List[tuple]] = [[] for _ in bindings]
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, ["o_orderkey", "o_custkey",
                                         "o_orderdate", "o_totalprice"])
        li = _cols("lineitem", sf, lo, hi, ["l_orderkey", "l_quantity"])
        qty = _per_order_sum(
            np.searchsorted(o["o_orderkey"], li["l_orderkey"]),
            li["l_quantity"], len(o["o_orderkey"]), dtype)
        if dtype is not None:
            qty = np.rint(qty).astype(np.int64)
        price = o["o_totalprice"]
        if dtype is not None:  # the control carries the price in its type too
            price = np.rint(price.astype(dtype).astype(np.float64)
                            ).astype(np.int64)
        for i, floor in enumerate(floors):
            best[i].extend(
                (-int(price[j]), int(o["o_orderdate"][j]),
                 int(o["o_orderkey"][j]), int(o["o_custkey"][j]), int(qty[j]))
                for j in np.flatnonzero(qty > floor))
    n_cust = gen.table_row_count("customer", sf)
    cust = gen.generate("customer", sf, 0, n_cust, ["c_custkey", "c_name"])
    ckeys = np.asarray(cust["c_custkey"].values)
    names, codes = cust["c_name"].dictionary.values, cust["c_name"].values
    out = []
    for rows in best:
        result = []
        for neg_price, day, okey, ckey, qty in sorted(rows)[:limit]:
            at = int(np.searchsorted(ckeys, ckey))
            if ckeys[at] != ckey:
                raise AssertionError(f"order {okey} names customer {ckey}, "
                                     "which the generator did not make")
            result.append((names[int(codes[at])], ckey, okey, _date(day),
                           _dec(-neg_price, 2), _dec(qty, 2)))
        out.append(wire_rows(result))
    return out


POINT_COLUMNS = ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"]


def point_order(schema: str, bindings: List[dict],
                dtype=None) -> List[List[list]]:
    """``select o_orderkey, o_custkey, o_totalprice, o_orderdate from
    orders where o_orderkey = <key>`` for every binding's key."""
    sf = schema_scale_factor(schema)
    keys = np.asarray([int(b["key"]) for b in bindings], dtype=np.int64)
    found: Dict[int, list] = {}
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, POINT_COLUMNS)
        at = np.searchsorted(o["o_orderkey"], keys)
        at[at >= len(o["o_orderkey"])] = 0
        for k, j in zip(keys, at):
            if o["o_orderkey"][j] == k:
                price = o["o_totalprice"][j]
                if dtype is not None:
                    price = int(round(float(np.asarray(price).astype(dtype))))
                found[int(k)] = [(int(k), int(o["o_custkey"][j]),
                                  _dec(price, 2), _date(o["o_orderdate"][j]))]
    return [wire_rows(found.get(int(k), [])) for k in keys]


def wire_rows(rows: List[tuple]) -> List[list]:
    """Reference rows in the statement protocol's JSON vocabulary:
    decimals as strings at their scale, dates as ISO strings, rows as
    lists."""
    def conv(v):
        if isinstance(v, Decimal):
            return str(v)
        if isinstance(v, datetime.date):
            return v.isoformat()
        return v

    return [[conv(v) for v in row] for row in rows]
