"""Plain numpy reference for the TPC-H queries ``chip_smoke.py`` serves.

Independent of the engine: the only repo code it touches is the TPC-H
generator (the data source) and the schema-name -> scale-factor table.
Decimals are the generator's scaled int64s and every sum is carried in
exact integer arithmetic (numpy int64 inside one chunk, whose bound is
asserted, Python ints across chunks), so rows compare EQUAL to the
engine's, not close. ``tests/tpch_oracle.py`` (row-at-a-time ``Decimal``)
cross-checks this module on ``tiny``; this one exists because that one
cannot walk 6 M rows.

Rows come back in the Python vocabulary the oracle and ``Page.to_pylist``
use (``Decimal``, ``datetime.date``, ``str``, ``int``); :func:`wire_rows`
turns them into what the statement protocol's JSON carries.

Lineitem is generated by ORDER-index range, so all lines of an order sit
in one chunk: per-order aggregates (q3, q18) finish inside a chunk and
only the top-k candidates cross chunks. That bounds host memory at sf10.
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


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _date(days: int) -> datetime.date:
    return _EPOCH + datetime.timedelta(days=int(days))


def _dec(scaled: int, scale: int) -> Decimal:
    return Decimal(int(scaled)).scaleb(-scale)


def _order_chunks(sf: float) -> Iterator[Tuple[int, int]]:
    n = gen.table_row_count("orders", sf)
    for lo in range(0, n, CHUNK_ORDERS):
        yield lo, min(n, lo + CHUNK_ORDERS)


def _cols(table: str, sf: float, lo: int, hi: int,
          names: List[str]) -> Dict[str, np.ndarray]:
    data = gen.generate(table, sf, lo, hi, names)
    return {n: np.asarray(data[n].values) for n in names}


def _exact_sum(values: np.ndarray, bound_per_row: int) -> int:
    """int64 sum of one chunk, refused if the chunk could overflow."""
    if len(values) * bound_per_row > _INT64_MAX:
        raise OverflowError("chunk too large for an exact int64 sum")
    return int(values.sum(dtype=np.int64))


def q1(schema: str = "sf1") -> List[tuple]:
    sf = schema_scale_factor(schema)
    cutoff = _days("1998-12-01") - 90
    names = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
             "l_discount", "l_tax", "l_shipdate"]
    # group -> [sum_qty, sum_base, sum_disc_price, sum_charge, sum_discount, n]
    groups: Dict[Tuple[str, str], List[int]] = {}
    eprice_max = 50 * 209900  # max quantity x max retail price, scaled
    for lo, hi in _order_chunks(sf):
        data = gen.generate("lineitem", sf, lo, hi, names)
        rf_vocab = data["l_returnflag"].dictionary.values
        ls_vocab = data["l_linestatus"].dictionary.values
        c = {n: np.asarray(data[n].values) for n in names}
        keep = c["l_shipdate"] <= cutoff
        key = (c["l_returnflag"].astype(np.int64) * len(ls_vocab)
               + c["l_linestatus"])
        for k in np.unique(key[keep]):
            m = keep & (key == k)
            ep, disc, tax = (c["l_extendedprice"][m], c["l_discount"][m],
                             c["l_tax"][m])
            disc_price = ep * (100 - disc)
            part = [
                _exact_sum(c["l_quantity"][m], 5000),
                _exact_sum(ep, eprice_max),
                _exact_sum(disc_price, eprice_max * 100),
                _exact_sum(disc_price * (100 + tax), eprice_max * 100 * 108),
                _exact_sum(disc, 10),
                int(m.sum()),
            ]
            g = groups.setdefault(
                (rf_vocab[int(k) // len(ls_vocab)],
                 ls_vocab[int(k) % len(ls_vocab)]), [0] * 6)
            for i, v in enumerate(part):
                g[i] += v

    def avg(total: int, n: int) -> Decimal:
        # decimal avg keeps the input scale and rounds half up
        return _dec((2 * total + n) // (2 * n), 2)

    return [
        (rf, ls, _dec(g[0], 2), _dec(g[1], 2), _dec(g[2], 4), _dec(g[3], 6),
         avg(g[0], g[5]), avg(g[1], g[5]), avg(g[4], g[5]), g[5])
        for (rf, ls), g in sorted(groups.items())
    ]


def q6(schema: str = "sf1") -> List[tuple]:
    sf = schema_scale_factor(schema)
    lo_d, hi_d = _days("1994-01-01"), _days("1995-01-01")
    total = 0
    for lo, hi in _order_chunks(sf):
        c = _cols("lineitem", sf, lo, hi, ["l_extendedprice", "l_discount",
                                           "l_quantity", "l_shipdate"])
        m = ((c["l_shipdate"] >= lo_d) & (c["l_shipdate"] < hi_d)
             & (c["l_discount"] >= 5) & (c["l_discount"] <= 7)
             & (c["l_quantity"] < 2400))
        total += _exact_sum(c["l_extendedprice"][m] * c["l_discount"][m],
                            50 * 209900 * 10)
    return [(_dec(total, 4),)]


def _per_order_sum(order_pos: np.ndarray, values: np.ndarray,
                   n_orders: int) -> np.ndarray:
    out = np.zeros(n_orders, dtype=np.int64)
    np.add.at(out, order_pos, values)
    return out


def q3(schema: str = "sf1", limit: int = 10) -> List[tuple]:
    sf = schema_scale_factor(schema)
    cut = _days("1995-03-15")
    n_cust = gen.table_row_count("customer", sf)
    cust = gen.generate("customer", sf, 0, n_cust,
                        ["c_custkey", "c_mktsegment"])
    seg = cust["c_mktsegment"]
    building = np.zeros(n_cust + 1, dtype=bool)
    building[np.asarray(cust["c_custkey"].values)[
        np.asarray(seg.values) == seg.dictionary.code_of("BUILDING")]] = True
    best: List[tuple] = []  # (-revenue, date, orderkey, shippriority)
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, ["o_orderkey", "o_custkey",
                                         "o_orderdate", "o_shippriority"])
        li = _cols("lineitem", sf, lo, hi, ["l_orderkey", "l_extendedprice",
                                            "l_discount", "l_shipdate"])
        o_ok = building[o["o_custkey"]] & (o["o_orderdate"] < cut)
        pos = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
        l_ok = (li["l_shipdate"] > cut) & o_ok[pos]
        pos = pos[l_ok]
        revenue = _per_order_sum(
            pos, li["l_extendedprice"][l_ok] * (100 - li["l_discount"][l_ok]),
            len(o["o_orderkey"]))
        hit = np.zeros(len(o["o_orderkey"]), dtype=bool)
        hit[pos] = True
        idx = np.flatnonzero(hit)
        order = np.lexsort((o["o_orderkey"][idx], o["o_orderdate"][idx],
                            -revenue[idx]))[:limit]
        best.extend(
            (-int(revenue[i]), int(o["o_orderdate"][i]),
             int(o["o_orderkey"][i]), int(o["o_shippriority"][i]))
            for i in idx[order])
    best.sort()
    return [(okey, _dec(-neg_rev, 4), _date(day), prio)
            for neg_rev, day, okey, prio in best[:limit]]


def q18(schema: str = "sf1", limit: int = 100) -> List[tuple]:
    sf = schema_scale_factor(schema)
    best: List[tuple] = []  # (-totalprice, date, orderkey, custkey, sum_qty)
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, ["o_orderkey", "o_custkey",
                                         "o_orderdate", "o_totalprice"])
        li = _cols("lineitem", sf, lo, hi, ["l_orderkey", "l_quantity"])
        qty = _per_order_sum(
            np.searchsorted(o["o_orderkey"], li["l_orderkey"]),
            li["l_quantity"], len(o["o_orderkey"]))
        best.extend(
            (-int(o["o_totalprice"][i]), int(o["o_orderdate"][i]),
             int(o["o_orderkey"][i]), int(o["o_custkey"][i]), int(qty[i]))
            for i in np.flatnonzero(qty > 30000))
    best.sort()
    best = best[:limit]
    n_cust = gen.table_row_count("customer", sf)
    cust = gen.generate("customer", sf, 0, n_cust, ["c_custkey", "c_name"])
    ckeys = np.asarray(cust["c_custkey"].values)
    names, codes = cust["c_name"].dictionary.values, cust["c_name"].values
    out = []
    for neg_price, day, okey, ckey, qty in best:
        at = int(np.searchsorted(ckeys, ckey))
        assert ckeys[at] == ckey
        out.append((names[int(codes[at])], ckey, okey, _date(day),
                    _dec(-neg_price, 2), _dec(qty, 2)))
    return out


POINT_COLUMNS = ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"]


def point_order_key(schema: str = "sf1") -> int:
    """The order the point query looks up: the middle one."""
    sf = schema_scale_factor(schema)
    mid = gen.table_row_count("orders", sf) // 2
    return int(_cols("orders", sf, mid, mid + 1, ["o_orderkey"])["o_orderkey"][0])


def point_order(schema: str, orderkey: int) -> List[tuple]:
    """``select o_orderkey, o_custkey, o_totalprice, o_orderdate from
    orders where o_orderkey = <orderkey>``."""
    sf = schema_scale_factor(schema)
    out = []
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, POINT_COLUMNS)
        for i in np.flatnonzero(o["o_orderkey"] == orderkey):
            out.append((int(o["o_orderkey"][i]), int(o["o_custkey"][i]),
                        _dec(o["o_totalprice"][i], 2),
                        _date(o["o_orderdate"][i])))
    return out


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
