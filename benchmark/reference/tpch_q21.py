"""Plain numpy reference for TPC-H Q21 (suppliers who kept orders waiting),
with qgen's NATION substitution.

Independent of the engine in the way ``benchmark/reference/tpch.py`` is:
the only repo code it touches is the TPC-H generator (the data, which takes
the place of weights here), the schema-name -> scale-factor table and that
module's helpers. No subquery is a join here. Lineitem goes by in order
chunks (every line of an order lies in its order's chunk), and for each
order two numbers are counted: its distinct suppliers and its distinct LATE
suppliers (``l_receiptdate > l_commitdate``). A line of ``l1`` counts when
it is late, its order's status is ``F``, its supplier is of the bound
nation, its order has a second supplier (the EXISTS) and no supplier of the
order but its own is late (the NOT EXISTS: its own is late, so the order
has exactly one late supplier). Then ``count(*)`` by ``s_name``, ordered by
``numwait desc, s_name``, the first 100: the rows the engine returns, as
the statement protocol carries them.

``dtype`` is the CONTROL's switch (``benchmark/control.py``): with
``numpy.float32`` the counts are carried in float32. That control cannot
come out as not correct here: Q21 has no decimal, and every count is under
2^24 (a supplier waits on at most a few hundred orders at SF 10), which
float32 holds exactly. What makes the comparison tight is elsewhere: an
answer that drops either subquery's residual (``l_suppkey <>
l1.l_suppkey``) differs from this one (``drop`` names the subquery whose
residual goes, for the test that shows it).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from benchmark.reference.tpch import _cols, _order_chunks, wire_rows
from trino_tpu.connector.tpch import generator as gen
from trino_tpu.connector.tpch.connector import schema_scale_factor

LIMIT = 100


def _per_order(ok: np.ndarray, sk: np.ndarray, late: np.ndarray,
               o_keys: np.ndarray):
    """(distinct suppliers, distinct late suppliers) of each order of
    ``o_keys`` (ascending), from its lines ``(ok, sk, late)``."""
    by = np.lexsort((sk, ok))
    ok_s, sk_s, late_s = ok[by], sk[by], late[by]
    first = np.ones(len(ok_s), dtype=bool)
    first[1:] = (ok_s[1:] != ok_s[:-1]) | (sk_s[1:] != sk_s[:-1])
    starts = np.flatnonzero(first)
    pair_late = np.logical_or.reduceat(late_s, starts) if len(starts) else \
        np.zeros(0, bool)
    at = np.searchsorted(o_keys, ok_s[starts])
    n = len(o_keys)
    return (np.bincount(at, minlength=n),
            np.bincount(at[pair_late], minlength=n))


def q21(schema: str, bindings: List[dict], dtype=None,
        drop: Optional[str] = None) -> List[List[list]]:
    """``select s_name, count(*) ... where n_name = '<nation>' ... group by
    s_name order by numwait desc, s_name limit 100`` for every binding's
    ``nation``. ``drop`` (``"exists"`` or ``"not exists"``) leaves
    ``l_suppkey <> l1.l_suppkey`` out of that subquery: without it EXISTS
    holds for every line (``l2`` may be ``l1``) and NOT EXISTS for no late
    one (``l3`` may be ``l1``)."""
    sf = schema_scale_factor(schema)
    n_supp = gen.table_row_count("supplier", sf)
    supp = gen.generate("supplier", sf, 0, n_supp,
                        ["s_suppkey", "s_name", "s_nationkey"])
    s_keys = np.asarray(supp["s_suppkey"].values)
    nation_of = np.full(n_supp + 1, -1, dtype=np.int64)
    nation_of[s_keys] = np.asarray(supp["s_nationkey"].values)
    name_of = {int(k): supp["s_name"].dictionary.values[int(c)]
               for k, c in zip(s_keys, np.asarray(supp["s_name"].values))}
    nat = gen.generate("nation", sf, 0, gen.table_row_count("nation", sf),
                       ["n_nationkey", "n_name"])
    nation_key = {nat["n_name"].dictionary.values[int(c)]: int(k)
                  for k, c in zip(np.asarray(nat["n_nationkey"].values),
                                  np.asarray(nat["n_name"].values))}
    wanted = [nation_key[b["nation"]] for b in bindings]
    counts = [np.zeros(n_supp + 1, dtype=dtype or np.int64) for _ in bindings]
    for lo, hi in _order_chunks(sf):
        o = gen.generate("orders", sf, lo, hi, ["o_orderkey", "o_orderstatus"])
        o_keys = np.asarray(o["o_orderkey"].values)
        status = np.asarray(o["o_orderstatus"].dictionary.values)[
            np.asarray(o["o_orderstatus"].values)]
        li = _cols("lineitem", sf, lo, hi, [
            "l_orderkey", "l_suppkey", "l_commitdate", "l_receiptdate"])
        ok, sk = li["l_orderkey"], li["l_suppkey"]
        late = li["l_receiptdate"] > li["l_commitdate"]
        n_suppliers, n_late = _per_order(ok, sk, late, o_keys)
        at = np.searchsorted(o_keys, ok)
        waited = late & (status[at] == "F")
        if drop != "exists":
            waited &= n_suppliers[at] >= 2
        waited &= (n_late[at] == 1) if drop != "not exists" else False
        for nation, total in zip(wanted, counts):
            mine = sk[waited & (nation_of[sk] == nation)]
            total += np.bincount(mine, minlength=n_supp + 1).astype(
                total.dtype)
    out = []
    for total in counts:
        if dtype is not None:
            total = np.rint(total.astype(np.float64)).astype(np.int64)
        rows = sorted(((name_of[int(k)], int(total[k]))
                       for k in np.flatnonzero(total)),
                      key=lambda r: (-r[1], r[0]))
        out.append(wire_rows(rows[:LIMIT]))
    return out
