"""Plain numpy reference for TPC-H Q9 (product type profit), with qgen's
COLOR substitution.

Independent of the engine in the way ``benchmark/reference/tpch.py`` is:
the only repo code it touches is the TPC-H generator (the data, which takes
the place of weights here), the schema-name -> scale-factor table and that
module's helpers. No join is a join here: every dimension is an array
indexed by its dense key (``p_partkey``, ``s_suppkey``, ``n_nationkey``),
``partsupp`` is one sorted composite key searched by bisection, and
lineitem goes by in order chunks so that host memory stays bounded at sf10.
Decimals are the generator's scaled int64s and every sum is exact integer
arithmetic, so rows compare EQUAL to the engine's, not close.

``dtype`` is the CONTROL's switch (``benchmark/control.py``): with
``numpy.float32`` the products and sums are carried in float32, the
precision below the exact decimals the configuration states.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmark.reference.tpch import (
    _EPRICE_MAX, _INT64_MAX, _cols, _dec, _mul, _order_chunks, wire_rows)
from trino_tpu.connector.tpch import generator as gen
from trino_tpu.connector.tpch.connector import schema_scale_factor

_QUANTITY_MAX = 50 * 100        # 50.00 at scale 2
_SUPPLYCOST_MAX = 1000 * 100    # 1000.00 at scale 2
# |l_extendedprice * (100 - l_discount) - ps_supplycost * l_quantity|,
# scale 4, of one lineitem row
_AMOUNT_MAX = _EPRICE_MAX * 100 + _SUPPLYCOST_MAX * _QUANTITY_MAX


def _years(days: np.ndarray) -> np.ndarray:
    return (days.astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970)


def q9(schema: str, bindings: List[dict], dtype=None) -> List[List[list]]:
    """``select nation, o_year, sum(amount) ... where p_name like
    '%<color>%' ... group by nation, o_year order by nation, o_year desc``
    for every binding's ``color``."""
    sf = schema_scale_factor(schema)
    n_part = gen.table_row_count("part", sf)
    n_supp = gen.table_row_count("supplier", sf)
    part = gen.generate("part", sf, 0, n_part, ["p_partkey", "p_name"])
    pkeys = np.asarray(part["p_partkey"].values)
    names = part["p_name"].dictionary.values
    codes = np.asarray(part["p_name"].values)
    wanted = []     # per binding: wanted[p_partkey] is True for its parts
    for b in bindings:
        hit = np.array([b["color"] in v for v in names], dtype=bool)
        flag = np.zeros(n_part + 1, dtype=bool)
        flag[pkeys[hit[codes]]] = True
        wanted.append(flag)
    supp = _cols("supplier", sf, 0, n_supp, ["s_suppkey", "s_nationkey"])
    nation_of = np.zeros(n_supp + 1, dtype=np.int64)
    nation_of[supp["s_suppkey"]] = supp["s_nationkey"]
    nat = gen.generate("nation", sf, 0, gen.table_row_count("nation", sf),
                       ["n_nationkey", "n_name"])
    nation_names = {int(k): nat["n_name"].dictionary.values[int(c)]
                    for k, c in zip(np.asarray(nat["n_nationkey"].values),
                                    np.asarray(nat["n_name"].values))}
    ps = _cols("partsupp", sf, 0, gen.table_row_count("partsupp", sf),
               ["ps_partkey", "ps_suppkey", "ps_supplycost"])
    ps_key = ps["ps_partkey"] * (n_supp + 1) + ps["ps_suppkey"]
    by_key = np.argsort(ps_key, kind="stable")
    ps_key, ps_cost = ps_key[by_key], ps["ps_supplycost"][by_key]
    # per binding: (nation key, year) -> sum of amount at scale 4
    sums: List[Dict[Tuple[int, int], int]] = [{} for _ in bindings]
    for lo, hi in _order_chunks(sf):
        o = _cols("orders", sf, lo, hi, ["o_orderkey", "o_orderdate"])
        li = _cols("lineitem", sf, lo, hi, [
            "l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
            "l_extendedprice", "l_discount"])
        if dtype is None and len(li["l_orderkey"]) * _AMOUNT_MAX > _INT64_MAX:
            raise OverflowError("chunk too large for an exact int64 sum")
        year = _years(o["o_orderdate"])[
            np.searchsorted(o["o_orderkey"], li["l_orderkey"])]
        for flag, total in zip(wanted, sums):
            m = flag[li["l_partkey"]]
            pk, sk = li["l_partkey"][m], li["l_suppkey"][m]
            at = np.searchsorted(ps_key, pk * (n_supp + 1) + sk)
            if not (ps_key[at] == pk * (n_supp + 1) + sk).all():
                raise AssertionError("a lineitem names a (part, supplier) "
                                     "pair partsupp does not hold")
            amount = (_mul(li["l_extendedprice"][m], 100 - li["l_discount"][m],
                           dtype)
                      - _mul(ps_cost[at], li["l_quantity"][m], dtype))
            group = nation_of[sk] * 10000 + year[m]
            keys, inverse = np.unique(group, return_inverse=True)
            acc = np.zeros(len(keys), dtype=dtype or np.int64)
            np.add.at(acc, inverse, amount)
            if dtype is not None:
                acc = np.rint(acc.astype(np.float64)).astype(np.int64)
            for k, v in zip(keys.tolist(), acc.tolist()):
                g = (k // 10000, k % 10000)
                total[g] = total.get(g, 0) + int(v)
    return [wire_rows([
        (nation_names[n], y, _dec(v, 4))
        for (n, y), v in sorted(
            total.items(), key=lambda kv: (nation_names[kv[0][0]], -kv[0][1]))])
        for total in sums]
