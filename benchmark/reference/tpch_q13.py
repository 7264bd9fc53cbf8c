"""Plain numpy reference for TPC-H Q13 (customer distribution), with qgen's
WORD1 and WORD2 substitution.

Independent of the engine in the way ``benchmark/reference/tpch.py`` is:
the only repo code it touches is the TPC-H generator (the data, which takes
the place of weights here), the schema-name -> scale-factor table and that
module's helpers. No join is a join here. The binding's pattern
``%<word1>%<word2>%`` is matched against the o_comment vocabulary (the
generator draws the comment from a pool of phrases, so each chunk's column
is codes into a dictionary) and the match applied to the codes. Then
``counts = bincount(o_custkey[kept])`` over every customer key, which holds
the customers with no kept order at 0: ``c_count`` of each customer, and
``custdist`` of each ``c_count``, ordered by ``custdist desc, c_count
desc``: the rows the engine returns, as the statement protocol carries
them.

``dtype`` is the CONTROL's switch (``benchmark/control.py``): with
``numpy.float32`` the counts are carried in float32. That control cannot
come out as not correct here: Q13 has no decimal, and every count is under
2^24 (1.5 M customers at SF 10), which float32 holds exactly. What makes
the comparison tight is elsewhere (``variant`` names each, for the test that
shows it): an INNER join loses the ``c_count = 0`` row (about 500 K
customers at SF 10, a third of them); ``count(*)`` in place of
``count(o_orderkey)`` counts a customer with no order as 1, which moves those
customers to ``c_count = 1``; and the NOT LIKE applied above the join (in a
WHERE, not the ON clause) drops every customer without orders, whose
o_comment is NULL, and so answers as the inner join does.
"""
from __future__ import annotations

import re
from typing import List, Optional

import numpy as np

from benchmark.reference.tpch import _order_chunks, wire_rows
from trino_tpu.connector.tpch import generator as gen
from trino_tpu.connector.tpch.connector import schema_scale_factor

VARIANTS = ("inner", "count_star", "where")


def like(pattern: str) -> "re.Pattern":
    """SQL LIKE as a regular expression: ``%`` any run, ``_`` one
    character, everything else itself."""
    parts = (".*" if ch == "%" else "." if ch == "_" else re.escape(ch)
             for ch in pattern)
    return re.compile("".join(parts), re.DOTALL)


def q13(schema: str, bindings: List[dict], dtype=None,
        variant: Optional[str] = None) -> List[List[list]]:
    """``select c_count, count(*) as custdist from (select c_custkey,
    count(o_orderkey) as c_count from customer left outer join orders on
    c_custkey = o_custkey and o_comment not like '%<word1>%<word2>%' group
    by c_custkey) group by c_count order by custdist desc, c_count desc``
    for every binding. ``variant`` (one of :data:`VARIANTS`) answers as
    an engine that made that mistake would."""
    assert variant is None or variant in VARIANTS, variant
    sf = schema_scale_factor(schema)
    n_cust = gen.table_row_count("customer", sf)
    patterns = [like(f"%{b['word1']}%{b['word2']}%") for b in bindings]
    counts = [np.zeros(n_cust + 1, dtype=dtype or np.int64) for _ in bindings]
    for lo, hi in _order_chunks(sf):
        o = gen.generate("orders", sf, lo, hi, ["o_custkey", "o_comment"])
        cust = np.asarray(o["o_custkey"].values)
        codes = np.asarray(o["o_comment"].values)
        vocab = o["o_comment"].dictionary.values
        for pattern, total in zip(patterns, counts):
            excluded = np.fromiter((pattern.fullmatch(v) is not None
                                    for v in vocab), bool, len(vocab))
            total += np.bincount(cust[~excluded[codes]],
                                 minlength=n_cust + 1).astype(total.dtype)
    out = []
    for total in counts:
        if dtype is not None:
            total = np.rint(total.astype(np.float64)).astype(np.int64)
        per_customer = total[1:]
        if variant == "count_star":
            per_customer = np.maximum(per_customer, 1)
        elif variant in ("inner", "where"):
            per_customer = per_customer[per_customer > 0]
        dist = np.bincount(per_customer)
        rows = sorted(((int(c), int(d)) for c, d in enumerate(dist) if d),
                      key=lambda r: (-r[1], -r[0]))
        out.append(wire_rows(rows))
    return out
