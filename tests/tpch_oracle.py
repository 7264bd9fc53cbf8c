"""Independent reference implementation for TPC-H query results.

Role of the H2 oracle in the reference test strategy (SURVEY.md §4:
QueryAssertions.java:151-176 runs the same SQL against embedded H2 and
diffs). Here: plain-Python row-at-a-time evaluation with exact Decimal
arithmetic over the same generated data the engine scans — a fully
independent code path from the vectorized device kernels.
"""
from __future__ import annotations

import datetime
from collections import defaultdict
from decimal import Decimal

from trino_tpu.connector.tpch import TpchConnector
from trino_tpu.connector.tpch.generator import SCHEMAS


def load_table(schema: str, table: str, columns=None):
    """Table as list of dicts of Python values."""
    conn = TpchConnector()
    cols = columns or [n for n, _ in SCHEMAS[table]]
    split = conn.get_splits(schema, table, 1)
    from trino_tpu.data.page import Column

    out = []
    datas = [conn.scan(s, cols) for s in split]
    col_lists = {}
    for c in cols:
        vals = []
        for d in datas:
            cd = d[c]
            col = Column(cd.type, cd.values, None, cd.dictionary)
            vals.extend(col.to_python())
        col_lists[c] = vals
    n = len(next(iter(col_lists.values())))
    for i in range(n):
        out.append({c: col_lists[c][i] for c in cols})
    return out


def d(s: str) -> datetime.date:
    return datetime.date.fromisoformat(s)


def q1(schema="tiny"):
    rows = load_table(
        schema,
        "lineitem",
        [
            "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate",
        ],
    )
    cutoff = d("1998-12-01") - datetime.timedelta(days=90)
    groups = defaultdict(lambda: {
        "sum_qty": Decimal(0), "sum_base": Decimal(0), "sum_disc": Decimal(0),
        "sum_charge": Decimal(0), "sum_disc_only": Decimal(0), "count": 0,
    })
    for r in rows:
        if r["l_shipdate"] > cutoff:
            continue
        g = groups[(r["l_returnflag"], r["l_linestatus"])]
        g["sum_qty"] += r["l_quantity"]
        g["sum_base"] += r["l_extendedprice"]
        disc_price = r["l_extendedprice"] * (1 - r["l_discount"])
        g["sum_disc"] += disc_price
        g["sum_charge"] += disc_price * (1 + r["l_tax"])
        g["sum_disc_only"] += r["l_discount"]
        g["count"] += 1

    def avg_dec(total, cnt, scale):
        # decimal avg rounds half-up at the input scale
        q = (total / cnt).quantize(Decimal(1).scaleb(-scale), rounding="ROUND_HALF_UP")
        return q

    out = []
    for (rf, ls), g in sorted(groups.items()):
        out.append(
            (
                rf, ls, g["sum_qty"], g["sum_base"], g["sum_disc"], g["sum_charge"],
                avg_dec(g["sum_qty"], g["count"], 2),
                avg_dec(g["sum_base"], g["count"], 2),
                avg_dec(g["sum_disc_only"], g["count"], 2),
                g["count"],
            )
        )
    return out


def q3(schema="tiny", limit=10):
    cust = load_table(schema, "customer", ["c_custkey", "c_mktsegment"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"])
    building = {c["c_custkey"] for c in cust if c["c_mktsegment"] == "BUILDING"}
    cut = d("1995-03-15")
    omap = {
        o["o_orderkey"]: o
        for o in orders
        if o["o_custkey"] in building and o["o_orderdate"] < cut
    }
    groups = defaultdict(Decimal)
    meta = {}
    for r in li:
        if r["l_shipdate"] <= cut:
            continue
        o = omap.get(r["l_orderkey"])
        if o is None:
            continue
        groups[r["l_orderkey"]] += r["l_extendedprice"] * (1 - r["l_discount"])
        meta[r["l_orderkey"]] = (o["o_orderdate"], o["o_shippriority"])
    rows = [
        (k, rev, meta[k][0], meta[k][1]) for k, rev in groups.items()
    ]
    rows.sort(key=lambda t: (-t[1], t[2]))
    return rows[:limit]


def q6(schema="tiny"):
    li = load_table(schema, "lineitem", ["l_extendedprice", "l_discount", "l_quantity", "l_shipdate"])
    lo, hi = d("1994-01-01"), d("1995-01-01")
    total = Decimal(0)
    for r in li:
        if (
            lo <= r["l_shipdate"] < hi
            and Decimal("0.05") <= r["l_discount"] <= Decimal("0.07")
            and r["l_quantity"] < 24
        ):
            total += r["l_extendedprice"] * r["l_discount"]
    return [(total,)]


def q18(schema="tiny", limit=100, quantity=300):
    cust = load_table(schema, "customer", ["c_custkey", "c_name"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_quantity"])
    qty = defaultdict(Decimal)
    for r in li:
        qty[r["l_orderkey"]] += r["l_quantity"]
    big = {k for k, v in qty.items() if v > quantity}
    cmap = {c["c_custkey"]: c["c_name"] for c in cust}
    rows = []
    for o in orders:
        if o["o_orderkey"] not in big:
            continue
        rows.append(
            (
                cmap[o["o_custkey"]], o["o_custkey"], o["o_orderkey"],
                o["o_orderdate"], o["o_totalprice"], qty[o["o_orderkey"]],
            )
        )
    rows.sort(key=lambda t: (-t[4], t[3]))
    return rows[:limit]


def q5(schema="tiny"):
    region = load_table(schema, "region", ["r_regionkey", "r_name"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name", "n_regionkey"])
    cust = load_table(schema, "customer", ["c_custkey", "c_nationkey"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    supp = load_table(schema, "supplier", ["s_suppkey", "s_nationkey"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"])
    asia = {r["r_regionkey"] for r in region if r["r_name"] == "ASIA"}
    nmap = {n["n_nationkey"]: n["n_name"] for n in nation if n["n_regionkey"] in asia}
    cnat = {c["c_custkey"]: c["c_nationkey"] for c in cust if c["c_nationkey"] in nmap}
    lo, hi = d("1994-01-01"), d("1995-01-01")
    omap = {}
    for o in orders:
        if lo <= o["o_orderdate"] < hi and o["o_custkey"] in cnat:
            omap[o["o_orderkey"]] = cnat[o["o_custkey"]]
    snat = {s["s_suppkey"]: s["s_nationkey"] for s in supp}
    groups = defaultdict(Decimal)
    for r in li:
        cn = omap.get(r["l_orderkey"])
        if cn is None:
            continue
        sn = snat.get(r["l_suppkey"])
        if sn != cn:
            continue
        groups[nmap[cn]] += r["l_extendedprice"] * (1 - r["l_discount"])
    return sorted(groups.items(), key=lambda t: -t[1])


# ---------------------------------------------------------------------------
# Q2, Q4, Q7-Q17, Q19-Q22 (added with full-suite coverage)
# ---------------------------------------------------------------------------

import re as _re
from decimal import ROUND_HALF_UP


def _like(value: str, pattern: str) -> bool:
    rx = "".join(
        ".*" if c == "%" else "." if c == "_" else _re.escape(c) for c in pattern
    )
    return _re.fullmatch(rx, value, _re.S) is not None


def _divq(a: Decimal, b: Decimal, scale: int) -> Decimal:
    """Decimal division with the engine/Trino result scale, half-up."""
    return (a / b).quantize(Decimal(1).scaleb(-scale), rounding=ROUND_HALF_UP)


def _avgq(total: Decimal, cnt: int, scale: int) -> Decimal:
    return (total / cnt).quantize(Decimal(1).scaleb(-scale), rounding=ROUND_HALF_UP)


def q2(schema="tiny", limit=100):
    part = load_table(schema, "part")
    supp = load_table(schema, "supplier")
    ps = load_table(schema, "partsupp")
    nation = load_table(schema, "nation")
    region = load_table(schema, "region")
    europe = {r["r_regionkey"] for r in region if r["r_name"] == "EUROPE"}
    nmap = {n["n_nationkey"]: n["n_name"] for n in nation if n["n_regionkey"] in europe}
    smap = {s["s_suppkey"]: s for s in supp if s["s_nationkey"] in nmap}
    min_cost = {}
    for r in ps:
        if r["ps_suppkey"] in smap:
            k = r["ps_partkey"]
            if k not in min_cost or r["ps_supplycost"] < min_cost[k]:
                min_cost[k] = r["ps_supplycost"]
    rows = []
    for p in part:
        if p["p_size"] != 15 or not _like(p["p_type"], "%BRASS"):
            continue
        for r in ps:
            if r["ps_partkey"] != p["p_partkey"] or r["ps_suppkey"] not in smap:
                continue
            if r["ps_supplycost"] != min_cost.get(p["p_partkey"]):
                continue
            s = smap[r["ps_suppkey"]]
            rows.append(
                (s["s_acctbal"], s["s_name"], nmap[s["s_nationkey"]], p["p_partkey"],
                 p["p_mfgr"], s["s_address"], s["s_phone"], s["s_comment"])
            )
    rows.sort(key=lambda t: (-t[0], t[2], t[1], t[3]))
    return rows[:limit]


def q4(schema="tiny"):
    orders = load_table(schema, "orders", ["o_orderkey", "o_orderdate", "o_orderpriority"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_commitdate", "l_receiptdate"])
    late = {r["l_orderkey"] for r in li if r["l_commitdate"] < r["l_receiptdate"]}
    lo, hi = d("1993-07-01"), d("1993-10-01")
    groups = defaultdict(int)
    for o in orders:
        if lo <= o["o_orderdate"] < hi and o["o_orderkey"] in late:
            groups[o["o_orderpriority"]] += 1
    return sorted(groups.items())


def q7(schema="tiny"):
    supp = load_table(schema, "supplier", ["s_suppkey", "s_nationkey"])
    li = load_table(schema, "lineitem", ["l_suppkey", "l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey"])
    cust = load_table(schema, "customer", ["c_custkey", "c_nationkey"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name"])
    nmap = {n["n_nationkey"]: n["n_name"] for n in nation}
    snat = {s["s_suppkey"]: nmap[s["s_nationkey"]] for s in supp}
    cnat = {c["c_custkey"]: nmap[c["c_nationkey"]] for c in cust}
    ocust = {o["o_orderkey"]: o["o_custkey"] for o in orders}
    lo, hi = d("1995-01-01"), d("1996-12-31")
    groups = defaultdict(Decimal)
    for r in li:
        if not (lo <= r["l_shipdate"] <= hi):
            continue
        sn = snat[r["l_suppkey"]]
        cn = cnat[ocust[r["l_orderkey"]]]
        if {sn, cn} != {"FRANCE", "GERMANY"}:
            continue
        vol = r["l_extendedprice"] * (1 - r["l_discount"])
        groups[(sn, cn, r["l_shipdate"].year)] += vol
    return [(k[0], k[1], k[2], v) for k, v in sorted(groups.items())]


def q8(schema="tiny"):
    part = load_table(schema, "part", ["p_partkey", "p_type"])
    supp = load_table(schema, "supplier", ["s_suppkey", "s_nationkey"])
    li = load_table(schema, "lineitem", ["l_partkey", "l_suppkey", "l_orderkey", "l_extendedprice", "l_discount"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    cust = load_table(schema, "customer", ["c_custkey", "c_nationkey"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name", "n_regionkey"])
    region = load_table(schema, "region", ["r_regionkey", "r_name"])
    america = {r["r_regionkey"] for r in region if r["r_name"] == "AMERICA"}
    am_nat = {n["n_nationkey"] for n in nation if n["n_regionkey"] in america}
    nname = {n["n_nationkey"]: n["n_name"] for n in nation}
    steel = {p["p_partkey"] for p in part if p["p_type"] == "ECONOMY ANODIZED STEEL"}
    snat = {s["s_suppkey"]: nname[s["s_nationkey"]] for s in supp}
    omap = {o["o_orderkey"]: o for o in orders}
    cmap = {c["c_custkey"]: c["c_nationkey"] for c in cust}
    lo, hi = d("1995-01-01"), d("1996-12-31")
    num = defaultdict(Decimal)
    den = defaultdict(Decimal)
    for r in li:
        if r["l_partkey"] not in steel:
            continue
        o = omap[r["l_orderkey"]]
        if not (lo <= o["o_orderdate"] <= hi):
            continue
        if cmap[o["o_custkey"]] not in am_nat:
            continue
        vol = r["l_extendedprice"] * (1 - r["l_discount"])
        y = o["o_orderdate"].year
        den[y] += vol
        if snat[r["l_suppkey"]] == "BRAZIL":
            num[y] += vol
    return [(y, _divq(num[y], den[y], 4)) for y in sorted(den)]


def q9(schema="tiny", color="green"):
    part = load_table(schema, "part", ["p_partkey", "p_name"])
    supp = load_table(schema, "supplier", ["s_suppkey", "s_nationkey"])
    ps = load_table(schema, "partsupp", ["ps_partkey", "ps_suppkey", "ps_supplycost"])
    li = load_table(schema, "lineitem", ["l_partkey", "l_suppkey", "l_orderkey", "l_quantity", "l_extendedprice", "l_discount"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_orderdate"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name"])
    nname = {n["n_nationkey"]: n["n_name"] for n in nation}
    green = {p["p_partkey"] for p in part if _like(p["p_name"], f"%{color}%")}
    snat = {s["s_suppkey"]: nname[s["s_nationkey"]] for s in supp}
    cost = {(r["ps_partkey"], r["ps_suppkey"]): r["ps_supplycost"] for r in ps}
    odate = {o["o_orderkey"]: o["o_orderdate"] for o in orders}
    groups = defaultdict(Decimal)
    for r in li:
        if r["l_partkey"] not in green:
            continue
        amount = r["l_extendedprice"] * (1 - r["l_discount"]) - cost[
            (r["l_partkey"], r["l_suppkey"])
        ] * r["l_quantity"]
        k = (snat[r["l_suppkey"]], odate[r["l_orderkey"]].year)
        groups[k] += amount
    rows = [(k[0], k[1], v) for k, v in groups.items()]
    rows.sort(key=lambda t: (t[0], -t[1]))
    return rows


def q10(schema="tiny", limit=20):
    cust = load_table(schema, "customer")
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_returnflag", "l_extendedprice", "l_discount"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name"])
    nname = {n["n_nationkey"]: n["n_name"] for n in nation}
    lo, hi = d("1993-10-01"), d("1994-01-01")
    okeep = {
        o["o_orderkey"]: o["o_custkey"]
        for o in orders
        if lo <= o["o_orderdate"] < hi
    }
    rev = defaultdict(Decimal)
    for r in li:
        if r["l_returnflag"] != "R" or r["l_orderkey"] not in okeep:
            continue
        rev[okeep[r["l_orderkey"]]] += r["l_extendedprice"] * (1 - r["l_discount"])
    rows = []
    for c in cust:
        k = c["c_custkey"]
        if k not in rev:
            continue
        rows.append(
            (k, c["c_name"], rev[k], c["c_acctbal"], nname[c["c_nationkey"]],
             c["c_address"], c["c_phone"], c["c_comment"])
        )
    rows.sort(key=lambda t: -t[2])
    return rows[:limit]


def q11(schema="tiny"):
    ps = load_table(schema, "partsupp", ["ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost"])
    supp = load_table(schema, "supplier", ["s_suppkey", "s_nationkey"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name"])
    germany = {n["n_nationkey"] for n in nation if n["n_name"] == "GERMANY"}
    gsupp = {s["s_suppkey"] for s in supp if s["s_nationkey"] in germany}
    groups = defaultdict(Decimal)
    total = Decimal(0)
    for r in ps:
        if r["ps_suppkey"] not in gsupp:
            continue
        v = r["ps_supplycost"] * r["ps_availqty"]
        groups[r["ps_partkey"]] += v
        total += v
    cutoff = total * Decimal("0.0001")
    rows = [(k, v) for k, v in groups.items() if v > cutoff]
    rows.sort(key=lambda t: -t[1])
    return rows


def q12(schema="tiny"):
    orders = load_table(schema, "orders", ["o_orderkey", "o_orderpriority"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"])
    omap = {o["o_orderkey"]: o["o_orderpriority"] for o in orders}
    lo, hi = d("1994-01-01"), d("1995-01-01")
    high = defaultdict(int)
    low = defaultdict(int)
    for r in li:
        if r["l_shipmode"] not in ("MAIL", "SHIP"):
            continue
        if not (r["l_commitdate"] < r["l_receiptdate"] and r["l_shipdate"] < r["l_commitdate"]):
            continue
        if not (lo <= r["l_receiptdate"] < hi):
            continue
        pri = omap[r["l_orderkey"]]
        if pri in ("1-URGENT", "2-HIGH"):
            high[r["l_shipmode"]] += 1
            low[r["l_shipmode"]] += 0
        else:
            high[r["l_shipmode"]] += 0
            low[r["l_shipmode"]] += 1
    return [(m, high[m], low[m]) for m in sorted(set(high) | set(low))]


def q13(schema="tiny", word1="special", word2="requests"):
    cust = load_table(schema, "customer", ["c_custkey"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_custkey", "o_comment"])
    cnt = defaultdict(int)
    for o in orders:
        if _like(o["o_comment"], f"%{word1}%{word2}%"):
            continue
        cnt[o["o_custkey"]] += 1
    dist = defaultdict(int)
    for c in cust:
        dist[cnt.get(c["c_custkey"], 0)] += 1
    rows = [(k, v) for k, v in dist.items()]
    rows.sort(key=lambda t: (-t[1], -t[0]))
    return rows


def q14(schema="tiny"):
    li = load_table(schema, "lineitem", ["l_partkey", "l_shipdate", "l_extendedprice", "l_discount"])
    part = load_table(schema, "part", ["p_partkey", "p_type"])
    promo = {p["p_partkey"] for p in part if _like(p["p_type"], "PROMO%")}
    lo, hi = d("1995-09-01"), d("1995-10-01")
    num = Decimal(0)
    den = Decimal(0)
    for r in li:
        if not (lo <= r["l_shipdate"] < hi):
            continue
        v = r["l_extendedprice"] * (1 - r["l_discount"])
        den += v
        if r["l_partkey"] in promo:
            num += v
    return [(_divq(Decimal("100.00") * num, den, 6),)]


def q15(schema="tiny"):
    li = load_table(schema, "lineitem", ["l_suppkey", "l_shipdate", "l_extendedprice", "l_discount"])
    supp = load_table(schema, "supplier", ["s_suppkey", "s_name", "s_address", "s_phone"])
    lo, hi = d("1996-01-01"), d("1996-04-01")
    rev = defaultdict(Decimal)
    for r in li:
        if lo <= r["l_shipdate"] < hi:
            rev[r["l_suppkey"]] += r["l_extendedprice"] * (1 - r["l_discount"])
    top = max(rev.values())
    rows = [
        (s["s_suppkey"], s["s_name"], s["s_address"], s["s_phone"], rev[s["s_suppkey"]])
        for s in supp
        if rev.get(s["s_suppkey"]) == top
    ]
    rows.sort(key=lambda t: t[0])
    return rows


def q16(schema="tiny"):
    ps = load_table(schema, "partsupp", ["ps_partkey", "ps_suppkey"])
    part = load_table(schema, "part", ["p_partkey", "p_brand", "p_type", "p_size"])
    supp = load_table(schema, "supplier", ["s_suppkey", "s_comment"])
    bad = {
        s["s_suppkey"] for s in supp if _like(s["s_comment"], "%Customer%Complaints%")
    }
    sizes = {49, 14, 23, 45, 19, 3, 36, 9}
    pmap = {
        p["p_partkey"]: p
        for p in part
        if p["p_brand"] != "Brand#45"
        and not _like(p["p_type"], "MEDIUM POLISHED%")
        and p["p_size"] in sizes
    }
    groups = defaultdict(set)
    for r in ps:
        p = pmap.get(r["ps_partkey"])
        if p is None or r["ps_suppkey"] in bad:
            continue
        groups[(p["p_brand"], p["p_type"], p["p_size"])].add(r["ps_suppkey"])
    rows = [(k[0], k[1], k[2], len(v)) for k, v in groups.items()]
    rows.sort(key=lambda t: (-t[3], t[0], t[1], t[2]))
    return rows


def q17(schema="tiny"):
    li = load_table(schema, "lineitem", ["l_partkey", "l_quantity", "l_extendedprice"])
    part = load_table(schema, "part", ["p_partkey", "p_brand", "p_container"])
    target = {
        p["p_partkey"]
        for p in part
        if p["p_brand"] == "Brand#23" and p["p_container"] == "MED BOX"
    }
    qty = defaultdict(list)
    for r in li:
        qty[r["l_partkey"]].append(r["l_quantity"])
    total = Decimal(0)
    for r in li:
        if r["l_partkey"] not in target:
            continue
        qs = qty[r["l_partkey"]]
        avg = _avgq(sum(qs, Decimal(0)), len(qs), 2)
        if r["l_quantity"] < Decimal("0.2") * avg:
            total += r["l_extendedprice"]
    return [(_divq(total, Decimal("7.0"), 2),)]


def q19(schema="tiny"):
    li = load_table(schema, "lineitem", ["l_partkey", "l_quantity", "l_extendedprice", "l_discount", "l_shipmode", "l_shipinstruct"])
    part = load_table(schema, "part", ["p_partkey", "p_brand", "p_container", "p_size"])
    pmap = {p["p_partkey"]: p for p in part}
    total = Decimal(0)
    for r in li:
        if r["l_shipmode"] not in ("AIR", "AIR REG") or r["l_shipinstruct"] != "DELIVER IN PERSON":
            continue
        p = pmap[r["l_partkey"]]
        q = r["l_quantity"]
        ok = (
            (p["p_brand"] == "Brand#12"
             and p["p_container"] in ("SM CASE", "SM BOX", "SM PACK", "SM PKG")
             and 1 <= q <= 11 and 1 <= p["p_size"] <= 5)
            or (p["p_brand"] == "Brand#23"
                and p["p_container"] in ("MED BAG", "MED BOX", "MED PKG", "MED PACK")
                and 10 <= q <= 20 and 1 <= p["p_size"] <= 10)
            or (p["p_brand"] == "Brand#34"
                and p["p_container"] in ("LG CASE", "LG BOX", "LG PACK", "LG PKG")
                and 20 <= q <= 30 and 1 <= p["p_size"] <= 15)
        )
        if ok:
            total += r["l_extendedprice"] * (1 - r["l_discount"])
    return [(total,)]


def q20(schema="tiny"):
    supp = load_table(schema, "supplier", ["s_suppkey", "s_name", "s_address", "s_nationkey"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name"])
    ps = load_table(schema, "partsupp", ["ps_partkey", "ps_suppkey", "ps_availqty"])
    part = load_table(schema, "part", ["p_partkey", "p_name"])
    li = load_table(schema, "lineitem", ["l_partkey", "l_suppkey", "l_quantity", "l_shipdate"])
    canada = {n["n_nationkey"] for n in nation if n["n_name"] == "CANADA"}
    forest = {p["p_partkey"] for p in part if _like(p["p_name"], "forest%")}
    lo, hi = d("1994-01-01"), d("1995-01-01")
    shipped = defaultdict(Decimal)
    for r in li:
        if lo <= r["l_shipdate"] < hi:
            shipped[(r["l_partkey"], r["l_suppkey"])] += r["l_quantity"]
    good_supp = set()
    for r in ps:
        k = (r["ps_partkey"], r["ps_suppkey"])
        if r["ps_partkey"] not in forest or k not in shipped:
            continue
        if r["ps_availqty"] > Decimal("0.5") * shipped[k]:
            good_supp.add(r["ps_suppkey"])
    rows = [
        (s["s_name"], s["s_address"])
        for s in supp
        if s["s_suppkey"] in good_supp and s["s_nationkey"] in canada
    ]
    rows.sort()
    return rows


def q21(schema="tiny", limit=100, nation_name="SAUDI ARABIA"):
    supp = load_table(schema, "supplier", ["s_suppkey", "s_name", "s_nationkey"])
    li = load_table(schema, "lineitem", ["l_orderkey", "l_suppkey", "l_receiptdate", "l_commitdate"])
    orders = load_table(schema, "orders", ["o_orderkey", "o_orderstatus"])
    nation = load_table(schema, "nation", ["n_nationkey", "n_name"])
    saudi = {n["n_nationkey"] for n in nation if n["n_name"] == nation_name}
    sname = {s["s_suppkey"]: s["s_name"] for s in supp if s["s_nationkey"] in saudi}
    fstat = {o["o_orderkey"] for o in orders if o["o_orderstatus"] == "F"}
    by_order = defaultdict(list)
    for r in li:
        by_order[r["l_orderkey"]].append(r)
    groups = defaultdict(int)
    for r in li:
        if r["l_suppkey"] not in sname:
            continue
        if r["l_orderkey"] not in fstat:
            continue
        if not (r["l_receiptdate"] > r["l_commitdate"]):
            continue
        others = [x for x in by_order[r["l_orderkey"]] if x["l_suppkey"] != r["l_suppkey"]]
        if not others:
            continue
        if any(x["l_receiptdate"] > x["l_commitdate"] for x in others):
            continue
        groups[sname[r["l_suppkey"]]] += 1
    rows = [(k, v) for k, v in groups.items()]
    rows.sort(key=lambda t: (-t[1], t[0]))
    return rows[:limit]


def q22(schema="tiny"):
    cust = load_table(schema, "customer", ["c_custkey", "c_phone", "c_acctbal"])
    orders = load_table(schema, "orders", ["o_custkey"])
    codes = {"13", "31", "23", "29", "30", "18", "17"}
    pool = [c for c in cust if c["c_phone"][:2] in codes and c["c_acctbal"] > 0]
    avg = _avgq(sum((c["c_acctbal"] for c in pool), Decimal(0)), len(pool), 2)
    has_order = {o["o_custkey"] for o in orders}
    groups = defaultdict(lambda: [0, Decimal(0)])
    for c in cust:
        code = c["c_phone"][:2]
        if code not in codes or c["c_acctbal"] <= avg:
            continue
        if c["c_custkey"] in has_order:
            continue
        g = groups[code]
        g[0] += 1
        g[1] += c["c_acctbal"]
    return [(k, v[0], v[1]) for k, v in sorted(groups.items())]
