"""The eight TPC-H tables as parquet, made from a seed, after clause 4.2.3 of
the specification (v3): every column of every table, key ranges, the sparse
``o_orderkey``, customers with no orders, and the rules that tie one column
to another (``l_shipdate = o_orderdate + 1..121``, ``l_extendedprice =
l_quantity * p_retailprice``, ``o_totalprice`` and ``o_orderstatus`` from
the order's lines, ``l_returnflag`` / ``l_linestatus`` from the dates, the
``ps_suppkey`` / ``l_suppkey`` formula, the phone's country code).

It stands in for ``dbgen`` and is not it (``assumed`` in the configuration
file): the random streams are numpy's, so no value equals ``dbgen``'s; text
columns are stretches, of a length drawn from the column's range, of one
pool of words from the specification's lists; the lines of an order are
drawn 1..7 and then moved by one on a few thousand orders so that
``lineitem`` has the specification's 6,001,215 rows x SF at every seed; money
is float64.  ``models/tpch_suite.py::gen_db``, which this replaces as the
yardstick's generator, is listed in PERF.md for a later PR to delete.

The seed is an argument and every table draws from its own stream of it
(``orders`` and ``lineitem`` share one: they are made together), so a table
is the same whichever others are written beside it; only the tables asked
for are written.  ``SCHEMA`` lists, in file order, the bytes a value of each
column takes on the device as the engine holds it, for the queries'
``min_bytes``.
"""

from __future__ import annotations

import os
import zlib
from typing import Dict, Iterable, Optional

import numpy as np

from datagen._columns import money, pick, text

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# clause 4.2.3: nation and the region it belongs to
NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLORS = ("almond antique aquamarine azure beige bisque black blanched blue "
          "blush brown burlywood burnished chartreuse chiffon chocolate "
          "coral cornflower cornsilk cream cyan dark deep dim dodger drab "
          "firebrick floral forest frosted gainsboro ghost goldenrod green "
          "grey honeydew hot indian ivory khaki lace lavender lawn lemon "
          "light lime linen magenta maroon medium metallic midnight mint "
          "misty moccasin navajo navy olive orange orchid pale papaya peach "
          "peru pink plum powder puff purple red rose rosy royal saddle "
          "salmon sandy seashell sienna sky slate smoke snow spring steel "
          "tan thistle tomato turquoise violet wheat white yellow").split()
START = np.datetime64("1992-01-01")     # STARTDATE
CURRENT = np.datetime64("1995-06-17")   # CURRENTDATE
ORDER_DAYS = 2406                       # STARTDATE .. ENDDATE - 151 days

# rows at SF1 (clause 4.2.5)
_SIZES = {
    "lineitem": 6_001_215, "orders": 1_500_000, "customer": 150_000,
    "part": 200_000, "partsupp": 800_000, "supplier": 10_000,
}
TABLES = ["region", "nation", "customer", "supplier", "part", "partsupp",
          "orders", "lineitem"]

# bytes a value of each column takes on the device, as the engine holds it:
# int64 and float64 as they are, a date as int32 days, a string as an int32
# dictionary code (spark_rapids_tpu/types.py)
_I, _F, _D, _S = 8, 8, 4, 4
SCHEMA = {
    "region": {"r_regionkey": _I, "r_name": _S, "r_comment": _S},
    "nation": {"n_nationkey": _I, "n_name": _S, "n_regionkey": _I,
               "n_comment": _S},
    "customer": {"c_custkey": _I, "c_name": _S, "c_address": _S,
                 "c_nationkey": _I, "c_phone": _S, "c_acctbal": _F,
                 "c_mktsegment": _S, "c_comment": _S},
    "supplier": {"s_suppkey": _I, "s_name": _S, "s_address": _S,
                 "s_nationkey": _I, "s_phone": _S, "s_acctbal": _F,
                 "s_comment": _S},
    "part": {"p_partkey": _I, "p_name": _S, "p_mfgr": _S, "p_brand": _S,
             "p_type": _S, "p_size": _I, "p_container": _S,
             "p_retailprice": _F, "p_comment": _S},
    "partsupp": {"ps_partkey": _I, "ps_suppkey": _I, "ps_availqty": _I,
                 "ps_supplycost": _F, "ps_comment": _S},
    "orders": {"o_orderkey": _I, "o_custkey": _I, "o_orderstatus": _S,
               "o_totalprice": _F, "o_orderdate": _D, "o_orderpriority": _S,
               "o_clerk": _S, "o_shippriority": _I, "o_comment": _S},
    "lineitem": {"l_orderkey": _I, "l_partkey": _I, "l_suppkey": _I,
                 "l_linenumber": _I, "l_quantity": _F, "l_extendedprice": _F,
                 "l_discount": _F, "l_tax": _F, "l_returnflag": _S,
                 "l_linestatus": _S, "l_shipdate": _D, "l_commitdate": _D,
                 "l_receiptdate": _D, "l_shipinstruct": _S, "l_shipmode": _S,
                 "l_comment": _S},
}


def rows(sf: float) -> Dict[str, int]:
    """Rows of every table at scale factor ``sf``."""
    out = {"region": len(REGIONS), "nation": len(NATIONS)}
    out.update({t: max(8, int(n * sf)) for t, n in _SIZES.items()})
    out["partsupp"] = 4 * out["part"]
    return out


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(table.encode())])


# -- columns, made whole and vectorised: set-up pays for every second --------

def numbered(prefix: str, keys, digits: int = 9):
    """``Customer#000000001`` and the like."""
    import pyarrow as pa
    return pa.array([f"{prefix}{k:0{digits}d}" for k in keys.tolist()])


def phones(rng, nation):
    """Clause 4.2.2.9: country code nation + 10, three local groups."""
    import pyarrow as pa
    a, b, c = (rng.integers(100, 1000, len(nation)),
               rng.integers(100, 1000, len(nation)),
               rng.integers(1000, 10000, len(nation)))
    return pa.array([f"{n + 10}-{x}-{y}-{z}" for n, x, y, z in zip(
        nation.tolist(), a.tolist(), b.tolist(), c.tolist())])


def dates(days_from_start):
    import pyarrow as pa
    return pa.array(START + days_from_start.astype("timedelta64[D]"),
                    type=pa.date32())


def supplier_of(partkey, i, n_supp):
    """Clause 4.2.3, the ``ps_suppkey`` / ``l_suppkey`` formula: the
    ``i``-th (0..3) of a part's four suppliers."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def retail_price(partkey):
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def lines_per_order(rng, n_ord: int, n_li: int):
    """1..7 lines an order, then moved by one on as many orders as it takes
    for the table to have the specification's rows at every seed."""
    if not n_ord <= n_li <= 7 * n_ord:
        raise ValueError(f"{n_li} lines do not go into {n_ord} orders")
    counts = rng.integers(1, 8, n_ord)
    diff = n_li - int(counts.sum())
    while diff:
        room = np.flatnonzero(counts < 7 if diff > 0 else counts > 1)
        moved = rng.choice(room, min(abs(diff), len(room)), replace=False)
        counts[moved] += 1 if diff > 0 else -1
        diff = n_li - int(counts.sum())
    return counts


def _orders_and_lineitem(seed: int, n: Dict[str, int]):
    """Both tables whole, as arrow tables: a line's dates hang on its
    order's, the order's price and status on its lines."""
    import pyarrow as pa
    rng = _rng(seed, "orders+lineitem")
    n_ord, n_li = n["orders"], n["lineitem"]
    n_cust, n_part, n_supp = n["customer"], n["part"], n["supplier"]

    i = np.arange(n_ord, dtype=np.int64)
    okey = (i // 8) * 32 + i % 8 + 1      # the first 8 of every 32 keys
    j = rng.integers(0, n_cust - n_cust // 3, n_ord)
    custkey = 3 * (j // 2) + j % 2 + 1    # never a multiple of 3
    oday = rng.integers(0, ORDER_DAYS, n_ord)
    counts = lines_per_order(rng, n_ord, n_li)

    of = np.repeat(i, counts)             # a line's order, ascending
    first = np.cumsum(counts) - counts
    partkey = rng.integers(1, n_part + 1, n_li)
    quantity = rng.integers(1, 51, n_li).astype(np.float64)
    extended = np.round(quantity * retail_price(partkey), 2)
    discount = rng.integers(0, 11, n_li) / 100.0
    tax = rng.integers(0, 9, n_li) / 100.0
    ship = oday[of] + rng.integers(1, 122, n_li)
    commit = oday[of] + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    today = int((CURRENT - START).astype(int))
    returned = np.where(receipt <= today, rng.integers(0, 2, n_li), 2)
    open_ = ship > today
    lineitem = pa.table({
        "l_orderkey": okey[of],
        "l_partkey": partkey,
        "l_suppkey": supplier_of(partkey, rng.integers(0, 4, n_li), n_supp),
        "l_linenumber": np.arange(n_li, dtype=np.int64) - first[of] + 1,
        "l_quantity": quantity,
        "l_extendedprice": extended,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": pick("RAN", returned),
        "l_linestatus": pick("FO", open_.astype(np.int64)),
        "l_shipdate": dates(ship),
        "l_commitdate": dates(commit),
        "l_receiptdate": dates(receipt),
        "l_shipinstruct": pick(INSTRUCTIONS, rng.integers(0, 4, n_li)),
        "l_shipmode": pick(SHIPMODES, rng.integers(0, 7, n_li)),
        "l_comment": text(rng, n_li, 10, 43),
    })

    n_open = np.bincount(of, weights=open_, minlength=n_ord)
    status = np.where(n_open == 0, 0, np.where(n_open == counts, 1, 2))
    total = np.bincount(of, weights=extended * (1 + tax) * (1 - discount),
                        minlength=n_ord)
    clerks = max(1, n_ord // 1500)        # SF * 1000
    orders = pa.table({
        "o_orderkey": okey,
        "o_custkey": custkey,
        "o_orderstatus": pick("FOP", status),
        "o_totalprice": np.round(total, 2),
        "o_orderdate": dates(oday),
        "o_orderpriority": pick(PRIORITIES, rng.integers(0, 5, n_ord)),
        "o_clerk": pick([f"Clerk#{k:09d}" for k in range(1, clerks + 1)],
                        rng.integers(0, clerks, n_ord)),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": text(rng, n_ord, 19, 78),
    })
    return orders, lineitem


def gen(sf: float, seed: int, out_dir: str,
        tables: Optional[Iterable[str]] = None,
        chunk: int = 1_000_000) -> Dict[str, str]:
    """Write ``tables`` (all eight when None) under ``out_dir``, anew every
    time, in row groups of ``chunk`` rows; returns {table: parquet path}."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    tables = list(tables) if tables is not None else list(TABLES)
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise ValueError(f"tpch datagen has no table {unknown}")
    os.makedirs(out_dir, exist_ok=True)
    paths = {t: os.path.join(out_dir, f"{t}.parquet") for t in tables}
    n = rows(sf)
    n_cust, n_supp, n_part = n["customer"], n["supplier"], n["part"]

    def write(table, columns):
        t = columns if isinstance(columns, pa.Table) else pa.table(columns)
        assert t.column_names == list(SCHEMA[table]), table
        pq.write_table(t, paths[table], row_group_size=chunk)

    if "region" in paths:
        rng = _rng(seed, "region")
        write("region", {
            "r_regionkey": np.arange(len(REGIONS), dtype=np.int64),
            "r_name": REGIONS,
            "r_comment": text(rng, len(REGIONS), 31, 115),
        })

    if "nation" in paths:
        rng = _rng(seed, "nation")
        write("nation", {
            "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
            "n_name": [name for name, _ in NATIONS],
            "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
            "n_comment": text(rng, len(NATIONS), 31, 114),
        })

    if "customer" in paths:
        rng = _rng(seed, "customer")
        key = np.arange(1, n_cust + 1, dtype=np.int64)
        nation = rng.integers(0, len(NATIONS), n_cust)
        write("customer", {
            "c_custkey": key,
            "c_name": numbered("Customer#", key),
            "c_address": text(rng, n_cust, 10, 40),
            "c_nationkey": nation,
            "c_phone": phones(rng, nation),
            "c_acctbal": money(rng, -999.99, 9999.99, n_cust),
            "c_mktsegment": pick(SEGMENTS, rng.integers(0, 5, n_cust)),
            "c_comment": text(rng, n_cust, 29, 116),
        })

    if "supplier" in paths:
        rng = _rng(seed, "supplier")
        key = np.arange(1, n_supp + 1, dtype=np.int64)
        nation = rng.integers(0, len(NATIONS), n_supp)
        write("supplier", {
            "s_suppkey": key,
            "s_name": numbered("Supplier#", key),
            "s_address": text(rng, n_supp, 10, 40),
            "s_nationkey": nation,
            "s_phone": phones(rng, nation),
            "s_acctbal": money(rng, -999.99, 9999.99, n_supp),
            "s_comment": text(rng, n_supp, 25, 100),
        })

    if "part" in paths:
        rng = _rng(seed, "part")
        key = np.arange(1, n_part + 1, dtype=np.int64)
        mfgr = rng.integers(1, 6, n_part)
        brand = mfgr * 10 + rng.integers(1, 6, n_part)
        colors = np.array(COLORS)[rng.integers(0, len(COLORS), (n_part, 5))]
        write("part", {
            "p_partkey": key,
            "p_name": pa.array([" ".join(w) for w in colors.tolist()]),
            "p_mfgr": pick([f"Manufacturer#{m}" for m in range(6)], mfgr),
            "p_brand": pick([f"Brand#{b}" for b in range(56)], brand),
            "p_type": pick(TYPES, rng.integers(0, len(TYPES), n_part)),
            "p_size": rng.integers(1, 51, n_part),
            "p_container": pick(CONTAINERS,
                                rng.integers(0, len(CONTAINERS), n_part)),
            "p_retailprice": retail_price(key),
            "p_comment": text(rng, n_part, 5, 22),
        })

    if "partsupp" in paths:
        rng = _rng(seed, "partsupp")
        part = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
        m = len(part)
        write("partsupp", {
            "ps_partkey": part,
            "ps_suppkey": supplier_of(
                part, np.tile(np.arange(4, dtype=np.int64), n_part), n_supp),
            "ps_availqty": rng.integers(1, 10000, m),
            "ps_supplycost": money(rng, 1.0, 1000.0, m),
            "ps_comment": text(rng, m, 49, 198),
        })

    if "orders" in paths or "lineitem" in paths:
        orders, lineitem = _orders_and_lineitem(seed, n)
        if "orders" in paths:
            write("orders", orders)
        if "lineitem" in paths:
            write("lineitem", lineitem)
    return paths
