"""The benchmark's own TPC-H: schema, generator, and the plain reference.

``SCHEMAS`` holds the specification's eight tables with every column at
its declared width.  ``generate`` draws the columns that the program's
cut has (``tinysql_tpu/bench/tpch.py`` as of PR 22) as that generator
draws them, seed for seed, and the rest of the specification's columns
and tables from a second stream of the same seed, so that a later PR may
change the program's generator without moving the yardstick.  ``days``
keeps the two date columns that the queries filter on as day numbers,
from before they are printed as strings, for the reference's use.

The reference answers a query template from the generated arrays with
numpy and nothing else: it imports nothing of the program and takes
nothing the program has made.  ``dtype`` is the precision it computes in:
``float64`` is the reference; ``float32`` is the *control* (the nearest
precision below the double the schema states), which the comparison has to
reject.
"""
from __future__ import annotations

import numpy as np

#: The specification's eight tables with every column and its declared
#: width (TPC-H 1.4.1), in the types the engine has: identifier ->
#: bigint, integer -> int, fixed text -> char(n), variable text ->
#: varchar(n).  What the engine cannot express (the configuration's
#: ``assumed``): decimal -> double (its parser maps DECIMAL to double),
#: date -> varchar(10) holding 'YYYY-MM-DD' (it has no DATE; strings
#: compare as the dates do).  ``l_id`` is added: the engine's clustered
#: handle is one integer column, and a composite key would be a secondary
#: index that ``bulk_load`` does not build.
SCHEMAS = {
    "region": """create table region (
        r_regionkey bigint primary key,
        r_name char(25),
        r_comment varchar(152))""",
    "nation": """create table nation (
        n_nationkey bigint primary key,
        n_name char(25),
        n_regionkey bigint,
        n_comment varchar(152))""",
    "supplier": """create table supplier (
        s_suppkey bigint primary key,
        s_name char(25),
        s_address varchar(40),
        s_nationkey bigint,
        s_phone char(15),
        s_acctbal double,
        s_comment varchar(101))""",
    "part": """create table part (
        p_partkey bigint primary key,
        p_name varchar(55),
        p_mfgr char(25),
        p_brand char(10),
        p_type varchar(25),
        p_size int,
        p_container char(10),
        p_retailprice double,
        p_comment varchar(23))""",
    "partsupp": """create table partsupp (
        ps_partkey bigint,
        ps_suppkey bigint,
        ps_availqty int,
        ps_supplycost double,
        ps_comment varchar(199))""",
    "customer": """create table customer (
        c_custkey bigint primary key,
        c_name varchar(25),
        c_address varchar(40),
        c_nationkey bigint,
        c_phone char(15),
        c_acctbal double,
        c_mktsegment char(10),
        c_comment varchar(117))""",
    "orders": """create table orders (
        o_orderkey bigint primary key,
        o_custkey bigint,
        o_orderstatus char(1),
        o_totalprice double,
        o_orderdate varchar(10),
        o_orderpriority char(15),
        o_clerk char(15),
        o_shippriority int,
        o_comment varchar(79))""",
    "lineitem": """create table lineitem (
        l_id bigint primary key,
        l_orderkey bigint,
        l_partkey bigint,
        l_suppkey bigint,
        l_linenumber int,
        l_quantity double,
        l_extendedprice double,
        l_discount double,
        l_tax double,
        l_returnflag char(1),
        l_linestatus char(1),
        l_shipdate varchar(10),
        l_commitdate varchar(10),
        l_receiptdate varchar(10),
        l_shipinstruct char(25),
        l_shipmode char(10),
        l_comment varchar(44))""",
}

DATABASE = "tpch"

_SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE",
                      "MACHINERY", "HOUSEHOLD"])
_EPOCH = np.datetime64("1992-01-01")
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
_WORDS = ["furiously", "carefully", "quickly", "slyly", "blithely", "even",
          "final", "ironic", "pending", "regular", "express", "bold",
          "packages", "requests", "accounts", "deposits", "foxes", "ideas",
          "theodolites", "pinto", "beans", "instructions", "dependencies",
          "excuses", "platelets", "asymptotes", "courts", "dolphins",
          "sleep", "wake", "are", "cajole", "haggle", "nag", "use", "boost",
          "above", "the", "about", "across", "after", "among", "along"]
_COLORS = np.array(["almond", "antique", "aquamarine", "azure", "beige",
                    "bisque", "black", "blanched", "blue", "blush", "brown",
                    "burlywood", "burnished", "chartreuse", "chiffon",
                    "chocolate", "coral", "cornflower", "cornsilk", "cream",
                    "cyan", "dark", "deep", "dim", "dodger", "drab",
                    "firebrick", "floral", "forest", "frosted", "gainsboro",
                    "ghost", "goldenrod", "green", "grey", "honeydew"])
_PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                        "5-LOW"])
_INSTRUCTIONS = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                          "TAKE BACK RETURN"])
_MODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"])
_TYPES = np.array([f"{a} {b} {c}"
                   for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE",
                             "ECONOMY", "PROMO")
                   for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                             "BRUSHED")
                   for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")])
_CONTAINERS = np.array([f"{a} {b}"
                        for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
                        for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK",
                                  "CAN", "DRUM")])
_ALNUM = np.array(list("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                       "0123456789, "))
_POOL = 16384  # distinct texts of one column, as dbgen cuts from one pool


def _date_strings(days: np.ndarray) -> np.ndarray:
    """'YYYY-MM-DD' of days since 1992-01-01, by a table of every day."""
    table = (_EPOCH + np.arange(int(days.max()) + 1)
             .astype("timedelta64[D]")).astype("datetime64[D]")
    return table.astype("<U10")[days]


def _tagged_names(tag: str, ids: np.ndarray) -> np.ndarray:
    return np.char.add(tag + "#", np.char.zfill(ids.astype(str), 9))


def _comments(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` texts of ``lo`` to ``hi`` characters (the specification's
    range for the column), drawn from a pool of sentences."""
    size = min(n, _POOL)
    words = np.array(_WORDS)[rng.integers(0, len(_WORDS),
                                          (size, hi // 4 + 1))]
    pool = [" ".join(row)[:length]
            for row, length in zip(words, rng.integers(lo, hi + 1, size))]
    return np.array(pool)[rng.integers(0, len(pool), n)]


def _addresses(rng, n: int) -> np.ndarray:
    """The specification's v-strings: 10 to 40 random characters."""
    size = min(n, _POOL)
    chars = _ALNUM[rng.integers(0, len(_ALNUM), (size, 40))]
    pool = ["".join(row)[:length]
            for row, length in zip(chars, rng.integers(10, 41, size))]
    return np.array(pool)[rng.integers(0, len(pool), n)]


def _phones(rng, nationkey: np.ndarray, last7: np.ndarray) -> np.ndarray:
    """CC-LLL-LLL-LLLL, CC the nation's key + 10."""
    out = np.char.add((nationkey + 10).astype(str), "-")
    for part in (rng.integers(100, 1000, len(nationkey)).astype(str), "-",
                 (last7 // 10_000).astype(str), "-",
                 np.char.zfill((last7 % 10_000).astype(str), 4)):
        out = np.char.add(out, part)
    return out.astype("<U15")


class Dataset:
    """``tables``: {table: {column: ndarray}} in CREATE TABLE column order;
    ``days``: {date column: days since 1992-01-01}."""

    def __init__(self, tables: dict, days: dict):
        self.tables = tables
        self.days = days
        self.memo = {}  # the reference's own intermediate arrays


def generate(sf: float, seed: int) -> Dataset:
    """Two streams from the seed.  ``rng`` draws what the program's
    generator draws, in its order (so the columns the three queries read
    are, seed for seed, those of ``tinysql_tpu/bench/tpch.py`` as of PR
    22); ``more`` draws the columns and tables the specification has
    beside them."""
    rng = np.random.default_rng(seed)
    more = np.random.default_rng([seed, 1])
    n_cust = int(150_000 * sf)
    n_ord = int(1_500_000 * sf)
    n_supp = max(int(10_000 * sf), 10)
    n_part = max(int(200_000 * sf), 10)
    n_clerk = max(int(1_000 * sf), 1)
    n_li_avg = 4  # ~6M lineitems at SF=1

    region = {
        "r_regionkey": np.arange(len(_REGIONS), dtype=np.int64),
        "r_name": np.array(_REGIONS),
        "r_comment": _comments(more, len(_REGIONS), 31, 115),
    }
    nation = {
        "n_nationkey": np.arange(len(_NATIONS), dtype=np.int64),
        "n_name": np.array([n for n, _ in _NATIONS]),
        "n_regionkey": np.array([r for _, r in _NATIONS], dtype=np.int64),
        "n_comment": _comments(more, len(_NATIONS), 31, 114),
    }
    supp_ids = np.arange(1, n_supp + 1, dtype=np.int64)
    s_nationkey = rng.integers(0, len(_NATIONS), n_supp).astype(np.int64)
    supplier = {
        "s_suppkey": supp_ids,
        "s_name": _tagged_names("Supplier", supp_ids),
        "s_address": _addresses(more, n_supp),
        "s_nationkey": s_nationkey,
        "s_phone": _phones(more, s_nationkey,
                           more.integers(100_0000, 999_9999, n_supp)),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": _comments(more, n_supp, 25, 100),
    }

    part_ids = np.arange(1, n_part + 1, dtype=np.int64)
    mfgr = more.integers(1, 6, n_part)
    brand = mfgr * 10 + more.integers(1, 6, n_part)
    p_name = _COLORS[more.integers(0, len(_COLORS), n_part)]
    for _ in range(4):
        p_name = np.char.add(np.char.add(p_name, " "), _COLORS[
            more.integers(0, len(_COLORS), n_part)])
    part = {
        "p_partkey": part_ids,
        "p_name": p_name,
        "p_mfgr": np.array([f"Manufacturer#{i}" for i in range(6)])[mfgr],
        "p_brand": np.array([f"Brand#{i}" for i in range(56)])[brand],
        "p_type": _TYPES[more.integers(0, len(_TYPES), n_part)],
        "p_size": more.integers(1, 51, n_part).astype(np.int64),
        "p_container": _CONTAINERS[more.integers(0, len(_CONTAINERS),
                                                 n_part)],
        "p_retailprice": (90_000 + (part_ids // 10) % 20_001
                          + 100 * (part_ids % 1_000)) / 100.0,
        "p_comment": _comments(more, n_part, 5, 22),
    }
    ps_partkey = np.repeat(part_ids, 4)
    nth = np.tile(np.arange(4, dtype=np.int64), n_part)
    partsupp = {
        "ps_partkey": ps_partkey,
        "ps_suppkey": (ps_partkey + nth * (n_supp // 4
                                           + (ps_partkey - 1) // n_supp))
        % n_supp + 1,
        "ps_availqty": more.integers(1, 10_000, 4 * n_part).astype(np.int64),
        "ps_supplycost": np.round(more.uniform(1.0, 1000.0, 4 * n_part), 2),
        "ps_comment": _comments(more, 4 * n_part, 49, 198),
    }

    cust_ids = np.arange(1, n_cust + 1, dtype=np.int64)
    c_nationkey = rng.integers(0, len(_NATIONS), n_cust).astype(np.int64)
    rng.integers(0, 10 ** 9, n_cust)  # the program's c_address: dropped
    c_phone = _phones(more, c_nationkey,
                      rng.integers(100_0000, 999_9999, n_cust))
    c_mktsegment = _SEGMENTS[rng.integers(0, len(_SEGMENTS), n_cust)]
    c_acctbal = np.round(rng.uniform(-999.99, 9999.99, n_cust), 2)
    for _ in range(2):  # the program's two-word c_comment: dropped
        rng.integers(0, 12, n_cust)
    customer = {
        "c_custkey": cust_ids,
        "c_name": _tagged_names("Customer", cust_ids),
        "c_address": _addresses(more, n_cust),
        "c_nationkey": c_nationkey,
        "c_phone": c_phone,
        "c_acctbal": c_acctbal,
        "c_mktsegment": c_mktsegment,
        "c_comment": _comments(more, n_cust, 29, 116),
    }

    o_days = rng.integers(0, 2405, n_ord)
    clerks = _tagged_names("Clerk", np.arange(1, n_clerk + 1))
    orders = {
        "o_orderkey": np.arange(1, n_ord + 1, dtype=np.int64),
        "o_custkey": rng.integers(1, n_cust + 1, n_ord).astype(np.int64),
        "o_orderstatus": np.array(["O", "F", "P"])[rng.integers(0, 3, n_ord)],
        "o_totalprice": np.round(rng.uniform(800.0, 500_000.0, n_ord), 2),
        "o_orderdate": _date_strings(o_days),
        "o_orderpriority": _PRIORITIES[more.integers(0, 5, n_ord)],
        "o_clerk": clerks[more.integers(0, n_clerk, n_ord)],
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": _comments(more, n_ord, 19, 78),
    }

    per_order = rng.integers(1, 2 * n_li_avg, n_ord)
    l_orderkey = np.repeat(orders["o_orderkey"], per_order)
    n_li = len(l_orderkey)
    l_days = np.repeat(o_days, per_order) + rng.integers(1, 122, n_li)
    first = np.repeat(np.cumsum(per_order) - per_order, per_order)
    commit_days = np.repeat(o_days, per_order) + more.integers(30, 91, n_li)
    receipt_days = l_days + more.integers(1, 31, n_li)
    lineitem = {
        "l_id": np.arange(1, n_li + 1, dtype=np.int64),
        "l_orderkey": l_orderkey,
        "l_partkey": more.integers(1, n_part + 1, n_li).astype(np.int64),
        "l_suppkey": rng.integers(1, n_supp + 1, n_li).astype(np.int64),
        "l_linenumber": np.arange(n_li, dtype=np.int64) - first + 1,
        "l_quantity": rng.integers(1, 51, n_li).astype(np.float64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105_000.0, n_li), 2),
        "l_discount": np.round(rng.integers(0, 11, n_li) * 0.01, 2),
        "l_tax": np.round(rng.integers(0, 9, n_li) * 0.01, 2),
        "l_returnflag": np.array(["A", "N", "R"])[rng.integers(0, 3, n_li)],
        "l_linestatus": np.array(["O", "F"])[rng.integers(0, 2, n_li)],
        "l_shipdate": _date_strings(l_days),
        "l_commitdate": _date_strings(commit_days),
        "l_receiptdate": _date_strings(receipt_days),
        "l_shipinstruct": _INSTRUCTIONS[more.integers(0, 4, n_li)],
        "l_shipmode": _MODES[more.integers(0, 7, n_li)],
        "l_comment": _comments(more, n_li, 10, 43),
    }
    tables = {"region": region, "nation": nation, "supplier": supplier,
              "part": part, "partsupp": partsupp, "customer": customer,
              "orders": orders, "lineitem": lineitem}
    return Dataset(tables, {"o_orderdate": o_days, "l_shipdate": l_days})


# ---- the plain reference ---------------------------------------------------

def _day(date: str) -> int:
    return int((np.datetime64(date) - _EPOCH).astype(int))


def _memo(ds: Dataset, key, make):
    if key not in ds.memo:
        ds.memo[key] = make()
    return ds.memo[key]


def _money(ds: Dataset, dtype) -> tuple:
    """l_quantity, l_extendedprice, l_discount, l_tax in ``dtype``."""
    li = ds.tables["lineitem"]
    return _memo(ds, ("money", dtype), lambda: tuple(
        li[c].astype(dtype) for c in
        ("l_quantity", "l_extendedprice", "l_discount", "l_tax")))


def q1(ds: Dataset, params: dict, dtype=np.float64) -> list:
    li = ds.tables["lineitem"]
    qty, price, disc, tax = _money(ds, dtype)
    one = dtype(1)
    keep = ds.days["l_shipdate"] <= _day(params["date"])
    rows = []
    for flag in np.unique(li["l_returnflag"]):
        for status in np.unique(li["l_linestatus"]):
            m = keep & (li["l_returnflag"] == flag) \
                & (li["l_linestatus"] == status)
            n = int(m.sum())
            if not n:
                continue
            q, p, d, t = qty[m], price[m], disc[m], tax[m]
            disc_price = p * (one - d)
            sums = [x.sum(dtype=dtype) for x in
                    (q, p, disc_price, disc_price * (one + t))]
            avgs = [x.sum(dtype=dtype) / dtype(n) for x in (q, p, d)]
            rows.append((str(flag), str(status),
                         *(float(x) for x in sums + avgs), n))
    return rows


def q3(ds: Dataset, params: dict, dtype=np.float64) -> list:
    t = ds.tables
    cut = _day(params["date"])
    building = t["customer"]["c_mktsegment"] == params["segment"]
    # c_custkey and o_orderkey are 1..n in order: a key indexes its row
    o_keep = building[t["orders"]["o_custkey"] - 1] \
        & (ds.days["o_orderdate"] < cut)
    l_order = t["lineitem"]["l_orderkey"]
    l_keep = o_keep[l_order - 1] & (ds.days["l_shipdate"] > cut)
    keys = l_order[l_keep]
    if not len(keys):
        return []
    _qty, price, disc, _tax = _money(ds, dtype)
    value = price[l_keep] * (dtype(1) - disc[l_keep])
    # l_orderkey ascends, so each group is one run
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    revenue = np.add.reduceat(value, starts)
    group_keys = keys[starts]
    dates = t["orders"]["o_orderdate"][group_keys - 1]
    order = np.lexsort((dates, -revenue.astype(np.float64)))
    head = order[:int(params["limit"])]
    return [(int(group_keys[i]), float(revenue[i]), str(dates[i]),
             int(t["orders"]["o_shippriority"][group_keys[i] - 1]))
            for i in head]


def q6(ds: Dataset, params: dict, dtype=np.float64) -> list:
    date, discount = params["date"], params["discount"]

    def in_range():
        days = ds.days["l_shipdate"]
        rows = np.flatnonzero((days >= _day(date["lo"]))
                              & (days < _day(date["hi"])))
        li = ds.tables["lineitem"]
        return rows, li["l_discount"][rows], li["l_quantity"][rows]

    rows, l_discount, l_quantity = _memo(
        ds, ("q6 rows", date["lo"], date["hi"]), in_range)
    _qty, price, disc, _tax = _money(ds, dtype)
    price, disc = _memo(ds, ("q6 money", date["lo"], date["hi"], dtype),
                        lambda: (price[rows], disc[rows]))
    # the literals compare as the doubles the text says, whatever dtype
    # the sum runs in: the filter is the query's, not the arithmetic's
    m = (l_discount >= float(discount["lo"])) \
        & (l_discount <= float(discount["hi"])) \
        & (l_quantity < float(params["quantity"]))
    if not m.any():
        return [(None,)]
    return [(float((price[m] * disc[m]).sum(dtype=dtype)),)]


REFERENCES = {"q1": q1, "q3": q3, "q6": q6}

#: the precision the schema states (``double``) and the one below it
REFERENCE_DTYPE = np.float64
CONTROL_DTYPE = np.float32


# ---- what the roofline counts ----------------------------------------------

#: bytes of one value of each SQL type of the cut as the schema declares it
#: (bigint and double are 8 bytes, int 4; a char(n) or varchar(n) column
#: is counted at n bytes a row, its declared width)
def column_bytes(table: str, column: str) -> int:
    for line in SCHEMAS[table].splitlines()[1:]:
        parts = line.strip().rstrip(",)").split()
        if parts and parts[0] == column:
            sql_type = parts[1].rstrip(",)")
            if sql_type in ("bigint", "double"):
                return 8
            if sql_type == "int":
                return 4
            if sql_type.startswith(("varchar(", "char(")):
                return int(sql_type[sql_type.index("(") + 1:].rstrip(")"))
            raise ValueError(f"no byte width for SQL type {sql_type!r}")
    raise KeyError(f"{table}.{column} is not in the schema")


def scan_bytes(reads: dict, row_counts: dict) -> int:
    """Bytes a statement must read at the least: every row of every column
    it names, once.  ``reads`` is {table: [column, ...]}."""
    return sum(column_bytes(table, c) * row_counts[table]
               for table, cols in reads.items() for c in cols)
