"""TPC-H made in row blocks, for the scale factors ``tpch.py`` makes too
slowly: the same eight tables by the same rules, the same plain reference.

``tpch.generate`` draws every column in one pass of one thread: 8 s and
4.5 GB of host arrays at SF=1 on the chip's host; PERF.md section 6 (PR
33) has its seconds and peak memory at SF=10 on the four-chip host beside
this module's.  Here ``orders`` and ``lineitem``, which are
nine tenths of the bytes, are made in blocks of orders, each block from a
stream of its own (``[seed, table, block]``) and written straight into its
rows of arrays allocated once, on as many threads as the host has cores
(numpy's generators, ``take`` and ``repeat`` release the interpreter).  The
six small tables are drawn whole by a copy of ``tpch.generate``'s rules
for them (``_small_tables``): that is one function over all eight tables
in a file this PR may not edit, so its rules cannot be had by import
(PERF.md section 7 names the repair for a ``benchmark`` issue).

What is shared with ``tpch.py`` is imported from it, not copied: the
schema, the value sets, the text pools' maker, the date strings, the
``Dataset`` the reference reads, the reference and its control, and the
roofline's byte counts.  What differs: the streams (a seed gives other
values than ``tpch.generate`` gives, of the same distributions), so the
two modules' answers are compared in shape, not in value
(``benchmark/tests/test_sf10_cell.py``).

``generate`` first asks the program for what this deployment's statements
need of it (the mesh join's broadcast budget counted in bytes, PR 33) and
fails at once without it: a program before PR 33 would run Q3's
``customer`` join at SF=10 through a 64-bit sort that the chip's compiler
takes tens of minutes over, so it is stopped before any data is made.
That is the only thing read of the program, and no data of it is taken.
"""
from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_datasets_tpch",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)

DATABASE = tpch.DATABASE
SCHEMAS = tpch.SCHEMAS
Dataset = tpch.Dataset
REFERENCES = tpch.REFERENCES
REFERENCE_DTYPE = tpch.REFERENCE_DTYPE
CONTROL_DTYPE = tpch.CONTROL_DTYPE
column_bytes = tpch.column_bytes
scan_bytes = tpch.scan_bytes

#: orders a block: about 0.5 M ``lineitem`` rows, 0.2 GB of columns
BLOCK_ORDERS = 1 << 17


def _workers() -> int:
    try:
        return max(len(os.sched_getaffinity(0)), 1)
    except AttributeError:
        return os.cpu_count() or 1


def _require_program() -> None:
    from tinysql_tpu.parallel import dist
    if not hasattr(dist, "broadcast_budget_bytes"):
        raise RuntimeError(
            "this deployment needs a program whose mesh join strategy "
            "counts its broadcast budget in bytes (PR 33): an earlier one "
            "runs Q3's customer join at this scale through a sort that "
            "compiles for tens of minutes on the chip")


def _pool(rng, lo: int, hi: int) -> np.ndarray:
    """A column's pool of texts, as ``tpch._comments`` cuts it."""
    return tpch._comments(rng, tpch._POOL, lo, hi)


def _small_tables(sf: float, rng, more) -> dict:
    """region, nation, supplier, part, partsupp, customer: drawn whole,
    by ``tpch.generate``'s rules (none is a tenth of ``lineitem``)."""
    n_cust = int(150_000 * sf)
    n_supp = max(int(10_000 * sf), 10)
    n_part = max(int(200_000 * sf), 10)
    regions, nations = tpch._REGIONS, tpch._NATIONS
    region = {
        "r_regionkey": np.arange(len(regions), dtype=np.int64),
        "r_name": np.array(regions),
        "r_comment": tpch._comments(more, len(regions), 31, 115),
    }
    nation = {
        "n_nationkey": np.arange(len(nations), dtype=np.int64),
        "n_name": np.array([n for n, _ in nations]),
        "n_regionkey": np.array([r for _, r in nations], dtype=np.int64),
        "n_comment": tpch._comments(more, len(nations), 31, 114),
    }
    supp_ids = np.arange(1, n_supp + 1, dtype=np.int64)
    s_nationkey = rng.integers(0, len(nations), n_supp).astype(np.int64)
    supplier = {
        "s_suppkey": supp_ids,
        "s_name": tpch._tagged_names("Supplier", supp_ids),
        "s_address": tpch._addresses(more, n_supp),
        "s_nationkey": s_nationkey,
        "s_phone": tpch._phones(more, s_nationkey,
                                more.integers(100_0000, 999_9999, n_supp)),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": tpch._comments(more, n_supp, 25, 100),
    }
    part_ids = np.arange(1, n_part + 1, dtype=np.int64)
    mfgr = more.integers(1, 6, n_part)
    brand = mfgr * 10 + more.integers(1, 6, n_part)
    p_name = tpch._COLORS[more.integers(0, len(tpch._COLORS), n_part)]
    for _ in range(4):
        p_name = np.char.add(np.char.add(p_name, " "), tpch._COLORS[
            more.integers(0, len(tpch._COLORS), n_part)])
    part = {
        "p_partkey": part_ids,
        "p_name": p_name,
        "p_mfgr": np.array([f"Manufacturer#{i}" for i in range(6)])[mfgr],
        "p_brand": np.array([f"Brand#{i}" for i in range(56)])[brand],
        "p_type": tpch._TYPES[more.integers(0, len(tpch._TYPES), n_part)],
        "p_size": more.integers(1, 51, n_part).astype(np.int64),
        "p_container": tpch._CONTAINERS[
            more.integers(0, len(tpch._CONTAINERS), n_part)],
        "p_retailprice": (90_000 + (part_ids // 10) % 20_001
                          + 100 * (part_ids % 1_000)) / 100.0,
        "p_comment": tpch._comments(more, n_part, 5, 22),
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
    }  # ps_comment, the widest column of the eight tables: in blocks
    cust_ids = np.arange(1, n_cust + 1, dtype=np.int64)
    c_nationkey = rng.integers(0, len(nations), n_cust).astype(np.int64)
    customer = {
        "c_custkey": cust_ids,
        "c_name": tpch._tagged_names("Customer", cust_ids),
        "c_address": tpch._addresses(more, n_cust),
        "c_nationkey": c_nationkey,
        "c_phone": tpch._phones(more, c_nationkey,
                                rng.integers(100_0000, 999_9999, n_cust)),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
        "c_mktsegment": tpch._SEGMENTS[
            rng.integers(0, len(tpch._SEGMENTS), n_cust)],
        "c_comment": tpch._comments(more, n_cust, 29, 116),
    }
    return {"region": region, "nation": nation, "supplier": supplier,
            "part": part, "partsupp": partsupp, "customer": customer}


def _empty(columns: dict, n: int) -> dict:
    """{column: array of ``n`` rows} for {column: dtype}."""
    return {c: np.empty(n, dtype=dt) for c, dt in columns.items()}


def _take(table: np.ndarray, idx: np.ndarray, out: np.ndarray) -> None:
    # every index is drawn inside the table; "clip" writes ``out``
    # directly where the default mode fills a buffer first
    np.take(table, idx, out=out, mode="clip")


def generate(sf: float, seed: int) -> Dataset:
    _require_program()
    rng = np.random.default_rng([seed, 0])
    more = np.random.default_rng([seed, 1])
    n_cust = int(150_000 * sf)
    n_ord = int(1_500_000 * sf)
    n_supp = max(int(10_000 * sf), 10)
    n_part = max(int(200_000 * sf), 10)
    n_clerk = max(int(1_000 * sf), 1)
    clerks = tpch._tagged_names("Clerk", np.arange(1, n_clerk + 1))
    pools = np.random.default_rng([seed, 3])
    o_comments = _pool(pools, 19, 78)
    l_comments = _pool(pools, 10, 43)
    ps_comments = _pool(pools, 49, 198)
    ps_comment = np.empty(4 * n_part, dtype=ps_comments.dtype)
    day_table = tpch._date_strings(np.arange(2405 + 122 + 31))
    flags, status = np.array(["A", "N", "R"]), np.array(["O", "F"])
    ostatus = np.array(["O", "F", "P"])

    # an order has 1 to 7 lines: its lines' rows follow from every
    # order's count, drawn first
    per_order = rng.integers(1, 8, n_ord)
    ends = np.cumsum(per_order)
    n_li = int(ends[-1]) if n_ord else 0
    orders = _empty({
        "o_orderkey": np.int64, "o_custkey": np.int64,
        "o_orderstatus": ostatus.dtype, "o_totalprice": np.float64,
        "o_orderdate": day_table.dtype,
        "o_orderpriority": tpch._PRIORITIES.dtype, "o_clerk": clerks.dtype,
        "o_shippriority": np.int64, "o_comment": o_comments.dtype}, n_ord)
    lineitem = _empty({
        "l_id": np.int64, "l_orderkey": np.int64, "l_partkey": np.int64,
        "l_suppkey": np.int64, "l_linenumber": np.int64,
        "l_quantity": np.float64, "l_extendedprice": np.float64,
        "l_discount": np.float64, "l_tax": np.float64,
        "l_returnflag": flags.dtype, "l_linestatus": status.dtype,
        "l_shipdate": day_table.dtype, "l_commitdate": day_table.dtype,
        "l_receiptdate": day_table.dtype,
        "l_shipinstruct": tpch._INSTRUCTIONS.dtype,
        "l_shipmode": tpch._MODES.dtype, "l_comment": l_comments.dtype},
        n_li)
    o_days = np.empty(n_ord, dtype=np.int64)
    l_days = np.empty(n_li, dtype=np.int64)

    def block(b: int) -> None:
        a, z = b * BLOCK_ORDERS, min((b + 1) * BLOCK_ORDERS, n_ord)
        m = z - a
        r = np.random.default_rng([seed, 2, b])
        o = {c: v[a:z] for c, v in orders.items()}
        days = r.integers(0, 2405, m)
        o_days[a:z] = days
        o["o_orderkey"][:] = np.arange(a + 1, z + 1)
        o["o_custkey"][:] = r.integers(1, n_cust + 1, m)
        _take(ostatus, r.integers(0, 3, m), o["o_orderstatus"])
        o["o_totalprice"][:] = np.round(r.uniform(800.0, 500_000.0, m), 2)
        _take(day_table, days, o["o_orderdate"])
        _take(tpch._PRIORITIES, r.integers(0, 5, m), o["o_orderpriority"])
        _take(clerks, r.integers(0, n_clerk, m), o["o_clerk"])
        o["o_shippriority"][:] = 0
        _take(o_comments, r.integers(0, len(o_comments), m), o["o_comment"])

        lo = int(ends[a] - per_order[a])
        hi = int(ends[z - 1])
        k = hi - lo
        per = per_order[a:z]
        li = {c: v[lo:hi] for c, v in lineitem.items()}
        ship = np.repeat(days, per) + r.integers(1, 122, k)
        l_days[lo:hi] = ship
        first = np.repeat(ends[a:z] - per, per)
        li["l_id"][:] = np.arange(lo + 1, hi + 1)
        li["l_orderkey"][:] = np.repeat(o["o_orderkey"], per)
        li["l_partkey"][:] = r.integers(1, n_part + 1, k)
        li["l_suppkey"][:] = r.integers(1, n_supp + 1, k)
        li["l_linenumber"][:] = np.arange(lo, hi) - first + 1
        li["l_quantity"][:] = r.integers(1, 51, k)
        li["l_extendedprice"][:] = np.round(
            r.uniform(900.0, 105_000.0, k), 2)
        li["l_discount"][:] = np.round(r.integers(0, 11, k) * 0.01, 2)
        li["l_tax"][:] = np.round(r.integers(0, 9, k) * 0.01, 2)
        _take(flags, r.integers(0, 3, k), li["l_returnflag"])
        _take(status, r.integers(0, 2, k), li["l_linestatus"])
        _take(day_table, ship, li["l_shipdate"])
        _take(day_table, np.repeat(days, per) + r.integers(30, 91, k),
              li["l_commitdate"])
        _take(day_table, ship + r.integers(1, 31, k), li["l_receiptdate"])
        _take(tpch._INSTRUCTIONS, r.integers(0, 4, k), li["l_shipinstruct"])
        _take(tpch._MODES, r.integers(0, 7, k), li["l_shipmode"])
        _take(l_comments, r.integers(0, len(l_comments), k), li["l_comment"])

    def ps_block(b: int) -> None:
        out = ps_comment[b * BLOCK_ORDERS:(b + 1) * BLOCK_ORDERS]
        r = np.random.default_rng([seed, 4, b])
        _take(ps_comments, r.integers(0, len(ps_comments), len(out)), out)

    with ThreadPoolExecutor(max_workers=_workers()) as pool:
        # the small tables on one thread beside the blocks on the others
        small = pool.submit(_small_tables, sf, rng, more)
        done = [pool.submit(block, b)
                for b in range(-(-n_ord // BLOCK_ORDERS))]
        done += [pool.submit(ps_block, b)
                 for b in range(-(-len(ps_comment) // BLOCK_ORDERS))]
        for f in done:
            f.result()
        tables = small.result()
    tables["partsupp"]["ps_comment"] = ps_comment
    tables["orders"] = orders
    tables["lineitem"] = lineitem
    return Dataset(tables, {"o_orderdate": o_days, "l_shipdate": l_days})
