"""TPC-H's correlated-subquery reports Q2 and Q17: the dataset of the
configuration ``tpch_sf1_subq``.

Schema, generator, byte counts and the references of ``tpch.py`` are
imported as they are (``tpch_joins.py`` takes them the same way); what
this module adds is the plain reference of the two statements.  Each
answers its template from the generated host arrays with numpy and
nothing else: a join is a lookup of the foreign key in a key -> row table
made from the primary key's column, the correlated subquery's aggregate
one value a part (``np.add.at`` / ``np.minimum.at`` in ``dtype``), Q17's
threshold ``0.2 * (sum / count)`` in ``dtype`` as the text writes it, an
ORDER BY a stable sort.  Nothing of the program is imported and nothing
it made is read.  Every double of an answer and of a comparison passes
through ``dtype``: the float32 control rounds the supply costs, the
threshold, the revenue and the carried ``s_acctbal``.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_datasets_tpch_for_subq",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)

DATABASE, SCHEMAS, generate = tpch.DATABASE, tpch.SCHEMAS, tpch.generate
Dataset, scan_bytes, column_bytes = (tpch.Dataset, tpch.scan_bytes,
                                     tpch.column_bytes)
REFERENCE_DTYPE, CONTROL_DTYPE = tpch.REFERENCE_DTYPE, tpch.CONTROL_DTYPE
_memo, _money = tpch._memo, tpch._money


def _lookup(ds: Dataset, table: str, key: str, values: np.ndarray):
    """(row of ``table`` whose primary key ``key`` is each of ``values``,
    found?)."""
    def make():
        keys = ds.tables[table][key]
        rows = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
        rows[keys] = np.arange(len(keys))
        return rows
    rows = _memo(ds, ("rows by key", table), make)
    inside = (values >= 0) & (values < len(rows))
    row = np.where(inside, rows[np.where(inside, values, 0)], -1)
    return np.maximum(row, 0), row >= 0


def q2(ds: Dataset, params: dict, dtype=np.float64) -> list:
    """Minimum Cost Supplier: for each part of a size and type, the
    suppliers of a region whose supply cost is the part's least among
    that region's suppliers."""
    t = ds.tables
    part, supplier, partsupp, nation, region = (
        t["part"], t["supplier"], t["partsupp"], t["nation"], t["region"])
    regions = region["r_regionkey"][region["r_name"] == params["region"]]
    n_row, found = _lookup(ds, "nation", "n_nationkey",
                           supplier["s_nationkey"])
    s_keep = found & np.isin(nation["n_regionkey"][n_row], regions)
    s_row, found = _lookup(ds, "supplier", "s_suppkey",
                           partsupp["ps_suppkey"])
    ps_keep = found & s_keep[s_row]
    p_row, found = _lookup(ds, "part", "p_partkey", partsupp["ps_partkey"])
    ps_keep &= found
    cost = partsupp["ps_supplycost"].astype(dtype)
    # the subquery, a value a part: min(ps_supplycost) over the region's
    # suppliers of the part (a part with none compares with NULL)
    least = np.full(len(part["p_partkey"]), np.inf, dtype=dtype)
    np.minimum.at(least, p_row[ps_keep], cost[ps_keep])
    p_keep = (part["p_size"] == int(params["size"])) \
        & np.char.endswith(part["p_type"], params["type"])
    rows = np.flatnonzero(ps_keep & p_keep[p_row]
                          & (cost == least[p_row]))
    s, p = s_row[rows], p_row[rows]
    acctbal = supplier["s_acctbal"].astype(dtype)[s]
    names = nation["n_name"][n_row[s]]
    order = np.lexsort((part["p_partkey"][p], supplier["s_name"][s], names,
                        -acctbal.astype(np.float64)))
    return [(float(acctbal[i]), str(supplier["s_name"][s[i]]),
             str(names[i]), int(part["p_partkey"][p[i]]),
             str(part["p_mfgr"][p[i]]), str(supplier["s_address"][s[i]]),
             str(supplier["s_phone"][s[i]]),
             str(supplier["s_comment"][s[i]]))
            for i in order[:int(params["limit"])]]


def q17_threshold(ds: Dataset, dtype=np.float64):
    """(each line's part row, found?, the part's ``0.2 * avg(l_quantity)``
    over all of its lines, in ``dtype`` as written: the sum and the count
    first, then the quotient, then the product)."""
    def make():
        li, part = ds.tables["lineitem"], ds.tables["part"]
        p_row, found = _lookup(ds, "part", "p_partkey", li["l_partkey"])
        qty = _money(ds, dtype)[0]
        n = len(part["p_partkey"])
        sums = np.zeros(n, dtype=dtype)
        np.add.at(sums, p_row[found], qty[found])
        count = np.bincount(p_row[found], minlength=n).astype(dtype)
        with np.errstate(invalid="ignore", divide="ignore"):
            return p_row, found, dtype(0.2) * (sums / count)
    return _memo(ds, ("q17 threshold", dtype), make)


def q17(ds: Dataset, params: dict, dtype=np.float64) -> list:
    """Small-Quantity-Order Revenue: the yearly revenue lost without the
    lines of a brand and container whose quantity is below a fifth of
    their part's average."""
    li, part = ds.tables["lineitem"], ds.tables["part"]
    p_row, found, threshold = q17_threshold(ds, dtype)
    p_keep = (part["p_brand"] == params["brand"]) \
        & (part["p_container"] == params["container"])
    qty, price = _money(ds, dtype)[:2]
    keep = found & p_keep[p_row] & (qty < threshold[p_row])
    if not keep.any():
        return [(None,)]
    return [(float(price[keep].sum(dtype=dtype) / dtype(7.0)),)]


REFERENCES = {**tpch.REFERENCES, "q2": q2, "q17": q17}
