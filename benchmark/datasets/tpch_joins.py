"""TPC-H's join queries Q5, Q10 and Q18 beside Q1, Q3 and Q6: the dataset of
the configuration ``tpch_sf1_joins``.

Schema, generator, byte counts and the three references of ``tpch.py`` are
imported as they are; what this module adds is the plain reference of the
three join statements.  Each answers its template from the generated host
arrays with numpy and nothing else: a join is a lookup of the foreign key
in a key -> row table made from the primary key's column, a GROUP BY a sum
into one slot a group (``np.add.at``, in ``dtype``), an ORDER BY a stable
sort.  Nothing of the program is imported and nothing it made is read.
Every double of an answer passes through ``dtype``: the sums are made in
it and the double columns an answer carries (``c_acctbal``,
``o_totalprice``) are read in it, so the float32 control rounds both.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_datasets_tpch_for_joins",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)

DATABASE, SCHEMAS, generate = tpch.DATABASE, tpch.SCHEMAS, tpch.generate
Dataset, scan_bytes, column_bytes = (tpch.Dataset, tpch.scan_bytes,
                                     tpch.column_bytes)
REFERENCE_DTYPE, CONTROL_DTYPE = tpch.REFERENCE_DTYPE, tpch.CONTROL_DTYPE
_day, _memo, _money = tpch._day, tpch._memo, tpch._money


def _rows_by_key(ds: Dataset, table: str, key: str) -> np.ndarray:
    """key value -> row of ``table`` (-1: no such key), for a primary key."""
    def make():
        keys = ds.tables[table][key]
        rows = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
        rows[keys] = np.arange(len(keys))
        return rows
    return _memo(ds, ("rows by key", table), make)


def _lookup(ds: Dataset, table: str, key: str, values: np.ndarray):
    """(row of ``table`` whose ``key`` is each of ``values``, found?)."""
    rows = _rows_by_key(ds, table, key)
    inside = (values >= 0) & (values < len(rows))
    row = np.where(inside, rows[np.where(inside, values, 0)], -1)
    return np.maximum(row, 0), row >= 0


def _order_of_lines(ds: Dataset):
    """For each ``lineitem`` row its order's row, and whether it has one."""
    return _memo(ds, ("order of lines",), lambda: _lookup(
        ds, "orders", "o_orderkey", ds.tables["lineitem"]["l_orderkey"]))


def _orders_between(ds: Dataset, date: dict) -> np.ndarray:
    """Which orders have ``date[lo] <= o_orderdate < date[hi]``."""
    days = ds.days["o_orderdate"]
    return (days >= _day(date["lo"])) & (days < _day(date["hi"]))


def _group_sums(slots: np.ndarray, values: np.ndarray, n: int, dtype):
    """(sum of ``values`` in ``dtype``, number of rows) by slot."""
    sums = np.zeros(n, dtype=dtype)
    np.add.at(sums, slots, values)
    return sums, np.bincount(slots, minlength=n)


def q5(ds: Dataset, params: dict, dtype=np.float64) -> list:
    t = ds.tables
    li, orders, customer = t["lineitem"], t["orders"], t["customer"]
    supplier, nation, region = t["supplier"], t["nation"], t["region"]
    o_keep = _orders_between(ds, params["date"])
    regions = region["r_regionkey"][region["r_name"] == params["region"]]
    n_keep = np.isin(nation["n_regionkey"], regions)
    o_row, keep = _order_of_lines(ds)
    keep = keep & o_keep[o_row]
    c_row, found = _lookup(ds, "customer", "c_custkey",
                           orders["o_custkey"][o_row])
    keep &= found
    s_row, found = _lookup(ds, "supplier", "s_suppkey", li["l_suppkey"])
    keep &= found
    s_nation = supplier["s_nationkey"][s_row]
    keep &= customer["c_nationkey"][c_row] == s_nation
    n_row, found = _lookup(ds, "nation", "n_nationkey", s_nation)
    keep &= found & n_keep[n_row]
    _qty, price, disc, _tax = _money(ds, dtype)
    value = price[keep] * (dtype(1) - disc[keep])
    # GROUP BY n_name: a group a name, in the names' order
    names, name_of = np.unique(nation["n_name"], return_inverse=True)
    revenue, count = _group_sums(name_of[n_row[keep]], value, len(names),
                                 dtype)
    groups = np.flatnonzero(count)
    order = groups[np.argsort(-revenue[groups].astype(np.float64),
                              kind="stable")]
    return [(str(names[g]), float(revenue[g])) for g in order]


def q10(ds: Dataset, params: dict, dtype=np.float64) -> list:
    t = ds.tables
    li, orders, customer, nation = (t["lineitem"], t["orders"],
                                    t["customer"], t["nation"])
    o_keep = _orders_between(ds, params["date"])
    o_row, keep = _order_of_lines(ds)
    keep = keep & o_keep[o_row] & (li["l_returnflag"] == "R")
    c_row, found = _lookup(ds, "customer", "c_custkey",
                           orders["o_custkey"][o_row])
    keep &= found
    n_row, found = _lookup(ds, "nation", "n_nationkey",
                           customer["c_nationkey"])
    keep &= found[c_row]
    _qty, price, disc, _tax = _money(ds, dtype)
    value = price[keep] * (dtype(1) - disc[keep])
    # the seven GROUP BY columns are the customer's and its nation's name:
    # a group a customer, in c_custkey's order
    by_key = np.argsort(customer["c_custkey"], kind="stable")
    slot_of = np.empty(len(by_key), dtype=np.int64)
    slot_of[by_key] = np.arange(len(by_key))
    revenue, count = _group_sums(slot_of[c_row[keep]], value, len(by_key),
                                 dtype)
    groups = np.flatnonzero(count)
    head = groups[np.argsort(-revenue[groups].astype(np.float64),
                             kind="stable")][:int(params["limit"])]
    acctbal = customer["c_acctbal"].astype(dtype)
    out = []
    for g in head:
        c = by_key[g]
        out.append((int(customer["c_custkey"][c]),
                    str(customer["c_name"][c]), float(revenue[g]),
                    float(acctbal[c]), str(nation["n_name"][n_row[c]]),
                    str(customer["c_address"][c]),
                    str(customer["c_phone"][c]),
                    str(customer["c_comment"][c])))
    return out


def q18(ds: Dataset, params: dict, dtype=np.float64) -> list:
    t = ds.tables
    orders, customer = t["orders"], t["customer"]
    o_row, keep = _order_of_lines(ds)
    qty = _money(ds, dtype)[0]
    # both the sub-query's sum and the statement's own: every line of an
    # order, by order
    sum_qty, _count = _group_sums(o_row[keep], qty[keep],
                                  len(orders["o_orderkey"]), dtype)
    big = np.flatnonzero(sum_qty > dtype(float(params["quantity"])))
    c_row, found = _lookup(ds, "customer", "c_custkey",
                           orders["o_custkey"][big])
    big, c_row = big[found], c_row[found]
    # rows in o_orderkey's order before the sort, so that ties fall as a
    # stable sort of the table leaves them
    by_key = np.argsort(orders["o_orderkey"][big], kind="stable")
    big, c_row = big[by_key], c_row[by_key]
    totalprice = orders["o_totalprice"].astype(dtype)
    order = np.lexsort((orders["o_orderdate"][big],
                        -totalprice[big].astype(np.float64)))
    out = []
    for i in order[:int(params["limit"])]:
        o, c = big[i], c_row[i]
        out.append((str(customer["c_name"][c]),
                    int(customer["c_custkey"][c]),
                    int(orders["o_orderkey"][o]),
                    str(orders["o_orderdate"][o]), float(totalprice[o]),
                    float(sum_qty[o])))
    return out


REFERENCES = {**tpch.REFERENCES, "q5": q5, "q10": q10, "q18": q18}
