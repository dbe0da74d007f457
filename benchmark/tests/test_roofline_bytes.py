"""The roofline's byte count against a hand count."""
import json
import os

from conftest import BENCH
import run as harness

tpch = harness.load_module("datasets", "tpch")


def _reads(mix, kind):
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        templates = json.load(f)["templates"]
    return next(t["reads"] for t in templates if t["kind"] == kind)


def test_q6_by_hand():
    # l_shipdate varchar(10) + l_discount, l_quantity, l_extendedprice
    # double: 10 + 8 + 8 + 8 = 34 bytes a row of lineitem
    rows = {"lineitem": 6_001_215}
    for mix in ("power_stream", "q6_dash_16c"):
        assert tpch.scan_bytes(_reads(mix, "q6"), rows) == 34 * 6_001_215


def test_q1_and_q3_by_hand():
    rows = {"lineitem": 1000, "orders": 100, "customer": 10}
    # Q1: two char(1), four doubles, one varchar(10) = 44 bytes a row
    assert tpch.scan_bytes(_reads("power_stream", "q1"), rows) == 44 * 1000
    # Q3: customer 8 + 10; orders 8 + 8 + 10 + 4; lineitem 8 + 8 + 8 + 10
    assert tpch.scan_bytes(_reads("power_stream", "q3"), rows) == \
        18 * 10 + 30 * 100 + 34 * 1000


def test_column_widths():
    assert tpch.column_bytes("lineitem", "l_shipdate") == 10
    assert tpch.column_bytes("orders", "o_orderkey") == 8
    assert tpch.column_bytes("customer", "c_comment") == 117
    assert tpch.column_bytes("lineitem", "l_linenumber") == 4
    assert tpch.column_bytes("lineitem", "l_shipinstruct") == 25
