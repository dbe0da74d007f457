"""The control (the reference in float32) has to come out as not correct,
and the reference itself, written as the wire writes it, as correct."""
import numpy as np
import pytest

import compare
import control
import run as harness
import traffic

tpch = harness.load_module("datasets", "tpch")

CELLS = ("tpch_sf1.power_stream", "tpch_sf1.q6_dash_16c")


@pytest.fixture(scope="module", params=(11, 2_147_483_659, 3_000_000_019))
def dataset(request):
    return tpch.generate(0.05, request.param)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, dataset):
    verdict = control.control_verdict(harness.Cell(name), dataset, tpch)
    assert verdict["correct"] is False
    compared = verdict["compared"]
    # the control fails on the gaps alone, each of them
    assert compared["unanswered"]["value"] == 0
    assert compared["wrong_answers"]["value"] == 0
    gaps = [c for name, c in compared.items()
            if name.startswith("max_rel_gap.")]
    assert gaps and all(g["value"] > g["limit"] for g in gaps)


@pytest.mark.parametrize("name", CELLS)
def test_reference_against_itself_is_correct(name, dataset):
    statements = traffic.expand(harness.Cell(name).mix)

    def ref(i):
        s = statements[i]
        return tpch.REFERENCES[s.reference](dataset, s.params)
    answers = [(i, control.as_wire(ref(i))) for i in range(len(statements))]
    verdict = compare.compare(answers, ref, lambda i: statements[i].kind,
                              traffic.gap_limits(statements))
    assert verdict["correct"] is True
    assert all(c["value"] == 0 for c in verdict["compared"].values())


def test_an_empty_window_is_not_correct():
    assert compare.compare([], lambda i: [], lambda i: "q", {})["correct"] \
        is False


def test_exact_parts_have_to_be_equal():
    want = [("A", "F", 1.5, 3)]
    ok, gap = compare.answer_gap([["A", "F", "1.5", "3"]], want)
    assert ok and gap == 0.0
    assert not compare.answer_gap([["A", "F", "1.5", "4"]], want)[0]
    assert not compare.answer_gap([["A", "O", "1.5", "3"]], want)[0]
    assert not compare.answer_gap([], want)[0]
    assert not compare.answer_gap([["A", "F", None, "3"]], want)[0]
    assert compare.answer_gap([["A", "F", "1.50000015", "3"]],
                              want)[1] == pytest.approx(1e-7, rel=1e-3)


@pytest.mark.parametrize("text", ["nan", "inf", "-inf", "NaN"])
def test_a_sum_that_is_not_finite_is_not_correct(text):
    ok, gap = compare.answer_gap([["A", "F", text, "3"]],
                                 [("A", "F", 1.5, 3)])
    assert not ok and gap == float("inf")
    verdict = compare.compare([(0, [[text]])], lambda i: [(1.5,)],
                              lambda i: "q6", {"q6": 1e-10})
    assert verdict["correct"] is False
    assert verdict["compared"]["wrong_answers"]["value"] == 1


#: columns of the program's cut that the specification writes otherwise
REWRITTEN = {("customer", "c_address"), ("customer", "c_phone"),
             ("customer", "c_comment")}


def test_the_programs_columns_are_drawn_as_the_program_draws_them():
    """What ``tinysql_tpu/bench/tpch.py`` draws, the copy draws too, seed
    for seed: every column the three queries read is the program's.  The
    specification's other columns and tables come from a second stream.
    When the program's generator changes (ROADMAP Reach 1) this test is
    the one to delete, not the copy."""
    from tinysql_tpu.bench import tpch as theirs
    mine = tpch.generate(0.01, 2_147_483_659)
    for table, cols in theirs.generate(0.01, 2_147_483_659).items():
        for name, values in cols.items():
            if (table, name) not in REWRITTEN:
                assert np.array_equal(values, mine.tables[table][name]), name


def test_every_table_has_the_specifications_columns():
    """TPC-H 1.4.1: 8 tables, 61 columns (and ``l_id``), each text at its
    declared width and no longer."""
    counts = {t: len(ddl.splitlines()) - 1 for t, ddl in tpch.SCHEMAS.items()}
    assert counts == {"region": 3, "nation": 4, "supplier": 7, "part": 9,
                      "partsupp": 5, "customer": 8, "orders": 9,
                      "lineitem": 17}
    data = tpch.generate(0.01, 7).tables
    for table, ddl in tpch.SCHEMAS.items():
        names = [line.split()[0] for line in ddl.splitlines()[1:]]
        assert names == list(data[table])
        rows = {len(v) for v in data[table].values()}
        assert len(rows) == 1
        for name in names:
            values = data[table][name]
            if values.dtype.kind == "U":
                assert values.dtype.itemsize // 4 <= tpch.column_bytes(
                    table, name), name
    assert len(data["partsupp"]["ps_partkey"]) == 4 * len(
        data["part"]["p_partkey"])
