"""The comparison that decides ``correct``: every answer the clients got
in the window against the plain reference's answer to the same statement.

Numbers compared, each with a limit of its own:

- ``unanswered``: statements that came back as an error or not at all
  (limit 0);
- ``wrong_answers``: answers that differ from the reference's in what has
  to be equal: the number of rows, their order, keys, counts, strings,
  NULLs (limit 0);
- ``max_rel_gap.<kind>``, for each kind of statement in the window: over
  every double of every answer of that kind, the widest
  |answer - reference| / |reference|.  Its limit stands beside the
  statement's template in the traffic mix (``max_rel_gap_limit``); PERF.md
  section 2 gives the readings it was set from.
"""
from __future__ import annotations

import math


def answer_gap(rows, want) -> tuple:
    """(exact parts equal?, widest relative gap of the doubles) of one
    answer as the wire gave it (strings, None for NULL) against the
    reference's rows (str, int, float or None)."""
    if len(rows) != len(want):
        return False, 0.0
    gap = 0.0
    for got_row, want_row in zip(rows, want):
        if len(got_row) != len(want_row):
            return False, gap
        for got, ref in zip(got_row, want_row):
            if ref is None or got is None:
                if got is not ref:
                    return False, gap
            elif isinstance(ref, float):
                try:
                    value = float(got)
                except ValueError:
                    return False, gap
                if not math.isfinite(value):  # max() would drop a NaN
                    return False, math.inf
                gap = max(gap, abs(value - ref) / max(abs(ref), 1e-300))
            elif got != str(ref):
                return False, gap
    return True, gap


def compare(answers, reference_of, kind_of, gap_limits: dict) -> dict:
    """``answers``: (statement index, rows or None) of every statement of
    the window; ``reference_of(index)``: the reference's rows;
    ``kind_of(index)``: the statement's kind; ``gap_limits``: {kind: limit
    of its widest gap}.  Returns the verdict ``correct`` and ``compared``:
    {number: {"value", "limit"}}."""
    unanswered = wrong = 0
    by_kind = {}
    for idx, rows in answers:
        if rows is None:
            unanswered += 1
            continue
        equal, gap = answer_gap(rows, reference_of(idx))
        wrong += not equal
        kind = kind_of(idx)
        by_kind[kind] = max(by_kind.get(kind, 0.0), gap)
    compared = {"unanswered": {"value": unanswered, "limit": 0},
                "wrong_answers": {"value": wrong, "limit": 0}}
    for kind in sorted(by_kind):
        compared[f"max_rel_gap.{kind}"] = {"value": by_kind[kind],
                                           "limit": gap_limits[kind]}
    correct = bool(answers) and all(c["value"] <= c["limit"]
                                    for c in compared.values())
    return {"correct": correct, "compared": compared}
