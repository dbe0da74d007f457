"""The one general generator: a traffic mix (a data file of templates,
parameter values, connections and an order) becomes statements and, for
each connection, the order in which it sends them.  The same seed gives
the same orders; every seed gives every connection the same set of
statements, in another order, so that the seed never changes the work.
"""
from __future__ import annotations

import itertools
import random


class Statement:
    """One distinct statement text of a mix."""

    def __init__(self, kind: str, sql: str, reference: str, params: dict,
                 reads: dict, gap_limit: float):
        self.kind = kind            # the template's name: "q1", "q6", ...
        self.sql = sql
        self.reference = reference  # name of the dataset's reference
        self.params = params
        self.reads = reads          # {table: [column, ...]} it must read
        self.gap_limit = gap_limit  # of the widest gap of its doubles


def expand(mix: dict) -> list:
    """Every template under every combination of its parameter values, in
    the file's order."""
    out = []
    for t in mix["templates"]:
        names = list(t["parameters"])
        for values in itertools.product(*(t["parameters"][n]
                                          for n in names)):
            params = dict(zip(names, values))
            out.append(Statement(t["kind"], t["sql"].format(**params),
                                 t["reference"], params, t["reads"],
                                 float(t["max_rel_gap_limit"])))
    return out


def gap_limits(statements: list) -> dict:
    return {s.kind: s.gap_limit for s in statements}


def orders(mix: dict, n_statements: int, connections: int,
           seed: int) -> list:
    """For each connection the indices of the statements it sends, in
    turn; it cycles through the list for as long as the window lasts."""
    base = list(range(n_statements))
    if mix["order"] == "cycle":
        return [list(base) for _ in range(connections)]
    if mix["order"] == "rotate":
        # connection i begins at statement i: every kind is in flight at
        # once, as the streams of a throughput test each have an order
        return [base[i % n_statements:] + base[:i % n_statements]
                for i in range(connections)]
    if mix["order"] == "shuffle":
        rng = random.Random(seed)
        out = []
        for _ in range(connections):
            order = list(base)
            rng.shuffle(order)
            out.append(order)
        return out
    raise ValueError(f"unknown order {mix['order']!r}")
