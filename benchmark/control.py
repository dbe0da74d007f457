#!/usr/bin/env python3
"""The control of the comparison: the plain reference computed in the
precision below the one the configuration states (float32 for the
schema's double), put in the program's place.  Its answers, written as
the wire writes them, go through the same comparison as a run's, and
``correct`` has to come out false.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--sf 0.05]

prints one line for each seed with the numbers compared.  It needs no
chip and starts no server; the benchmark's own runs never call it.  The
test ``tests/test_control.py`` keeps it at a size a test run can hold.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402


def as_wire(rows: list) -> list:
    """Rows as the server's text protocol writes them: ``repr`` of a
    double, ``str`` of the rest, None for NULL."""
    return [[None if v is None else repr(v) if isinstance(v, float)
             else str(v) for v in row] for row in rows]


def control_verdict(cell, dataset, module) -> dict:
    """Every distinct statement of the cell's mix answered once by the
    control, compared as a run's answers are."""
    statements = traffic.expand(cell.mix)

    def answer(idx, dtype):
        s = statements[idx]
        return module.REFERENCES[s.reference](dataset, s.params, dtype)

    answers = [(i, as_wire(answer(i, module.CONTROL_DTYPE)))
               for i in range(len(statements))]
    return compare.compare(
        answers, lambda i: answer(i, module.REFERENCE_DTYPE),
        lambda i: statements[i].kind, traffic.gap_limits(statements))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor, the configuration's by default")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    module = harness.load_module("datasets", cell.config["dataset"])
    sf = cell.config["scale_factor"] if args.sf is None else args.sf
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        verdict = control_verdict(cell, module.generate(sf, seed), module)
        print(json.dumps({"control_of": args.workload, "seed": seed,
                          "sf": sf, "seconds": round(time.time() - t0, 2),
                          **verdict}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
