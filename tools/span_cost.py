#!/usr/bin/env python3
"""tools/span_cost.py — what one span costs, with the profiler's session
closed and open.

    python3 tools/span_cost.py [--spans 100000]

Times a loop of process spans (begin, end: the contextvar stack, the
totals table, the bounded ring, the profiler's flag test) three ways:
before jax is loaded (no annotation class bound), with jax loaded and no
session open (one ``is_enabled`` test a span), and inside a
``jax.profiler`` session (a ``TraceAnnotation`` a span).  Prints one
JSON line; PERF.md §6 keeps the chip host's reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def loop_ns(n: int) -> float:
    from tinysql_tpu.obs.context import process_span
    t0 = time.perf_counter()
    for _ in range(n):
        with process_span("span_cost.probe", cat="probe"):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spans", type=int, default=100_000)
    n = ap.parse_args(argv).spans
    out = {"spans": n, "ns_per_span_before_jax": loop_ns(n)}
    from tinysql_tpu.ops import kernels
    jax = kernels.jax()
    out["ns_per_span_no_session"] = loop_ns(n)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["ns_per_span_in_session"] = loop_ns(n)
        finally:
            jax.profiler.stop_trace()
    out["device"] = jax.devices()[0].device_kind
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
