"""TPC-H's join queries Q5, Q10 and Q18 over tables made in row blocks: the
dataset of the configuration ``tpch_sf10_joins_mesh4``.

Glue, and nothing else.  The tables, their maker, the ``Dataset``, the
schema and the roofline's byte counts are ``tpch_blocks.py``'s (SF=10 in
20 s on the four-chip host's cores where ``tpch.py``'s one pass takes 75);
the plain reference of the three statements, its precision and its
control's are ``tpch_joins.py``'s (numpy, float64, key -> row lookups,
``np.add.at``, stable sorts).  Both are imported as they are, as
``tpch_joins.py`` imports ``tpch.py``; the reference reads a ``Dataset`` by
its ``tables`` and ``days`` alone, so whichever module made it serves.
Nothing of the program is imported by either.

``generate`` first asks the program whether it can run these statements
over a mesh as one fused program each (it then counts ``pipe_mesh_views``
and ``agg_key_mesh`` in ``ops.kernels.STATS``, PR 39) and fails at once
without them.  A program before PR 39 has the three counters that
``sources/pipes.py`` asks for, so it would start: under
``tidb_mesh_parallel`` it sends Q5's chain to the per-operator tier and
sorts Q10's GROUP BY on seven 64-bit keys inside its fused program, at
SF=10 over lanes of 2^24 and 2^26 rows, which the chip's compiler takes
tens of minutes over.  It is stopped before any data is made.  That is
the only thing read of the program, and no data of it is taken.
"""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_datasets_{name}_for_joins_blocks",
        os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


blocks = _beside("tpch_blocks")
joins = _beside("tpch_joins")

DATABASE, SCHEMAS, Dataset = blocks.DATABASE, blocks.SCHEMAS, blocks.Dataset
scan_bytes, column_bytes = blocks.scan_bytes, blocks.column_bytes
REFERENCES = joins.REFERENCES
REFERENCE_DTYPE, CONTROL_DTYPE = joins.REFERENCE_DTYPE, joins.CONTROL_DTYPE

#: what a program that runs the three statements over a mesh counts
MESH_COUNTERS = ("pipe_mesh_views", "agg_key_mesh")


def _require_program() -> None:
    from tinysql_tpu.ops import kernels
    missing = [k for k in MESH_COUNTERS if k not in kernels.STATS]
    if missing:
        raise RuntimeError(
            f"this program counts no {missing}: under tidb_mesh_parallel "
            f"its join chains leave the fused pipeline and its GROUP BY "
            f"above a chain sorts 64-bit lanes, which at this scale "
            f"compiles for tens of minutes on the chip; the cell's "
            f"statements cannot be run on it")


def generate(sf: float, seed: int) -> Dataset:
    _require_program()
    return blocks.generate(sf, seed)
