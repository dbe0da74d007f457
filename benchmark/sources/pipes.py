"""Source ``pipes``: what the fused pipeline traced into its programs
(``ops.kernels.STATS``, counted once a fused dispatch): ``pipe_joins``
(join nodes), ``pipe_view_builds`` (those whose build side is a view and
not a base table), ``agg_key_cut`` (GROUP BYs formed on the one key that
determines the other GROUP BY columns).

A cell whose metrics read these sends join chains that have to run as one
fused program each.  A program without the counters has no such path: its
per-operator tier would take TPC-H Q5 through a many-to-many join of 60 M
rows and Q10 and Q18 through 64-bit sorts that compile for minutes.
``start`` refuses such a program before any data is made, so that it fails
in seconds and is not left to hang."""

KEYS = ("pipe_joins", "pipe_view_builds", "agg_key_cut")


def start() -> None:
    from tinysql_tpu.ops import kernels
    missing = [k for k in KEYS if k not in kernels.STATS]
    if missing:
        raise RuntimeError(
            f"this program counts no {missing}: it has no fused join "
            f"chains, and the cell's statements cannot be run on it")


def snapshot() -> dict:
    from tinysql_tpu.ops import kernels
    return {k: kernels.STATS[k] for k in KEYS if k in kernels.STATS}
