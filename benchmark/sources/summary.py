"""Source ``summary``: ``statements_summary`` summed over every digest:
``exec_count`` and the host-clock sums in ms of each phase (``parse``,
``plan``, ``exec``, ``queue``, ``batch``, ``total``)."""

PHASES = ("parse", "plan", "exec", "queue", "batch", "total")


def snapshot() -> dict:
    from tinysql_tpu.obs import stmtsummary
    out = {"exec_count": 0, **{p: 0.0 for p in PHASES}}
    for rec in stmtsummary.snapshot():
        out["exec_count"] += rec["exec_count"]
        for p in PHASES:
            out[p] += rec["sum_ms"].get(p, 0.0)
    return out
