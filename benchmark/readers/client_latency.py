"""Reader ``client_latency``: a statistic of the client-side latencies (ms)
of the window's answered statements, of one kind or of all.
``stat``: ``median`` or ``p95``."""
import statistics


def read(run, stat, kind=None):
    ms = [(r[3] - r[2]) * 1e3 for r in run.answered
          if kind is None or run.statements[r[1]].kind == kind]
    if not ms:
        return None
    if stat == "median":
        return statistics.median(ms)
    if stat == "p95":
        ms.sort()
        return ms[min(len(ms) - 1, int(0.95 * len(ms)))]
    raise ValueError(f"unknown stat {stat!r}")
