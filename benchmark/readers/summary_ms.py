"""Reader ``summary_ms``: the window's growth of the program's
``statements_summary`` sums for ``phases`` (of parse, plan, exec, queue,
batch, total), in ms per statement the summary counted in the window."""


def read(run, phases):
    summary = run.deltas["summary"]
    count = summary.get("exec_count", 0)
    if not count:
        return None
    return sum(summary.get(p, 0.0) for p in phases) / count
