"""Reader ``span_sum``: the window's growth of several keys of one
source, summed (``host_unnamed_ms_per_query``: the self times of the
spans that contain a statement's host work), whole or per answered
statement, times ``scale``.  Keys the program does not have are left
out of the sum; nothing where it has none of them."""


def read(run, source, keys, per_statement=False, scale=1.0):
    delta = run.deltas[source]
    found = [float(delta[k]) for k in keys if k in delta]
    if not found:
        return None
    value = sum(found)
    if per_statement:
        if not run.answered:
            return None
        value /= len(run.answered)
    return value * scale
