"""Reader ``counter_share``: 100 * the window's growth of one of a
source's counters over the growth of another (``of``).  Nothing where
the program has no such counter or the other did not grow."""


def read(run, source, key, of):
    delta = run.deltas[source]
    if key not in delta or not delta.get(of):
        return None
    return 100.0 * float(delta[key]) / float(delta[of])
