"""Reader ``span_before_window``: set-up's share of one of the ``spans``
source's sums, in seconds: its total at the end of the run less its
growth in the window.  Nothing runs after the window (the server is
closed before the metrics are read), so what is left ran before it."""


def read(run, key):
    total = run.sources["spans"].snapshot().get(key)
    if total is None:
        return None
    return (total - run.deltas["spans"].get(key, 0.0)) / 1e3
