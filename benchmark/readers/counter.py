"""Reader ``counter``: the window's growth of one of the program's
counters (``source``: ``kernels`` for ``ops.kernels.stats_delta``,
``batching`` for ``ops.batching.stats_snapshot``) or of jax's (``jax``:
``programs_built``, the programs compiled or loaded from the persistent
cache, by ``jax.monitoring``), whole or per answered
statement, times ``scale``."""


def read(run, source, key, per_statement=False, scale=1.0):
    delta = run.deltas[source]
    if key not in delta:
        return None
    value = float(delta[key])
    if per_statement:
        if not run.answered:
            return None
        value /= len(run.answered)
    return value * scale
