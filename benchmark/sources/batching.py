"""Source ``batching``: the coalescer's counts
(``ops.batching.stats_snapshot``): ``stacked_rounds``,
``stacked_statements``, ``parks``, ``fallbacks`` and the rest."""


def snapshot() -> dict:
    from tinysql_tpu.ops import batching
    return batching.stats_snapshot()
