"""Source ``kernels``: the program's dispatch and transfer counters
(``ops.kernels.stats_snapshot``): ``dispatches``, ``host_dispatches``,
``h2d_bytes``, ``d2h_bytes``, ``progcache_misses`` and the rest."""


def snapshot() -> dict:
    from tinysql_tpu.ops import kernels
    return kernels.stats_snapshot()
