"""Source ``spans``: the program's span totals (``obs.trace.totals()``:
every span that has ended since the process began, by name) flattened to
``<span>.count``, ``<span>.ms`` (durations summed) and ``<span>.self_ms``
(less the child spans that ran on the same thread), and two sums a
reader can take as one key: ``bg.ms`` over the samplers' ticks
(``bg.<sampler>``) and ``program_load.ms`` over jax's own phases
(``jax.trace``, ``jax.lower``, ``jax.backend_compile``; their self
times, because tracing a program traces the functions it calls).  A
program without the table gives nothing, and the metrics that read this
source are then left out."""

JAX_PHASES = ("jax.trace", "jax.lower", "jax.backend_compile")


def snapshot() -> dict:
    from tinysql_tpu.obs import trace
    totals = getattr(trace, "totals", None)
    if totals is None:
        return {}
    out = {}
    bg = load = 0.0
    for name, t in totals().items():
        out[name + ".count"] = t["count"]
        out[name + ".ms"] = t["sum_s"] * 1e3
        out[name + ".self_ms"] = t["self_s"] * 1e3
        if name.startswith("bg."):
            bg += t["sum_s"] * 1e3
        if name in JAX_PHASES:
            load += t["self_s"] * 1e3
    out["bg.ms"] = bg
    out["program_load.ms"] = load
    return out
