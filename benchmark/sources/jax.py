"""Source ``jax``: ``programs_built``, the programs jax compiled or
loaded from its persistent cache (one ``backend_compile_duration`` event
each): what the program's own ``progcache_misses`` cannot see, a jitted
function's first call."""

EVENT = "/jax/core/compile/backend_compile_duration"
_state = {"built": 0, "listening": False}


def start() -> None:
    if _state["listening"]:  # once a process: jax keeps its listeners
        return
    _state["listening"] = True
    import jax.monitoring

    def on_duration(name, _seconds, **_kw):
        if name == EVENT:
            _state["built"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def snapshot() -> dict:
    return {"programs_built": _state["built"]}
