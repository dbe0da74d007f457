#!/usr/bin/env python3
"""tools/host_turn.py — a statement's host turn, by span, from one run
of the benchmark.

    python3 tools/host_turn.py <stdout of benchmark/run.py> [...]

Reads the ``window`` line (the growth of every ``<span>.ms`` /
``.self_ms`` / ``.count`` of ``obs.trace.totals()`` over the measured
window) and prints, in ms a statement the clients got an answer to: the
named spans on a statement's path, the containers' self times
(``host_unnamed``: what no child names), and the two identities PERF.md
§5 keeps:

    1 / rate  =  wire.idle + wire.command            (a closed loop, per connection)
    wire.command - drain  =  named spans + host_unnamed + left over

``left over`` is what lies between the spans on the statement's path
across threads (after ``pool.submit``, a worker between its claim and its
span, a span's own begin and end), read by no span.  Prints one JSON line
a file.  Needs nothing but the file: no jax, no program.
"""
from __future__ import annotations

import json
import sys

#: a statement's named host intervals, in the order it passes them;
#: ``drain`` (the host blocked on the device) is not host work and
#: ``batch_wait`` / ``pool.wait`` contain others' work
NAMED = ("wire.parse", "pool.submit", "queue_wait", "round.form", "plan",
         "place", "plan.publish", "exec.build", "pipe.prepare",
         "agg.prepare", "h2d", "dispatch", "exec.rows", "stmt.finish",
         "pool.wake", "wire.write")
#: the spans that contain a statement's host work: their self times are
#: ``host_unnamed_ms_per_query`` (benchmark/layer_metrics)
CONTAINERS = ("wire.command", "solo", "round.collect", "round.replay",
              "round.dispatch", "round.stack", "execute")


def window_line(path: str) -> dict:
    with open(path) as f:
        for line in f:
            if line.startswith("{") and '"phase": "window"' in line:
                return json.loads(line)
    raise SystemExit(f"{path}: no window line")


def host_turn(window: dict) -> dict:
    n = window["answered"]
    spans = window["spans"]

    def per(key: str) -> float:
        return spans.get(key, 0.0) / n

    named = {name: per(name + ".ms") for name in NAMED
             if name + ".ms" in spans}
    unnamed = {name: per(name + ".self_ms") for name in CONTAINERS
               if name + ".self_ms" in spans}
    command, drain = per("wire.command.ms"), per("drain.ms")
    idle = per("wire.idle.ms")
    wall = window["window_s"] * 1e3 / n
    turn = command - drain
    out = {
        "statements": n,
        "wall_ms": wall,
        "wire.idle": idle,
        "wire.command": command,
        "idle_plus_command_over_wall": (idle + command) / wall,
        "drain": drain,
        "host_turn": turn,
        "named": named,
        "named_sum": sum(named.values()),
        "host_unnamed": sum(unnamed.values()),
        "unnamed_by_container": unnamed,
        "round.self": per("round.self_ms"),
    }
    out["left_over"] = turn - out["named_sum"] - out["host_unnamed"] \
        - out["round.self"]
    for name in ("pool.wait", "batch_wait", "gc", "bg", "memprof.window"):
        if name + ".ms" in spans:
            out[name] = per(name + ".ms")
    return out


def main(argv=None) -> int:
    for path in (argv if argv is not None else sys.argv[1:]):
        table = host_turn(window_line(path))
        print(json.dumps({"file": path, **{
            k: ({a: round(b, 4) for a, b in v.items()}
                if isinstance(v, dict) else
                round(v, 4) if isinstance(v, float) else v)
            for k, v in table.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
