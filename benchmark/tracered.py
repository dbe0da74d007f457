"""Reduction of a profiler trace (``.xplane.pb``) to what the benchmark
reports: the union of the intervals in which an operation ran on the
device, the operations by total time, and the idle gaps.  It needs no
kernel names: whatever the operations are called, busy is busy.

The device is the plane(s) jax names ``/device:TPU:<n>``; on each, the
line ``XLA Ops`` holds one event for each operation the chip ran (the
lines ``XLA Modules`` and ``Steps`` hold whole programs, gaps between
their operations included, and are not read).  A trace with no such
plane, as the CPU's, reduces to nothing.
"""
from __future__ import annotations

import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``.  The profiler names
    the file after the host, and a host with no name gives a dotfile, which
    a glob's ``*`` would skip."""
    found = sorted(os.path.join(where, f)
                   for where, _dirs, files in os.walk(trace_dir)
                   for f in files if f.endswith(".xplane.pb"))
    return found[-1] if found else None


_HLO = re.compile(r"^(%[\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(hlo: str) -> str:
    """``%fusion.69 f32[8388608]`` of an event named by its whole HLO
    line: the instruction and the type of its (first) result."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    return m.group(1) if m.group(2) is None else f"{m.group(1)} {m.group(2)}"


def device_events(profile) -> dict:
    """{device plane name: [(start_ns, duration_ns, name), ...]} of the
    operations line, sorted by start."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = sorted(
                    (float(e.start_ns), float(e.duration_ns),
                     short_name(e.name)) for e in line.events)
    return out


def union_intervals(events: list) -> list:
    """Merged [start, end, name of the event that ends it] intervals of
    (start, duration, name) events that are sorted by start."""
    merged = []
    for start, duration, name in events:
        end = start + duration
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1:] = [end, name]
        else:
            merged.append([start, end, name])
    return merged


class Reduction:
    """Of one traced window of ``window_s`` seconds on the host's clock."""

    def __init__(self, by_device: dict, window_s: float, top: int = 10):
        self.window_s = window_s
        busy = []
        ops, gaps = {}, {}
        for events in by_device.values():
            merged = union_intervals(events)
            busy.append(sum(end - start for start, end, _n in merged) / 1e9)
            for _start, duration, name in events:
                ops[name] = ops.get(name, 0.0) + duration / 1e9
            # an idle gap is named by the operation that ended before it:
            # the trace holds no host span to say what the host was doing
            for (_s, end, name), nxt in zip(merged, merged[1:]):
                key = f"after {name}"
                gaps[key] = gaps.get(key, 0.0) + (nxt[0] - end) / 1e9
        #: seconds in which an operation ran, averaged over the devices
        self.busy_s = sum(busy) / len(busy) if busy else 0.0
        self.device_ops = _top(ops, top)
        self.idle_gaps = _top(gaps, top)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _top(totals: dict, n: int) -> list:
    return [[name, seconds] for name, seconds in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce_file(path: str, window_s: float):
    """The Reduction of one ``.xplane.pb``, or None where it holds no
    device operation."""
    from jax.profiler import ProfileData
    by_device = device_events(ProfileData.from_file(path))
    if not any(by_device.values()):
        return None
    return Reduction(by_device, window_s)
