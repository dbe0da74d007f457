#!/usr/bin/env python3
"""benchmark/gapspans.py — the device's idle gaps, named by what the host
was doing in them.

    python3 benchmark/gapspans.py <file.xplane.pb[.gz]> [--top 10]

A stand-alone reduction of a kept trace (``BENCH_KEEP_TRACE=<dir>`` with
``run.py --trace 1``).  The program enters every live span as a
``tinysql/<name>`` annotation of the profiler, so the trace's
``/host:CPU`` plane holds them on the clock of the device plane's
``XLA Ops``.  An idle gap is the time between two merged busy intervals
of a device (``tracered.device_events``, ``tracered.union_intervals``:
the same gaps as ``breakdown.idle_gaps``).  Every nanosecond of a gap is
put down to ONE span:

- on each host thread, the innermost span live at that moment (the one
  that began last);
- among the threads, a span that works before one that only waits for
  another thread (``WAITING``: a connection thread blocked in
  ``pool.wait`` says nothing about why the chip is idle while a pool
  worker plans), and among equals the one that began last;
- ``no span`` where no thread has one (the client's turn, the socket,
  a thread the program does not instrument).

So the named seconds and ``no span`` add up to the gaps' total, to the
nanosecond.  Also printed: the device's busy time by program
(``XLA Modules``, the name less its fingerprint), which the program's
families now name.  Wiring this into ``run.py``'s ``breakdown`` is a
later ``benchmark`` change.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import os
import re
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import tracered  # noqa: E402

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "tinysql/"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span"
#: spans in which a thread only waits for another thread of the program
WAITING = frozenset({"pool.wait"})


def _ns(x) -> int:
    return int(round(float(x)))


def host_spans(profile) -> list:
    """One list for each host thread that recorded a program span:
    [(start_ns, end_ns, name), ...] sorted by start."""
    threads = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = sorted(
                (_ns(e.start_ns), _ns(e.start_ns) + _ns(e.duration_ns),
                 e.name[len(SPAN_PREFIX):])
                for e in line.events if e.name.startswith(SPAN_PREFIX))
            if spans:
                threads.append(spans)
    return threads


def device_gaps(by_device: dict) -> list:
    """[(start_ns, end_ns, "after <operation>"), ...] over every device,
    as ``tracered.Reduction`` finds them."""
    gaps = []
    for events in by_device.values():
        merged = tracered.union_intervals(events)
        for (_s, end, name), nxt in zip(merged, merged[1:]):
            if _ns(nxt[0]) > _ns(end):
                gaps.append((_ns(end), _ns(nxt[0]), f"after {name}"))
    return gaps


def flatten(spans: list) -> list:
    """One thread's spans (they nest) as pieces that do not overlap,
    sorted: [(start, end, name, began)], each under the innermost span
    live in it, ``began`` being when that span started."""
    pieces, stack = [], []
    pos = 0

    def add(upto: int) -> None:
        if upto > pos:
            pieces.append((pos, upto, stack[-1][2], stack[-1][0]))

    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= s[0]:
            add(stack[-1][1])
            pos = max(pos, stack.pop()[1])
        if stack:
            add(s[0])
        pos = s[0]
        stack.append(s)
    while stack:
        add(stack[-1][1])
        pos = max(pos, stack.pop()[1])
    return pieces


def _innermost(pieces: list, starts: list, t0: int, t1: int) -> list:
    """The part of one thread's ``flatten``ed pieces inside [t0, t1)."""
    out = []
    i = max(bisect.bisect_right(starts, t0) - 1, 0)
    while i < len(pieces) and pieces[i][0] < t1:
        a, b, name, began = pieces[i]
        if b > t0:
            out.append((max(a, t0), min(b, t1), name, began))
        i += 1
    return out


def by_thread(threads: list) -> list:
    """``host_spans`` made ready for ``attribute``: each thread's pieces
    and their starts."""
    flat = [flatten(spans) for spans in threads]
    return [(pieces, [p[0] for p in pieces]) for pieces in flat]


def attribute(gap: tuple, threads: list) -> dict:
    """{span name or ``no span``: nanoseconds} of one gap over
    ``by_thread``'s threads: they add up to the gap's length."""
    t0, t1 = gap[0], gap[1]
    pieces = [p for flat, starts in threads
              for p in _innermost(flat, starts, t0, t1)]
    cuts = sorted({t0, t1, *(t for p in pieces for t in p[:2])})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [p for p in pieces if p[0] <= a and p[1] >= b]
        if over:
            # working before waiting, then the latest to begin
            name = max(over, key=lambda p: (p[2] not in WAITING, p[3]))[2]
        else:
            name = NO_SPAN
        out[name] = out.get(name, 0) + (b - a)
    return out


def module_seconds(profile) -> dict:
    """{program name less its fingerprint: busy seconds} over the
    devices' ``XLA Modules`` lines."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(tracered.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                name = re.sub(r"\(\d+\)$", "", e.name)
                out[name] = out.get(name, 0.0) + float(e.duration_ns) / 1e9
    return out


class GapSpans:
    """The whole reduction of one profile."""

    def __init__(self, profile, top: int = 10):
        self.threads = by_thread(host_spans(profile))
        self.gaps = sorted(device_gaps(tracered.device_events(profile)),
                           key=lambda g: g[0] - g[1])
        self.total_ns = sum(g[1] - g[0] for g in self.gaps)
        self.by_span = {}
        self.longest = []  # [(gap, {name: ns})] of the ``top`` longest
        top_ns = top_named_ns = 0
        for i, gap in enumerate(self.gaps):
            parts = attribute(gap, self.threads)
            for name, ns in parts.items():
                self.by_span[name] = self.by_span.get(name, 0) + ns
            if i < top:
                self.longest.append((gap, parts))
                top_ns += gap[1] - gap[0]
                top_named_ns += gap[1] - gap[0] - parts.get(NO_SPAN, 0)
        #: share of the ``top`` longest gaps' time that has a name
        self.top_named_share = top_named_ns / top_ns if top_ns else 0.0
        self.modules = module_seconds(profile)

    def lines(self) -> list:
        out = [f"idle gaps: {len(self.gaps)}, {self.total_ns / 1e9:.6f} s; "
               f"host threads with program spans: {len(self.threads)}",
               "", "| span | idle s | share |", "|---|---|---|"]
        for name, ns in sorted(self.by_span.items(), key=lambda kv: -kv[1]):
            out.append(f"| `{name}` | {ns / 1e9:.4f} | "
                       f"{100 * ns / max(self.total_ns, 1):.1f} % |")
        out += ["", f"the {len(self.longest)} longest gaps "
                f"({100 * self.top_named_share:.1f} % of their time named):",
                "", "| at s | gap s | follows | spans |", "|---|---|---|---|"]
        for gap, parts in self.longest:
            named = ", ".join(
                f"`{n}` {ns / 1e9:.3f}" for n, ns in
                sorted(parts.items(), key=lambda kv: -kv[1])[:4])
            out.append(f"| {gap[0] / 1e9:.3f} | "
                       f"{(gap[1] - gap[0]) / 1e9:.4f} | "
                       f"{gap[2][6:]} | {named} |")
        if self.modules:
            out += ["", "| program | device s |", "|---|---|"]
            for name, s in sorted(self.modules.items(),
                                  key=lambda kv: -kv[1])[:12]:
                out.append(f"| `{name}` | {s:.4f} |")
        return out


def load(path: str):
    """The ProfileData of a ``.xplane.pb``, gzipped or not."""
    from jax.profiler import ProfileData
    if not path.endswith(".gz"):
        return ProfileData.from_file(path)
    with gzip.open(path) as f, tempfile.NamedTemporaryFile(
            suffix=".xplane.pb") as plain:
        plain.write(f.read())
        plain.flush()
        return ProfileData.from_file(plain.name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print("\n".join(GapSpans(load(args.xplane), args.top).lines()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
