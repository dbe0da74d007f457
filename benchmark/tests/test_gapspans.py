"""The idle gaps named by host span: by hand on a three-interval toy, and
on a trace recorded on the chip (PR 27, call 1: the first 8 s of a
``tpch_sf1.q6_dash_16c`` window on one v5e, with the program's spans on
the host plane)."""
import os

import gapspans
import tracered

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "q6_dash_16c_8s_spans.xplane.pb.gz")


def test_flatten_one_threads_nested_spans():
    assert gapspans.flatten([(0, 100, "a"), (10, 30, "b"),
                             (12, 20, "c"), (120, 130, "d")]) == [
        (0, 10, "a", 0), (10, 12, "b", 10), (12, 20, "c", 12),
        (20, 30, "b", 10), (30, 100, "a", 0), (120, 130, "d", 120)]


def test_three_intervals_by_hand():
    """Device busy [0,10) [40,50) [90,100): two gaps of 30 and 40 ns.
    Host: a worker plans [5,45) inside its round.collect [0,60); a
    connection waits in pool.wait [0,95); a sampler ticks [85,92)."""
    by_device = {"/device:TPU:0": [(0.0, 10.0, "a"), (40.0, 10.0, "b"),
                                   (90.0, 10.0, "c")]}
    gaps = gapspans.device_gaps(by_device)
    assert gaps == [(10, 40, "after a"), (50, 90, "after b")]
    threads = gapspans.by_thread([
        [(0, 60, "round.collect"), (5, 45, "plan")],
        [(0, 95, "pool.wait")],
        [(85, 92, "bg.memprof")],
    ])
    # first gap: the innermost working span, not the waiting connection
    assert gapspans.attribute(gaps[0], threads) == {"plan": 30}
    # second: collect to 60, then only the waiter, then the sampler
    assert gapspans.attribute(gaps[1], threads) == {
        "round.collect": 10, "pool.wait": 25, "bg.memprof": 5}
    # nothing live: no span; partly covered: the rest is no span
    assert gapspans.attribute((100, 130, "after c"), threads) == {
        gapspans.NO_SPAN: 30}
    assert gapspans.attribute((90, 100, "x"), threads) == {
        "bg.memprof": 2, "pool.wait": 3, gapspans.NO_SPAN: 5}
    for gap in gaps:
        assert sum(gapspans.attribute(gap, threads).values()) \
            == gap[1] - gap[0]


def test_among_working_spans_the_latest_to_begin_wins():
    threads = gapspans.by_thread([[(0, 100, "round.replay")],
                                  [(20, 80, "wire.parse")]])
    assert gapspans.attribute((10, 90, "g"), threads) == {
        "round.replay": 20, "wire.parse": 60}


def test_the_recorded_trace():
    profile = gapspans.load(RECORDED)
    red = gapspans.GapSpans(profile, top=10)
    # the same gaps as the accepted reduction's, to the nanosecond
    merged = [tracered.union_intervals(events) for events in
              tracered.device_events(profile).values()]
    total_ns = sum(int(round(nxt[0])) - int(round(cur[1]))
                   for m in merged for cur, nxt in zip(m, m[1:]))
    assert red.total_ns == total_ns > 0
    assert sum(red.by_span.values()) == red.total_ns
    for gap, parts in red.longest:
        assert sum(parts.values()) == gap[1] - gap[0]
    assert len(red.threads) >= 16 + 4  # connection threads and workers
    # the host plane names what the chip waited for
    assert red.top_named_share >= 0.8, red.lines()
    assert gapspans.NO_SPAN not in max(red.by_span, key=red.by_span.get)
    # and the device's programs carry their families' names
    assert red.modules and not any(
        name.startswith("jit_kernel") for name in red.modules), red.modules
    text = "\n".join(red.lines())
    assert "| span | idle s | share |" in text and "| program |" in text
