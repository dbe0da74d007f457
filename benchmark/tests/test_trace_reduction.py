"""The reduction from a trace to busy seconds, on intervals made by hand
and on a small trace recorded on the chip (PR 26, call 3: the first 8 s of
a ``tpch_sf1.q6_dash_16c`` window on one v5e; 1794 operations)."""
import gzip
import os

import pytest

import tracered

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "q6_dash_16c_8s.xplane.pb.gz")
RECORDED_WINDOW_S = 8.004730939865112


def test_union_of_intervals_by_hand():
    events = [(0, 10, "a"), (5, 10, "b"), (30, 5, "c"), (31, 1, "d"),
              (50, 10, "a")]
    assert tracered.union_intervals(events) == [
        [0, 15, "b"], [30, 35, "c"], [50, 60, "a"]]
    red = tracered.Reduction({"dev": events}, window_s=100e-9)
    assert red.busy_s == pytest.approx(30e-9)
    assert red.idle_share == pytest.approx(0.7)
    assert red.device_ops[0] == ["a", pytest.approx(20e-9)]
    assert dict(red.idle_gaps) == {"after b": pytest.approx(15e-9),
                                   "after c": pytest.approx(15e-9)}


def test_busy_is_averaged_over_devices():
    red = tracered.Reduction({"d0": [(0, 10, "a")], "d1": [(0, 30, "a")]},
                             window_s=40e-9)
    assert red.busy_s == pytest.approx(20e-9)
    assert red.idle_share == pytest.approx(0.5)


def test_short_name():
    hlo = ("%fusion.69 = f32[8388608]{0:T(1024)S(1)} fusion(f32[8388608]"
           "{0:T(1024)} %get-tuple-element.3407), kind=kCustom")
    assert tracered.short_name(hlo) == "%fusion.69 f32[8388608]"
    assert tracered.short_name("%f = (pred[4]{0}, u32[4]{0}) fusion()") \
        == "%f pred[4]"
    assert tracered.short_name("jit_kernel(123)") == "jit_kernel(123)"


def test_the_recorded_trace(tmp_path):
    path = tmp_path / "vm.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    assert tracered.find_xplane(str(tmp_path)) == str(path)
    red = tracered.reduce_file(str(path), RECORDED_WINDOW_S)
    # the operations never overlap on the one core, so the union equals
    # the sum of the 1794 durations: 0.238304227 s, read by hand
    assert red.busy_s == pytest.approx(0.238304227, abs=1e-9)
    assert 100 * red.idle_share == pytest.approx(97.0229577, abs=1e-6)
    assert red.device_ops[0] == ["%iota_reduce_fusion pred[4]",
                                 pytest.approx(0.023076796, abs=1e-9)]
    assert len(red.device_ops) == 10 and len(red.idle_gaps) == 10
    gaps = sum(seconds for _name, seconds in red.idle_gaps)
    assert gaps < RECORDED_WINDOW_S - red.busy_s


def test_a_trace_with_no_device_reduces_to_nothing(tmp_path):
    """As the CPU's: only host planes."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = tracered.find_xplane(str(tmp_path))
    assert path is not None
    assert tracered.reduce_file(path, 1.0) is None
