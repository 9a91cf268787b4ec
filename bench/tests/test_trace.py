"""The trace reduction, on hand-made events and on a small recorded trace."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.trace import Device, Event  # noqa: E402


def test_union_and_gaps_by_hand():
    evs = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5),
           Event("d", 50, 10)]
    assert trace.union(evs) == [(0, 15), (30, 35), (50, 60)]
    assert trace.gaps(trace.union(evs), 0, 70) == [(15, 30), (35, 50),
                                                   (60, 70)]


def test_attribute_by_largest_overlap():
    spans = [Event("segment dispatch", 10, 10), Event("probe", 18, 20)]
    assert trace.attribute((15, 30), spans) == "probe"
    assert trace.attribute((100, 110), spans) == "host"


def test_summary_by_hand():
    # two devices; device 1 is busy 40 of the 100 ns window, device 0 60
    d0 = Device("/device:TPU:0",
                ops=[Event("fusion.1", 0, 30), Event("collective-permute", 20,
                                                     30)],
                modules=[Event("jit_bench_probe_x(1)", 0, 50),
                         Event("jit_other", 90, 10)])
    d0.ops.append(Event("fusion.2", 90, 10))
    d1 = Device("/device:TPU:1",
                ops=[Event("fusion.1", 0, 40)],
                modules=[Event("jit_bench_probe_x(1)", 0, 40)])
    s = trace.Summary([d0, d1], [Event("probe", 0, 100)])
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((60 + 40) / 2 * 1e-9)
    assert s.module("bench_probe_x") == (1.0, pytest.approx(45e-9))
    assert s.idle_gaps() == [["probe", pytest.approx(40e-9)]]
    top = dict((n, v) for n, v in s.top_ops())
    assert top["fusion.1"] == pytest.approx(35e-9)


def test_short_name():
    op = ("%fusion.343 = f32[640,1024,100]{0,2,1:T(8,128)} fusion(f32[640,"
          "1024,100]{0,2,1:T(8,128)} %p)")
    assert trace.short_name(op) == "fusion.343 f32[640,1024,100]"


def test_recorded_trace():
    """bench/tests/record_trace.py on one TPU v5 lite: three 512x512 calls
    of ``bench_probe_toy`` in ``probe`` spans, ~21 ms ``idle wait`` after
    each. Numbers below are read off the trace's events by hand."""
    s = trace.summarize(ROOT / "bench" / "tests" / "data" /
                        "toy.xplane.pb")
    assert len(s.devices) == 1
    # op durations per call (no overlaps): 6795, 6598 and 6817 ns
    assert s.busy_s == pytest.approx(20210e-9)
    assert s.module("bench_probe_toy") == (3.0, pytest.approx(20253e-9))
    # first module start 46,922,320 ns to the last idle wait's end
    # 113,369,862 ns
    assert s.window_s == pytest.approx(66447542e-9)
    longest = s.idle_gaps()[0]
    assert longest[0] == "idle wait"
    # last op (fusion) ends at 90,494,381 ns
    assert longest[1] == pytest.approx((113369862 - 90494381) * 1e-9)
    top = dict(s.top_ops())
    assert top["fusion f32[512,512]"] == pytest.approx(
        (3581 + 3472 + 3524) * 1e-9)


def test_top_ops_within_a_program():
    d = Device("/device:TPU:0",
               ops=[Event("%fusion.1 = f32[4]{0} fusion()", 0, 10),
                    Event("%fusion.2 = f32[4]{0} fusion()", 20, 5)],
               modules=[Event("jit_bench_probe_a(1)", 0, 15),
                        Event("jit_bench_probe_b(2)", 18, 10)])
    s = trace.Summary([d], [])
    assert s.top_ops(within="bench_probe_b") == [["fusion.2 f32[4]",
                                                  pytest.approx(5e-9)]]
