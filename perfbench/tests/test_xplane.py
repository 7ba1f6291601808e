"""The trace reducer, on a small trace recorded on a TPU v5 lite: three
rounds of a 3 ms ``data_fn`` span and a ``tick`` span that runs a jitted
matmul and one call of the fused emu bank kernel (256x64 by 100x64)."""

import os

import pytest

import harness
import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(TRACE)


def test_window_busy_and_idle(reduced):
    assert len(reduced.devices) == 1
    assert reduced.window_s == pytest.approx(0.011752235, rel=1e-9)
    assert reduced.busy_s == pytest.approx(5.0521e-05, rel=1e-6)
    # every idle nanosecond is named, and busy + idle fill the window
    assert sum(reduced.idle_by_span.values()) + reduced.busy_s == pytest.approx(
        reduced.window_s, rel=1e-12)


def test_gap_attribution(reduced):
    idle = reduced.idle_by_span
    assert set(idle) == {"data_fn", "tick", "none"}
    assert idle["data_fn"] == pytest.approx(0.008379864, rel=1e-6)
    assert idle["tick"] == pytest.approx(0.0032414, rel=1e-6)


def test_per_op_time_and_kernel_match(reduced):
    emu = harness.load_py(harness.bench_file("kernels", "emu_bank.py"))
    ops = reduced.op_seconds()
    calls = {name: v for name, v in ops.items() if emu.match(name)}
    assert len(calls) == 1
    (count, seconds), = calls.values()
    assert count == 3 and seconds == pytest.approx(25.203e-6, rel=1e-6)
    # the matmul's fusion is not the kernel
    assert not any(emu.match(n) for n in ops if "fusion(" in n)
    top = reduced.breakdown()["device_ops"][0]
    assert top[0].startswith("_lambda_.1 custom-call f32[2,256,50]")


def test_nesting_self_time_and_exposed_collective():
    # a while op holding a matmul and then an all-reduce that blocks
    events = [
        (0, 100, "%while.1 = (f32[]) while(...)"),
        (10, 40, "%fusion.2 = f32[8] fusion(...)"),
        (50, 80, "%all-reduce.3 = f32[8] all-reduce(f32[8] %x)"),
    ]
    dev, idle = xplane.reduce_device(events, (0, 120), xplane.segments([(0, 120, "fit")]))
    assert dev.busy_s == pytest.approx(100e-9)
    assert dev.self_s["%while.1 = (f32[]) while(...)"][1] == pytest.approx(40e-9)
    assert dev.collective_exposed_s == pytest.approx(30e-9)
    assert idle == {"fit": pytest.approx(20e-9)}


def test_segments_take_the_innermost_span():
    segs = xplane.segments([(0, 10, "fit"), (2, 4, "data_fn"), (6, 8, "drain")])
    assert segs == [(0, 2, "fit"), (2, 4, "data_fn"), (4, 6, "fit"),
                    (6, 8, "drain"), (8, 10, "fit")]
    assert xplane.attribute([(1, 3), (9, 12)], segs) == [
        ("fit", 1), ("data_fn", 1), ("fit", 1), ("none", 2)]
