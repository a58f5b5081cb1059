"""Busy, idle and kernel time from device and host events."""
import pathlib

import pytest

from harness import devtrace

DATA = pathlib.Path(__file__).with_name("data")


def test_hand_worked_window():
    host = [("bench.window", 1000, 9000),          # window [1000, 10000]
            ("bench.step", 900, 4100),             # covers 900..5000
            ("PjitFunction(decode_step)", 5500, 500)]
    device = {0: [("nm_spmm_pallas_decode", 500, 1000),   # clipped to 1000..1500
                  ("fusion.1", 1400, 600),                 # overlaps: union to 2000
                  ("nm_spmm_pallas_decode", 3000, 2000),   # 3000..5000
                  ("fusion.2", 9500, 1000)]}               # clipped to 9500..10000
    r = devtrace.reduce(device, host)
    assert r.window_s == pytest.approx(9000e-9)
    assert r.busy_s == pytest.approx((1000 + 2000 + 500) * 1e-9)
    assert r.idle_share == pytest.approx(1 - 3500 / 9000)
    assert r.seconds_of("nm_spmm") == pytest.approx(2500e-9)
    assert r.count_of("nm_spmm_pallas_decode") == 2
    # gaps: 2000..3000 (in bench.step), 5000..9500 (midpoint 7250: none)
    assert [n for _, n in r.gaps] == ["none", "bench.step"]
    assert r.gaps[0][0] == pytest.approx(4500e-9)
    b = r.breakdown()
    assert b["device_ops"][0][0] == "nm_spmm_pallas_decode"
    assert b["idle_gaps"][0] == ["none", pytest.approx(4500e-9)]


def test_container_ops_keep_only_their_own_time():
    """A layer scan's ``while`` holds its kernels: busy counts the union,
    each op its self time, so the kernels are not counted twice."""
    host = [("bench.window", 0, 1000)]
    device = {0: [("while.3", 100, 800),
                  ("nm_spmm_pallas_decode.4", 150, 300),
                  ("fusion.1", 500, 100),
                  ("fusion.2", 950, 50)]}
    r = devtrace.reduce(device, host)
    assert r.busy_s == pytest.approx((800 + 50) * 1e-9)
    assert r.op_seconds["while.3"] == pytest.approx((800 - 300 - 100) * 1e-9)
    assert r.seconds_of("nm_spmm") == pytest.approx(300e-9)
    assert sum(r.op_seconds.values()) == pytest.approx(r.busy_s)


def test_innermost_span_names_the_gap():
    host = [("bench.window", 0, 100), ("bench.step", 0, 100),
            ("PjitFunction(f)", 40, 20)]
    r = devtrace.reduce({0: [("op", 0, 40), ("op", 60, 40)]}, host)
    assert r.gaps == [(pytest.approx(20e-9), "PjitFunction(f)")]


def test_no_device_work_is_refused_and_no_host_span_falls_back():
    with pytest.raises(ValueError):
        devtrace.reduce({0: []}, [("bench.window", 0, 10)])
    r = devtrace.reduce({0: [("op", 10, 10), ("op", 40, 20)]}, [])
    assert r.window_s == pytest.approx(50e-9)
    assert r.busy_s == pytest.approx(30e-9)
    assert r.gaps == [(pytest.approx(20e-9), "none")]


def test_recorded_v5e_trace():
    """A small trace recorded on a TPU v5e: three steps of a decode-kernel
    call (M=8), a prefill-kernel call (M=1024) and a reduction, each step
    under a bench.step span (no bench.window: the fallback window runs
    from the first step's start to the last step's end)."""
    device, host = devtrace.load_xplane(str(DATA / "v5e_probe.xplane.pb"))
    assert list(device) == [0]
    names = {n for n, _, _ in device[0]}
    assert {"nm_spmm_pallas_decode.1", "nm_spmm_pallas.1", "fusion"} <= names
    steps = [(s, s + d) for n, s, d in host if n == "bench.step"]
    assert len(steps) == 3
    w0, w1 = steps[0][0], steps[-1][1]
    r = devtrace.reduce(device, host)
    assert r.window_s == pytest.approx((w1 - w0) * 1e-9)
    # the ops of this line do not overlap: busy is the clipped sum
    clipped = sum(max(0.0, min(s + d, w1) - max(s, w0))
                  for _, s, d in device[0])
    assert r.busy_s == pytest.approx(clipped * 1e-9)
    # the device timeline runs ~2 ms ahead of the host's: the first
    # step's decode call falls before its host span and is left out
    dec = [d for n, s, d in device[0] if n.startswith("nm_spmm_pallas_decode")
           and s >= w0]
    assert r.count_of("nm_spmm_pallas_decode") == len(dec) == 2
    assert r.seconds_of("nm_spmm_pallas_decode") == pytest.approx(sum(dec) * 1e-9)
    # the first prefill-kernel call straddles the window's start: clipped
    pre = sum(max(0.0, min(s + d, w1) - max(s, w0))
              for n, s, d in device[0] if n == "nm_spmm_pallas.1")
    assert r.seconds_of("nm_spmm") == pytest.approx((sum(dec) + pre) * 1e-9)
    assert 0.0 < r.idle_share < 1.0
