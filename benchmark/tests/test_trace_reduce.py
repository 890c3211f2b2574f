"""trace_reduce on planes with known intervals, and on a trace file
recorded on the chip (benchmark/tests/data)."""

import glob
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce

S = 1_000_000_000  # ns


def ev(name, start, end):
    return NS(name=name, start_ns=start * S, end_ns=end * S,
              duration_ns=(end - start) * S)


def planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.window", 10, 20),
                                  ev("bench.initialize", 10, 15),
                                  ev("racon_tpu.device_poa", 15, 20),
                                  ev("other", 0, 30)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("_poa_full.1", 9, 11),       # clipped to [10, 11]
            ev("_poa_full.1", 16, 18),
            ev("fusion", 17, 19),           # overlaps: union to 16-19
            ev("_wfa_call", 12, 13),
            ev("late", 25, 26)]),           # outside the window
        NS(name="XLA Modules", events=[ev("jit_call", 16, 18)])])
    return [host, dev, NS(name="/host:metadata", lines=[])]


def test_reduce_known_intervals():
    r = trace_reduce.reduce_planes(planes())
    assert r["window_s"] == pytest.approx(10)
    assert r["busy_s"] == pytest.approx(1 + 3 + 1)
    assert r["ops_s"]["_poa_full.1"] == pytest.approx(3)
    assert trace_reduce.device_seconds(r, [r"_poa_full"]) == \
        pytest.approx(3)
    assert trace_reduce.device_seconds(r, [r"nothing"]) is None
    # idle: 11-12 and 13-16 inside bench.initialize, 19-20 inside
    # racon_tpu.device_poa; the longest first, named by the innermost
    # span open at its middle
    assert r["idle_gaps"][0] == ["bench.initialize", pytest.approx(3)]
    assert sorted(g[0] for g in r["idle_gaps"][1:]) == [
        "bench.initialize", "racon_tpu.device_poa"]
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(5)


def test_no_window_span_is_an_error():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(p)


RECORDED = sorted(glob.glob(os.path.join(
    os.path.dirname(__file__), "data", "small_v5e.xplane.pb")))


def test_recorded_chip_trace():
    from benchmark.tests.data import recorded_expect as want

    r = trace_reduce.reduce_file(RECORDED[0])
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(want.WINDOW_S, abs=1e-8)
    assert r["busy_s"] == pytest.approx(want.BUSY_S, abs=1e-8)
    for name, secs in want.OPS_S.items():
        assert r["ops_s"][name] == pytest.approx(secs, abs=1e-8)
    assert r["idle_gaps"][0][0] == want.LONGEST_GAP[0]
    assert r["idle_gaps"][0][1] == pytest.approx(want.LONGEST_GAP[1],
                                                 abs=1e-8)
