"""The reduction from a profiler trace to numbers, on a small trace recorded on
a v5e chip and checked in beside this file (``data/trace_small.txt``: three
executions of a two-fusion step inside the benchmark's window span, a host
sleep between them). Expectations are worked out here by other means than the
code under test: intervals painted onto a nanosecond grid."""

import os

import numpy as np
import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_small.txt")


@pytest.fixture(scope="module")
def flat():
    return trace_reduce.load(DATA)


def _ns(x):
    return int(round(x * 1e9))


def test_what_the_recorded_trace_holds(flat):
    dev = flat["devices"]["/device:TPU:0"]
    assert len(dev["ops"]) == 12 and len(dev["modules"]) == 3
    assert sorted({n for n, _, _ in flat["host"]}) == ["bench_submit", "bench_window", "dtx_engine_decode"]
    assert all("jit_small_step" in n for n, _, _ in dev["modules"])


def test_window_busy_and_idle(flat):
    lo, hi = trace_reduce.window_of(flat, "bench_window")
    assert (_ns(lo), _ns(hi)) == (42447739, 42447739 + 13086290)
    grid = np.zeros(_ns(hi) - _ns(lo), bool)
    for _, s, d in flat["devices"]["/device:TPU:0"]["ops"]:
        a, b = max(_ns(s), _ns(lo)) - _ns(lo), min(_ns(s + d), _ns(hi)) - _ns(lo)
        if b > a:
            grid[a:b] = True
    busy = trace_reduce.busy_idle(flat, lo, hi)
    assert busy["chips"] == 1 and busy["window_s"] == pytest.approx(13086290e-9)
    assert _ns(busy["busy_s"]) == pytest.approx(int(grid.sum()), abs=2)
    # the first execution's device stamps lie 0.9 ms before the host span that
    # launched it (the two clocks are aligned to about a millisecond): it falls
    # outside the window, two executions of ~24 us each are inside
    assert 48_000 < grid.sum() < 49_000
    idle = trace_reduce.idle_gaps_by_host_span(flat, lo, hi, ("dtx_engine_decode", "bench_submit"))
    assert sum(t for _, t in idle) == pytest.approx(busy["window_s"] - busy["busy_s"], rel=1e-6)
    assert idle[0][0] == "bench_submit"  # the host slept; the device waited


def test_program_and_op_times(flat):
    lo, hi = trace_reduce.window_of(flat, "bench_window")
    inside = trace_reduce.program_times(flat, "small_step", lo, hi)
    assert len(inside) == 2 and all(24e-6 < t < 25e-6 for t in inside)
    assert len(trace_reduce.program_times(flat, "small_step")) == 3
    fusions = trace_reduce.op_times(flat, lambda n: " fusion(" in n, lo, hi)
    assert len(fusions) == 4 and sum(fusions) == pytest.approx(sum(inside), rel=0.01)
    top = trace_reduce.top_ops(flat, lo, hi)
    assert top[0][0].startswith("convolution_tanh_fusion") and top[0][0].endswith(" fusion")
    assert sum(t for _, t in top) == pytest.approx(sum(fusions), rel=0.01)


def test_self_time_takes_nested_ops_out_of_their_container():
    events = [("%while.1 = (s32[]) while(x)", 0.0, 10.0), ("%a = f32[] fusion(x)", 1.0, 3.0),
              ("%b = f32[] custom-call(x)", 5.0, 4.0), ("%c = f32[] fusion(y)", 12.0, 1.0)]
    got = dict(trace_reduce.self_times(events))
    assert got == {"%while.1 = (s32[]) while(x)": 3.0, "%a = f32[] fusion(x)": 3.0,
                   "%b = f32[] custom-call(x)": 4.0, "%c = f32[] fusion(y)": 1.0}
    assert trace_reduce.union_seconds(events) == 11.0
    assert trace_reduce.gaps(events, 0.0, 14.0) == [(10.0, 12.0), (13.0, 14.0)]
    assert trace_reduce.short_name("%while.1 = (s32[], f32[2]) while((s32[]) %t)") == "while.1 while"
    assert trace_reduce.short_name("%closed_call.16 = s32[16]{0:T(128)S(1)} custom-call(f32[16] %x)") \
        == "closed_call.16 custom-call"


def test_a_trace_without_a_device_is_refused(flat):
    with pytest.raises(ValueError, match="no device"):
        trace_reduce.busy_idle({"devices": {}, "host": flat["host"]}, 0.0, 1.0)
