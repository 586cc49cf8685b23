"""The readers of the scheduler's tick-phase spans (``tick_readers``) and of the
programs' named scopes (``scope_readers``), on a trace recorded on a v5e chip
and cut to two scheduler ticks (``data/trace_ticks.txt``; its header says what
was kept and what was edited in: a third tick with no decode).

Expectations are worked out here by other means than the code under test:
intervals painted onto a 100 ns grid, self times by brute-force containment,
and numbers typed in from the file.
"""

import os
import re
import types

import numpy as np
import pytest

import scope_readers
import tick_readers
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_ticks.txt")
CHUNK = 8
STEP = 1e-7  # the grid


@pytest.fixture(scope="module")
def obs():
    flat = trace_reduce.load(DATA)
    o = types.SimpleNamespace(flat=flat, xplane=DATA, cell=None,
                              engine_info={"chunk": CHUNK, "slots": 16})
    o.trace_clock = trace_reduce.window_of(flat, "bench_window")
    return o


def _cells(t):
    return int(round(t / STEP))


def _idle_by_span_painted(obs, names):
    """Idle runs of the device from a painted grid; each run goes whole to the
    shortest span of ``names`` that covers its middle."""
    lo, hi = obs.trace_clock
    grid = np.zeros(_cells(hi - lo), bool)
    for _, s, d in obs.flat["devices"]["/device:TPU:0"]["ops"]:
        a, b = max(_cells(s - lo), 0), min(_cells(s + d - lo), grid.size)
        if b > a:
            grid[a:b] = True
    edges = np.flatnonzero(np.diff(np.concatenate(([True], grid, [True])).astype(np.int8)))
    out = {}
    for a, b in zip(edges[::2], edges[1::2]):  # idle runs [a, b)
        mid = lo + 0.5 * (a + b) * STEP
        cover = [(d, n) for n, s, d in obs.flat["host"] if n in names and s <= mid <= s + d]
        name = min(cover)[1] if cover else None
        out[name] = out.get(name, 0.0) + (b - a) * STEP
    return out


def test_what_the_recorded_trace_holds(obs):
    names = [n for n, _, _ in obs.flat["host"]]
    assert names.count("dtx_engine_tick") == 3 and names.count("bench_window") == 1
    # the third tick has admission and prefill and no decode
    assert names.count("dtx_engine_decode") == names.count("dtx_engine_decode_sync") == 2
    assert names.count("dtx_engine_emit") == 2 and names.count("dtx_engine_admit") == 3
    assert set(names) <= set(tick_readers.LEAF_SPANS) | {"dtx_engine_tick", "bench_window"}
    dev = obs.flat["devices"]["/device:TPU:0"]
    programs = sorted({n.split("(")[0] for n, _, _ in dev["modules"]})
    assert programs == ["jit__activate_impl", "jit__decode_impl", "jit__prefill_chunk_impl"]
    # the kernels are in the trace under the names their pallas_call was given
    kernels = {re.match(r"%(dtx_[a-z_]+)\.\d+ = ", n).group(1)
               for n, _, _ in dev["ops"] if n.startswith("%dtx_")}
    # (dtx_paged_multitoken runs inside the prefill programs' layer scan: not among the outermost ops kept)
    assert kernels == {"dtx_fused_sample", "dtx_paged_decode"}


def test_idle_time_goes_to_the_innermost_scheduler_span(obs):
    names = tick_readers.LEAF_SPANS + (tick_readers.TICK,)
    want = _idle_by_span_painted(obs, names)
    got = tick_readers.idle_by_span(obs)
    assert set(got) == {n or "(no host span)" for n in want}
    for name, t in want.items():
        assert got[name or "(no host span)"] == pytest.approx(t, abs=2e-5), name
    lo, hi = obs.trace_clock
    busy = trace_reduce.busy_idle(obs.flat, lo, hi)
    assert sum(got.values()) == pytest.approx(busy["window_s"] - busy["busy_s"], rel=1e-9)
    # read off the file: the chip waits 30.6 ms under the two emit spans (the
    # first is 14.1 ms long and nothing is queued behind it), 13.1 ms under admit
    assert got["dtx_engine_emit"] == pytest.approx(0.030609, abs=1e-5)
    assert got["dtx_engine_admit"] == pytest.approx(0.013141, abs=1e-5)


def test_gaps_are_per_decode_dispatch_and_a_tick_without_decode_adds_none(obs):
    idle = _idle_by_span_painted(obs, tick_readers.LEAF_SPANS + (tick_readers.TICK,))
    decodes = 2  # three ticks in the window, two of them dispatched a decode
    for names in (tick_readers.EMIT, tick_readers.ADMIT, tick_readers.DISPATCH):
        want = sum(idle.get(n, 0.0) for n in names) * 1e3 / decodes
        assert tick_readers.gap_ms(obs, names) == pytest.approx(want, abs=2e-2), names
    # admit: both names count, and the recorded traffic never acquired an adapter slot
    assert "dtx_engine_adapter_acquire" not in idle
    total = sum(idle.values())
    unnamed = idle.get(None, 0.0) + idle.get(tick_readers.TICK, 0.0)
    assert tick_readers.gap_unnamed_share(obs) == pytest.approx(100.0 * unnamed / total, abs=0.05)
    # 27 ms of it: the 2 ms that the edit left between the second tick and the
    # replayed third, and the tail of the window after the third tick's last span
    assert 30.0 < tick_readers.gap_unnamed_share(obs) < 45.0


def test_tick_readers_return_none_where_the_program_has_no_such_span(obs):
    old = types.SimpleNamespace(
        flat={"devices": obs.flat["devices"],
              "host": [e for e in obs.flat["host"]
                       if e[0] in ("bench_window", "dtx_engine_decode", "dtx_engine_prefill_chunk")]},
        trace_clock=obs.trace_clock, engine_info=obs.engine_info)
    # the parent of PR 24: the two dispatch spans and nothing else
    assert tick_readers.gap_ms(old, tick_readers.EMIT) is None
    assert tick_readers.gap_ms(old, tick_readers.ADMIT) is None
    assert tick_readers.gap_ms(old, tick_readers.DISPATCH) > 0
    assert tick_readers.gap_unnamed_share(old) > 90.0
    train = types.SimpleNamespace(flat={"devices": obs.flat["devices"], "host": []},
                                  trace_clock=obs.trace_clock, engine_info={})
    assert tick_readers.gap_unnamed_share(train) is None
    assert tick_readers.gap_ms(types.SimpleNamespace(flat=None), tick_readers.EMIT) is None


# ------------------------------------------------------------------ scopes

def _field(number, payload):
    assert len(payload) < 128
    return bytes([number << 3 | 2, len(payload)]) + payload


def test_the_wire_reader_on_a_message_encoded_by_hand():
    ins = lambda name, op: _field(2, _field(1, name) + bytes([0x10, 0x2A])  # a varint field to skip
                                  + _field(7, _field(1, b"dot") + _field(2, op)))
    comp = _field(1, b"main") + ins(b"fusion.1", b"jit(f)/dtx.qkv/dot") + ins(b"copy.2", b"")
    hlo = _field(1, _field(1, b"jit_f") + _field(3, comp))
    meta = bytes([0x08, 0x07]) + _field(2, b"jit_f(7)") + _field(5, bytes([0x08, 0x01]) + _field(6, hlo))
    plane = _field(2, b"/host:metadata") + _field(4, bytes([0x08, 0x07]) + _field(2, meta))
    other = _field(2, b"/host:CPU") + _field(4, bytes([0x08, 0x01]) + _field(2, _field(2, b"span")))
    space = _field(1, other) + _field(1, plane)
    assert scope_readers.hlo_op_names(space) == {"jit_f(7)": {"fusion.1": "jit(f)/dtx.qkv/dot"}}
    assert scope_readers.hlo_op_names(_field(1, other)) == {}


@pytest.mark.parametrize("op_name,region", [
    ("jit(_decode_impl)/while/body/closed_call/dtx.layers/while/body/closed_call/checkpoint/"
     "dtx.qkv/dtx.lora/btd,bdr->btr/dot_general", "dtx.qkv"),  # two nested scopes: the outer one
    ("jit(_decode_impl)/while/body/closed_call/dtx.layers/while/body/squeeze", "dtx.layers"),
    ("jit(_decode_impl)/while/body/closed_call/dtx.sample/dtx_fused_sample/pallas_call", "dtx.sample"),
    ("jit(_train_step_impl)/transpose(jvp(dtx.layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/dtx.attn/dot_general", "dtx.attn"),
    ("checkpoint/dtx.mlp/reduce_sum", "dtx.mlp"),  # a called computation XLA did not inline
    ("jit(_decode_impl)/while", None),
    (None, None),  # a copy XLA put in: no metadata at all
])
def test_region_of_an_op_name(op_name, region):
    assert scope_readers.region_of(op_name) == region


def _self_times_by_containment(events):
    """self = duration less the durations of the DIRECT children, found by
    comparing every pair."""
    def holds(a, b):
        return a is not b and a[1] <= b[1] and b[1] + b[2] <= a[1] + a[2] and a[2] > b[2]
    out = []
    for e in events:
        inside = [c for c in events if holds(e, c)]
        direct = [c for c in inside if not any(holds(o, c) for o in inside)]
        out.append((e[0], e[2] - sum(c[2] for c in direct)))
    return out


def _decode_ops_by_hand(obs):
    dev = obs.flat["devices"]["/device:TPU:0"]
    tables = scope_readers.hlo_op_names(scope_readers._load_bytes(DATA))
    lo, hi = obs.trace_clock
    runs = [m for m in dev["modules"] if "_decode_impl" in m[0] and lo <= m[1] and m[1] + m[2] <= hi]
    out = []
    for program, s, d in runs:
        inside = [e for e in dev["ops"] if s <= e[1] and e[1] + e[2] <= s + d]
        for name, t in _self_times_by_containment(inside):
            op = tables[program].get(name.split(" = ")[0].lstrip("%"))
            scopes = [p for p in re.findall(r"dtx\.[a-z_]+", op or "")]
            inner = [p for p in scopes if p != "dtx.layers"]
            out.append((name, inner[0] if inner else ("dtx.layers" if scopes else None), t))
    return runs, out


def test_op_names_come_from_the_metadata_plane_of_the_same_file(obs):
    tables = scope_readers.hlo_op_names(scope_readers._load_bytes(DATA))
    decode = next(t for p, t in tables.items() if "_decode_impl" in p)
    assert decode["dtx_fused_sample.11"].endswith("/dtx.sample/dtx_fused_sample/pallas_call")
    assert decode["dtx_paged_decode.12"].endswith("/checkpoint/dtx.attn/dtx_paged_decode/pallas_call")
    assert "/dtx.qkv/dtx.lora/" in decode["multiply_reduce_fusion.7"]
    assert decode["while.48"].endswith("/dtx.layers/while") and decode["while.47"].endswith(")/while")
    # the whole-pool copies XLA inserts have no op_name: they stay unscoped
    assert "copy.99" not in decode and "copy.96" not in decode and "copy.116" not in decode


def test_decode_time_by_region_per_token_step(obs):
    runs, ops = _decode_ops_by_hand(obs)
    assert len(runs) == 2
    steps = len(runs) * CHUNK
    for regions in (("dtx.sample",), ("dtx.attn",), scope_readers.KV_POOL, scope_readers.WEIGHTS):
        want = sum(t for _, r, t in ops if r in regions) * 1e3 / steps
        assert scope_readers.decode_region_ms(obs, regions) == pytest.approx(want, rel=1e-6), regions
    # typed in from the file: the fused sampler ran 25.4826 and 25.4753 ms in the two
    # step-0s kept, and 17 us of small ops around each are under dtx.sample too
    assert scope_readers.decode_region_ms(obs, ("dtx.sample",)) == pytest.approx(
        (25.4826 + 25.4753) / steps, rel=2e-3)
    # the two step loops keep the time of the steps that were cut out as self time,
    # and they carry no dtx. scope: most of the program is unscoped in this cut
    unscoped = sum(t for _, r, t in ops if r is None)
    assert scope_readers.decode_unscoped_share(obs) == pytest.approx(
        100.0 * unscoped / sum(d for _, _, d in runs), rel=1e-6)
    assert scope_readers.decode_unscoped_share(obs) > 80.0
    # every second is in exactly one place
    parts = [scope_readers.decode_region_ms(obs, r) for r in
             (("dtx.sample",), ("dtx.attn",), scope_readers.KV_POOL, scope_readers.WEIGHTS)]
    inside = sum(t for _, _, t in ops)
    assert sum(parts) * steps / 1e3 + unscoped == pytest.approx(inside, rel=1e-9)


def test_an_execution_cut_by_the_sessions_edge_is_left_out(obs):
    cut = types.SimpleNamespace(**vars(obs))
    cut._scoped_ops = False
    dev = obs.flat["devices"]["/device:TPU:0"]
    second = [m for m in dev["modules"] if "_decode_impl" in m[0]][1]
    # the session closes 0.2 s into the second decode execution: the profiler keeps
    # the part it saw, as one shorter execution
    short = (second[0], second[1], 0.2)
    a_third = (second[0], second[1] + 0.6, second[2])
    cut.flat = {"host": obs.flat["host"], "devices": {"/device:TPU:0": {
        "ops": [e for e in dev["ops"] if e[1] + e[2] <= second[1] + 0.2 or e[1] >= second[1] + second[2]],
        "modules": [m for m in dev["modules"] if m is not second] + [short, a_third]}}}
    cut.trace_clock = (obs.trace_clock[0], obs.trace_clock[1] + 1.0)
    runs = [m for m in scope_readers.whole_runs(cut) if "_decode_impl" in m[0]]
    assert short not in runs and len(runs) == 2


def test_busy_share_and_programs_without_scopes(obs):
    sample = scope_readers.busy_share(obs, lambda op: scope_readers.region_of(op) == "dtx.sample")
    everything = scope_readers.busy_share(obs, lambda op: True)
    assert 0.0 < sample < everything < 100.0  # ops without an op_name are busy and never picked
    assert scope_readers.busy_share(obs, lambda op: scope_readers.REMAT_MARK in op) == 0.0
    # a program from before the scopes existed: the first recorded trace has an HLO-less file
    old = os.path.join(os.path.dirname(DATA), "trace_small.txt")
    flat = trace_reduce.load(old)
    o = types.SimpleNamespace(flat=flat, xplane=old, engine_info={"chunk": 1},
                              trace_clock=trace_reduce.window_of(flat, "bench_window"))
    assert scope_readers.scoped_ops(o) is None
    assert scope_readers.decode_region_ms(o, ("dtx.attn",)) is None
    assert scope_readers.decode_unscoped_share(o) is None
    assert scope_readers.busy_share(o, lambda op: True) is None
    assert scope_readers.decode_region_ms(types.SimpleNamespace(flat=None), ("dtx.attn",)) is None
