"""The readers of the spans and keywords the scheduler gained in PR 37
(``cause_readers``, ``span_stats``), on a hand-made trace laid out on round
milliseconds (``data/trace_causes.txt``: its header cuts the device's idle time
along the innermost open span, on paper), and on the PR 24 recording, which has
none of the new names. Expectations are typed in from that header."""

import os
import types

import pytest

import cause_readers
import span_stats
import spec
import tick_readers
import trace_reduce
from common import Observed

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "trace_causes.txt")
OLD = os.path.join(HERE, "data", "trace_ticks.txt")
NEW_ENTRIES = {
    "engine.idle_starved_share": ["qwen-serve-steady"],
    "engine.idle_starved_share.batch": ["mistral-serve-batch", "mimo-serve-batch"],
    "engine.gap_sync_ms": ["qwen-serve-steady"],
    "engine.gap_sync_ms.batch": ["mistral-serve-batch", "mimo-serve-batch"],
    "engine.gap_emit_push_ms.batch": ["mistral-serve-batch", "mimo-serve-batch"],
    "engine.gap_release_ms.batch": ["mistral-serve-batch", "mimo-serve-batch"],
    "engine.gap_complete_ms.batch": ["mistral-serve-batch", "mimo-serve-batch"],
    "engine.finishes_per_dispatch.batch": ["mistral-serve-batch", "mimo-serve-batch"],
    "engine.admit_waited_share": ["qwen-serve-steady"],
    "engine.clock_skew_ms": ["qwen-serve-steady"],
    "engine.clock_skew_ms.batch": ["mistral-serve-batch", "mimo-serve-batch"],
}
# idle milliseconds while each span was the scheduler's innermost open one (the file's header)
IDLE_MS = {"dtx_engine_wait_empty": 97, "dtx_engine_wait_blocked": 97, "dtx_engine_wait": 2,
           "dtx_engine_tick": 8.3, "dtx_engine_emit_push": 20 + 5 + 34.5 + 7.5,
           "dtx_engine_release": 5 + 15 + 8 + 11 + 8 + 0.5, "dtx_engine_complete": 10 + 15 + 0.2,
           "dtx_engine_emit": 4 + 9 + 0.8, "dtx_engine_decode_sync": 10 + 10 + 5,
           "dtx_engine_admit": 48.2, "dtx_engine_decode": 4, "dtx_engine_prefill_chunk": 5}
# the same idle time given whole to the span over each interval's middle (tick_readers' rule)
WHOLE_MS = {"dtx_engine_admit": 36, "dtx_engine_emit": 50 + 15 + 29 + 48, "dtx_engine_decode_sync": 15,
            "dtx_engine_wait": 144 + 103}
DISPATCHES = 3  # decode spans at 110, 455 and 901 ms
W0 = 1000.1     # the window span opens at 100 ms; the program's clock runs 1000 s ahead


def _record(*marks):
    req = types.SimpleNamespace(timeline=[(0.0, e, d) for e, d in marks])
    return types.SimpleNamespace(req=req)


def _obs(path=DATA, w0=W0, records=()):
    flat = trace_reduce.load(path)
    o = types.SimpleNamespace(flat=flat, xplane=path, cell=None, records=list(records),
                              engine_info={"chunk": 8, "slots": 16})
    o.trace_clock = trace_reduce.window_of(flat, "bench_window")
    o.trace_window = (w0, w0 + o.trace_clock[1] - o.trace_clock[0])
    return o


@pytest.fixture()
def obs():
    return _obs()


def test_what_the_hand_made_trace_holds(obs):
    names = [n for n, _, _ in obs.flat["host"]]
    assert names.count("dtx_engine_tick") == 5 and names.count("bench_window") == 1
    assert names.count("dtx_engine_decode") == names.count("dtx_engine_decode_sync") == DISPATCHES
    assert names.count("dtx_engine_release") == names.count("dtx_engine_complete") == 3
    assert names.count("dtx_engine_wait_empty") == names.count("dtx_engine_wait_blocked") == 1
    assert set(names) <= cause_readers.ALL_SPANS | {"bench_window"}
    assert obs.trace_clock == pytest.approx((0.1, 1.1))
    busy = trace_reduce.busy_idle(obs.flat, *obs.trace_clock)
    assert busy["window_s"] - busy["busy_s"] == pytest.approx(sum(IDLE_MS.values()) * 1e-3) == pytest.approx(0.440)


def test_the_keywords_come_back_with_their_spans(obs):
    spans = span_stats.spans(obs)
    assert [s for _, s, _, _ in spans] == sorted(s for _, s, _, _ in spans)
    assert all(n.startswith("dtx_engine_") for n, _, _, _ in spans)  # the window span is the benchmark's
    by_name = {}
    for n, _, _, st in spans:
        by_name.setdefault(n, []).append(st)
    assert [st["tick"] for st in by_name["dtx_engine_tick"]] == [7, 8, 9, 10, 11]
    assert by_name["dtx_engine_tick"][0]["t_perf"] == pytest.approx(1000.1 - 10e-6, abs=1e-9)
    assert by_name["dtx_engine_wait_blocked"] == [{"reason": "blocks"}]
    assert [st["blocks"] for st in by_name["dtx_engine_release"]] == [6, 4, 5]
    assert [st["tokens"] for st in by_name["dtx_engine_emit_push"]] == [16, 16, 8]
    assert by_name["dtx_engine_wait_empty"] == [{}]
    assert cause_readers.ticks(obs)[2] == (pytest.approx(0.7), pytest.approx(1000.7 - 30e-6), 9)


def test_idle_is_cut_along_the_innermost_open_span(obs):
    got = cause_readers.idle_by_cause(obs)
    assert set(got) == set(IDLE_MS)
    for name, ms in IDLE_MS.items():
        assert got[name] == pytest.approx(ms * 1e-3, abs=1e-9), name
    assert sum(got.values()) == pytest.approx(0.440)
    # the readers of PR 24 see what they saw: the new names are inside their leaves,
    # and whole intervals still go to the leaf over their middle
    old = tick_readers.idle_by_span(obs)
    assert {n: pytest.approx(ms * 1e-3) for n, ms in WHOLE_MS.items()} == old
    assert tick_readers.gap_ms(obs, tick_readers.EMIT) == pytest.approx(142 / DISPATCHES)


def test_innermost_on_spans_that_nest_touch_and_leave_holes():
    spans = [("a", 0.0, 10.0), ("b", 1.0, 4.0), ("c", 2.0, 3.0), ("d", 4.0, 6.0), ("e", 12.0, 13.0)]
    assert cause_readers.innermost(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"), (3.0, 4.0, "b"), (4.0, 6.0, "d"),
        (6.0, 10.0, "a"), (12.0, 13.0, "e")]
    assert cause_readers.innermost([]) == []
    # an idle interval that reaches outside every span keeps the rest apart
    o = types.SimpleNamespace(
        flat={"host": [("dtx_engine_tick", 1.0, 1.0)],
              "devices": {"/device:TPU:0": {"ops": [("%x", 0.0, 0.5), ("%y", 2.5, 0.5)], "modules": []}}},
        trace_clock=(0.0, 3.0))
    assert cause_readers.idle_by_cause(o) == {"(no host span)": pytest.approx(1.0),
                                              "dtx_engine_tick": pytest.approx(1.0)}


@pytest.mark.parametrize("metric,want", [
    ("engine.idle_starved_share", 100 * 97 / 440),
    ("engine.idle_starved_share.batch", 100 * 97 / 440),
    ("engine.gap_sync_ms", 25 / DISPATCHES),
    ("engine.gap_sync_ms.batch", 25 / DISPATCHES),
    ("engine.gap_emit_push_ms.batch", 67 / DISPATCHES),
    ("engine.gap_release_ms.batch", 47.5 / DISPATCHES),
    ("engine.gap_complete_ms.batch", 25.2 / DISPATCHES),
    ("engine.finishes_per_dispatch.batch", 3 / DISPATCHES),
    ("engine.clock_skew_ms", 0.030),
    ("engine.clock_skew_ms.batch", 0.030),
])
def test_each_new_metric_on_the_hand_made_trace(obs, metric, want):
    assert spec.load_module("metrics", metric + ".py").read(obs) == pytest.approx(want, rel=1e-6)


def test_push_release_complete_and_emissions_own_add_up_to_the_idle_inside_emission(obs, capsys):
    parts = sum(cause_readers.gap_ms(obs, (n,)) for n in
                (cause_readers.EMIT_PUSH, cause_readers.RELEASE, cause_readers.COMPLETE))
    own = cause_readers.gap_ms(obs, ("dtx_engine_emit",))
    assert parts == pytest.approx((67 + 47.5 + 25.2) / DISPATCHES)
    # the device is idle through the three emit spans but for the small programs that
    # run inside them: three of 1 ms (a released slot's table row) and one of 0.5 ms
    emit_spans = (399 - 300) + (699 - 650) + (1099 - 1090)
    assert (parts + own) * DISPATCHES == pytest.approx(emit_spans - 3 * 1.0 - 0.5)
    # the rule of tick_readers gives emission 142 ms: whole intervals, admissions and all
    assert tick_readers.gap_ms(obs, tick_readers.EMIT) * DISPATCHES == pytest.approx(142)
    # the push reader logs the whole table once
    capsys.readouterr()
    spec.load_module("metrics", "engine.gap_emit_push_ms.batch.py").read(obs)
    line = capsys.readouterr().err
    assert "'emit_push': 22.333" in line and "'release': 15.833" in line and "(3 dispatches)" in line


def test_a_window_span_that_opens_late_is_what_the_skew_reports(tmp_path):
    """The benchmark stamps ``w0``, THEN starts the profiler and opens the
    window span: here 250 ms later. ``_to_trace_clock`` takes the two for one
    instant; the passes' own ``t_perf`` say by how much that is off."""
    with open(DATA) as f:
        text = f.read()
    line = "events { metadata_id: 1 offset_ps: 100000000000 duration_ps: 1000000000000 }"
    late = "events { metadata_id: 1 offset_ps: 350000000000 duration_ps: 750000000000 }"
    host = text.index('name: "/host:CPU"')
    assert text.count(line, host) == 1
    path = tmp_path / "trace_late.txt"
    path.write_text(text[:host] + text[host:].replace(line, late))
    obs = _obs(str(path))
    assert obs.trace_clock == pytest.approx((0.35, 1.1))
    assert cause_readers.perf_to_trace_offset(obs) == pytest.approx(-1000.0 + 30e-6, abs=1e-9)
    assert cause_readers.clock_skew_ms(obs) == pytest.approx(250.0 - 0.030, abs=1e-6)
    # and an anchor that is right reads the microseconds between stamp and span
    assert cause_readers.clock_skew_ms(_obs(w0=W0)) == pytest.approx(0.030, abs=1e-6)


def test_admissions_meet_the_window_by_the_number_of_their_pass(obs, capsys):
    admit = lambda tick, cause, waited=0: ("admit", {"tick": tick, "slot": 0, "waited_for": cause,  # noqa: E731
                                                     "waited_ticks": waited})
    obs.records = [
        _record(admit(7, "tick"), ("finish", {"tick": 9})),
        _record(admit(8, "slot", 5)),
        _record(admit(8, "blocks", 9)),
        _record(admit(11, "tick", 1)),
        _record(admit(5, "slot", 3)),    # admitted before the window span opened
        _record(admit(12, "slot", 3)),   # and after the trace ended
        _record(("admit", {"tick": 8, "slot": 1})),  # no cause on it: not counted
        types.SimpleNamespace(req=None),  # a request that was never sent
    ]
    assert len(cause_readers.admitted_in_window(obs)) == 4
    assert cause_readers.admit_waited_share(obs) == pytest.approx(50.0)
    line = capsys.readouterr().err
    assert "4 admitted" in line and "'blocks': 1, 'slot': 1, 'tick': 2" in line and "most 9" in line
    obs.records = obs.records[4:]
    assert cause_readers.admit_waited_share(obs) is None


def test_a_program_from_before_the_new_spans_reads_none():
    """The PR 24 recording: ticks and leaves, none of the new names, no keyword
    on a tick. Every new reader finds nothing, but for the sync gap, whose span
    is PR 24's."""
    old = _obs(OLD, records=[_record(("admit", {"slot": 0, "plen": 64, "mode": "chunked"}))])
    assert not cause_readers.holds(old) and cause_readers.ticks(old) == []
    for metric in NEW_ENTRIES:
        value = spec.load_module("metrics", metric + ".py").read(old)
        if metric.startswith("engine.gap_sync_ms"):
            # read off trace_ticks.txt: the first decode program ends 4.7 ms before its
            # sync span does, the second 1.6 ms before
            assert 0.5 < value < 10.0
            assert value == pytest.approx(
                cause_readers.idle_by_cause(old)["dtx_engine_decode_sync"] * 1e3 / 2)
        else:
            assert value is None, metric
    # a trace with no scheduler span at all (a training cell), and no trace
    train = types.SimpleNamespace(flat={"devices": old.flat["devices"], "host": []}, xplane=OLD,
                                  trace_clock=old.trace_clock, trace_window=old.trace_window,
                                  engine_info={}, records=[], cell=None)
    for metric in NEW_ENTRIES:
        assert spec.load_module("metrics", metric + ".py").read(train) is None, metric
    assert span_stats.spans(types.SimpleNamespace(flat=None)) == []
    gone = types.SimpleNamespace(flat=old.flat, xplane=os.path.join(HERE, "data", "no_such.xplane.pb"))
    assert span_stats.spans(gone) == []


def test_every_new_entry_has_its_file_and_every_new_file_its_entry():
    bench = spec.benchmark_json()
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    for name, workloads in NEW_ENTRIES.items():
        m = entries[name]
        assert m["workloads"] == workloads and set(workloads) <= cells
        assert m["layer"] == "Engine scheduler"
        assert m["source"] in ("program_span", "program_counter")
        assert os.path.isfile(os.path.join(os.path.dirname(HERE), "metrics", name + ".py")), name
        # cell 5's per-layer count is pinned by a test this PR may not edit
        assert "ling-serve-decode" not in workloads
        # a metric moves an end-to-end metric that each of its cells reports
        for cell in workloads:
            assert m["moves"] in {e["name"] for e in spec.load_cell(cell).end_to_end}, (name, cell)
    # the entries were appended, in the order of ISSUE 37's table
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_ENTRIES):] == list(NEW_ENTRIES)
    files = {f[:-3] for f in os.listdir(os.path.join(os.path.dirname(HERE), "metrics"))
             if f.endswith(".py")}
    assert files == set(entries)
    readers_of = {n for n in files if "cause_readers" in open(
        os.path.join(os.path.dirname(HERE), "metrics", n + ".py")).read()}
    assert readers_of == set(NEW_ENTRIES)


@pytest.mark.parametrize("cell", ["qwen-serve-steady", "mistral-serve-batch", "mimo-serve-batch"])
def test_every_reader_of_the_cell_finds_nothing_on_an_empty_run(cell):
    loaded = spec.load_cell(cell)
    mine = [m for m in loaded.per_layer if m["name"] in NEW_ENTRIES]
    assert len(mine) == sum(1 for w in NEW_ENTRIES.values() if cell in w)
    for m in mine:
        reader = spec.load_module("metrics", m["name"] + ".py")
        assert reader.read(Observed(cell=loaded, engine_info={"chunk": 8, "slots": 16})) is None, m["name"]
    assert all(m["name"] not in NEW_ENTRIES for m in spec.load_cell("ling-serve-decode").per_layer)
