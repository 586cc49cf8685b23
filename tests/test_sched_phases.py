"""The scheduler's phase primitive and what rides on it (ISSUE 37).

``BatchedEngine._phase`` opens the profiler span that was there and adds the
phase's host seconds and a count to ``sched_stats``: these tests read the
always-on table (no profiler is open here), the request timelines
(``waited_for``, ``tick``) and the two ``/metrics`` series. Nothing here
asserts on the wall clock: seconds are only compared with each other.
"""

import json
import threading
import time
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

from datatunerx_tpu.serving.batched_engine import BatchedEngine

MODEL = "preset:debug"
P = "dtx_engine_"
# the spans a tick opens directly, one after the other: nothing nests them
TOP = ("migrate", "resume", "admit", "prefill_chunk", "activate", "grow",
       "decode", "decode_sync", "emit", "wait")
# child span -> the span it is opened inside in these tests
NESTED = {"wait_empty": "wait", "wait_blocked": "wait", "emit_push": "emit"}


def _engine(**kw):
    kw = {"template": "vanilla", "max_seq_len": 256, "slots": 2,
          "decode_chunk": 4, "kv_block_size": 16, "prefill_chunk": 64, **kw}
    return BatchedEngine(MODEL, **kw)


def _stats(eng):
    """{short phase name: (seconds, count)}; read after ``close`` where a
    test needs the table at rest."""
    return {k[len(P):]: tuple(v) for k, v in dict(eng.sched_stats).items()}


def _wait_for(cond, what, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def _admit(req):
    return next(d for _, e, d in req.timeline if e == "admit")


def _releases(eng):
    """The ``slots=`` of every ``dtx_engine_release`` span ``eng`` opens from
    here on (the always-on table keeps a span's seconds and count only)."""
    seen, phase = [], eng._phase

    def recording(name, **detail):
        if name == P + "release":
            seen.append(detail["slots"])
        return phase(name, **detail)

    eng._phase = recording
    return seen


def _hold_ticks_until(eng):
    """The scheduler's next pass waits for the event this returns: a
    submitting thread that stalls beside busy workers would else let the
    first requests run on before the last arrives."""
    all_in, tick = threading.Event(), eng._tick
    eng._tick = lambda: all_in.wait(600) and tick()
    return all_in


@pytest.fixture(scope="module")
def served():
    """An engine that served five requests over two slots and was closed:
    its table and its timelines are at rest."""
    eng = _engine()
    eng.released = _releases(eng)
    tok = eng.tokenizer
    reqs = [eng.submit(tok.encode(f"phase probe number {i} " * 3),
                       max_new_tokens=10, trace_id=f"phase-{i}")
            for i in range(5)]
    for r in reqs:
        assert r.done.wait(300) and r.error is None
    eng.close()
    return eng, reqs


def test_every_pass_is_counted_once_and_so_is_each_phase_of_it(served):
    eng, reqs = served
    st = _stats(eng)
    ticks = st["tick"][1]
    assert ticks == eng._tick_no > 0
    # the four phases every pass opens, once a pass
    for name in ("migrate", "resume", "admit", "grow"):
        assert st[name][1] == ticks, name
    # a pass decodes once or sleeps once or does neither (still prefilling)
    assert st["decode"][1] == st["decode_sync"][1] == st["emit"][1]
    assert st["decode"][1] + st["wait"][1] <= ticks
    assert st["emit_push"][1] == st["emit"][1]
    assert st["wait_empty"][1] + st.get("wait_blocked", (0, 0))[1] == st["wait"][1]
    # one chunk of 64 tokens a request, and one activation
    assert st["prefill_chunk"][1] == st["activate"][1] == len(reqs)


def test_the_phases_of_a_pass_fit_inside_it_and_a_child_inside_its_parent(served):
    st = _stats(served[0])
    assert set(st) <= {"tick", "release", "complete", *TOP, *NESTED}
    assert sum(st[n][0] for n in TOP if n in st) <= st["tick"][0]
    for parent in set(NESTED.values()):
        inside = sum(st[c][0] for c, p in NESTED.items() if p == parent and c in st)
        assert inside <= st[parent][0], parent
    # here a request only ever ends in emission: release and complete are emit's
    assert (st["emit_push"][0] + st["release"][0] + st["complete"][0]
            <= st["emit"][0])


def _one_release_a_pass_and_one_complete_a_request(eng, reqs, released):
    """One ``dtx_engine_complete`` and one ``finish`` a finished request; one
    ``dtx_engine_release`` a pass that ended requests, their ``slots=``
    summing to the finishes."""
    st = _stats(eng)
    assert st["complete"][1] == len(reqs)
    finishes = [d["tick"] for r in reqs for _, e, d in r.timeline if e == "finish"]
    assert sum(1 for e in eng.sched_trace if e[0] == "finish") == len(finishes) == len(reqs)
    # the passes' spans, in order, each as wide as the requests its pass ended
    assert released == [finishes.count(t) for t in sorted(set(finishes))]
    assert st["release"][1] == len(released) and sum(released) == len(reqs)


def test_one_release_and_one_complete_per_finished_request(served):
    eng, reqs = served
    _one_release_a_pass_and_one_complete_a_request(eng, reqs, eng.released)


@pytest.mark.parametrize("max_new, want", [
    ([8, 8, 8, 8], [4]), ([8] * 6, [4, 2]), ([8, 3, 8, 3], [2, 2])],
    ids=["4-at-once", "4-then-2", "2-and-2"])
def test_requests_that_end_in_one_chunk_are_released_by_one_span(max_new, want):
    eng = _engine(slots=4)
    released = _releases(eng)
    try:
        all_in = _hold_ticks_until(eng)
        ids = eng.tokenizer.encode("ending together")
        reqs = [eng.submit(ids, max_new_tokens=n, trace_id=f"together-{i}")
                for i, n in enumerate(max_new)]
        all_in.set()
        for r in reqs:
            assert r.done.wait(300) and r.error is None
    finally:
        eng.close()
    _one_release_a_pass_and_one_complete_a_request(eng, reqs, released)
    # four are admitted and prefilled in one pass and decode chunk for chunk:
    # those of one budget end in one chunk of four tokens
    assert released == want
    assert eng.free_kv_blocks == eng.total_kv_blocks


def test_every_mark_carries_its_tick_and_ticks_never_decrease(served):
    eng, reqs = served
    for r in reqs:
        ticks = [d["tick"] for _, _, d in r.timeline]
        assert [e for _, e, _ in r.timeline] == ["admit", "prefill", "activate", "finish"]
        assert ticks == sorted(ticks) and 1 <= ticks[0] and ticks[-1] <= eng._tick_no
        assert r.tick_submit <= ticks[0]
        stamps = [t for t, _, _ in r.timeline]
        assert stamps == sorted(stamps)
    # requests are admitted in the order they came
    first = [_admit(r)["tick"] for r in reqs]
    assert first == sorted(first)


def test_the_ring_and_the_timeline_are_fed_by_the_same_call(served):
    eng, reqs = served
    ring = list(eng.sched_trace)
    for name in ("admit", "prefill", "activate", "finish"):
        in_ring = [e for e in ring if e[0] == name]
        on_timelines = [d for r in reqs for _, e, d in r.timeline if e == name]
        assert len(in_ring) == len(on_timelines) == len(reqs), name
    # the ring keeps its tuple shapes
    assert {len(e) for e in ring if e[0] == "admit"} == {4}
    assert all(e[3] == "chunked" for e in ring if e[0] == "admit")
    assert {e for e in ring if e[0] == "prefill"} == {("prefill", 0, 64), ("prefill", 1, 64)}
    assert ("decode", 4) in ring


def test_an_engine_nothing_was_asked_of_waits_empty():
    eng = _engine()
    try:
        _wait_for(lambda: eng.sched_stats.get(P + "wait_empty", [0, 0])[1] >= 2,
                  "two empty waits")
        before = eng.sched_stats[P + "tick"][1]
        # the table advances with every pass, with no profiler open
        _wait_for(lambda: eng.sched_stats[P + "tick"][1] > before, "another pass")
    finally:
        eng.close()
    st = _stats(eng)
    assert "wait_blocked" not in st and "decode" not in st and "release" not in st
    assert st["wait_empty"][1] == st["wait"][1] == st["tick"][1]
    assert st["wait_empty"][0] <= st["wait"][0] <= st["tick"][0]
    assert eng._wait_cause() == ""


def test_a_request_that_waits_for_its_adapter_blocks_the_wait(tmp_path):
    from datatunerx_tpu.serving.adapters import make_adapter_sweep

    cks = make_adapter_sweep(str(tmp_path), MODEL, 1, ranks=(2,))
    eng = _engine(adapters=cks, adapter_pool=1, adapter_rank_max=8)
    gate = threading.Event()
    load = eng.adapter_registry._loader

    def held(path):
        assert gate.wait(120), "the test never let the load go"
        return load(path)

    eng.adapter_registry._loader = held
    try:
        name = sorted(cks)[0]
        req = eng.submit(eng.tokenizer.encode("adapter wait probe"),
                         max_new_tokens=6, adapter=name)
        _wait_for(lambda: eng.sched_stats.get(P + "wait_blocked", [0, 0])[1] >= 1,
                  "a blocked wait")
        # while the checkpoint reads, every sleep of the scheduler is a blocked one
        empty = eng.sched_stats.get(P + "wait_empty", [0, 0])[1]
        blocked = eng.sched_stats[P + "wait_blocked"][1]
        _wait_for(lambda: eng.sched_stats[P + "wait_blocked"][1] >= blocked + 2,
                  "two more blocked waits")
        assert eng.sched_stats.get(P + "wait_empty", [0, 0])[1] == empty
        assert eng._wait_cause() == "adapter" and not req.done.is_set()
        gate.set()
        assert req.done.wait(300) and req.error is None
    finally:
        gate.set()
        eng.close()
    admit = _admit(req)
    assert admit["waited_for"] == "adapter" and admit["waited_ticks"] >= 3
    assert ("adapter_wait", name) in list(eng.sched_trace)
    assert [e for _, e, _ in req.timeline][:2] == ["adapter", "admit"]
    assert req.timeline[0][2]["loaded"] is True and "tick" in req.timeline[0][2]


def test_a_lone_request_waited_for_nothing_but_the_tick(served):
    eng, reqs = served
    admit = _admit(reqs[0])
    assert admit["waited_for"] == "tick" and admit["waited_ticks"] <= 1
    assert (admit["slot"], admit["plen"], admit["mode"]) == (0, 64, "chunked")
    # five over two slots: the last could only take a slot another gave up
    last = _admit(reqs[-1])
    assert last["waited_for"] == "slot" and last["waited_ticks"] > 1


def test_the_17th_of_17_on_16_slots_waited_for_a_slot():
    eng = _engine(slots=16, max_seq_len=128)
    try:
        ids = eng.tokenizer.encode("seventeen on sixteen")
        all_in = _hold_ticks_until(eng)  # the next pass sees all seventeen
        reqs = [eng.submit(ids, max_new_tokens=24) for _ in range(17)]
        all_in.set()
        for r in reqs:
            assert r.done.wait(600) and r.error is None
    finally:
        eng.close()
    assert _admit(reqs[0])["waited_for"] == "tick"
    last = _admit(reqs[16])
    assert last["waited_for"] == "slot"
    # sixteen were decoding when it came: it waited out one of them, six chunks of four
    assert last["waited_ticks"] >= 6
    assert {_admit(r)["waited_for"] for r in reqs[:16]} <= {"tick"}
    st = _stats(eng)
    # the sixteen end in one chunk and are given up by one span, the last alone
    assert (st["release"][1], st["complete"][1]) == (2, 17)


def test_a_pool_too_small_for_two_makes_the_second_wait_for_blocks():
    # 64 prompt tokens + 32 new ones are 6 blocks of 16; the pool holds 8
    eng = _engine(kv_blocks=8, max_seq_len=128)
    try:
        ids = eng.tokenizer.encode("block pool probe")
        a = eng.submit(ids, max_new_tokens=32)
        b = eng.submit(ids, max_new_tokens=32)
        assert a.done.wait(300) and b.done.wait(300)
        assert a.error is None and b.error is None
    finally:
        eng.close()
    assert _admit(a)["waited_for"] == "tick"
    assert _admit(b)["waited_for"] == "blocks" and _admit(b)["waited_ticks"] >= 8
    assert _admit(b)["tick"] >= [d for _, e, d in a.timeline if e == "finish"][0]["tick"]
    assert eng.free_kv_blocks == eng.total_kv_blocks == 8
    # a slot was free all along: b never waited while the engine slept
    assert "wait_blocked" not in _stats(eng)


def test_a_request_that_fails_in_prefill_is_released_and_completed_once():
    eng = _engine()
    try:
        def broken(*a, **kw):
            raise RuntimeError("chunk program refused")

        good = eng._prefill_chunk_fn
        eng._prefill_chunk_fn = broken
        bad = eng.submit(eng.tokenizer.encode("doomed"), max_new_tokens=4,
                         trace_id="phase-doomed")
        assert bad.done.wait(120)
        assert "chunk program refused" in bad.error
        eng._prefill_chunk_fn = good
        ok = eng.submit(eng.tokenizer.encode("survivor"), max_new_tokens=4)
        assert ok.done.wait(300) and ok.error is None
    finally:
        eng.close()
    st = _stats(eng)
    assert st["release"][1] == st["complete"][1] == 2
    assert [e for _, e, _ in bad.timeline] == ["admit"]
    span = eng.trace_store.get("phase-doomed")["spans"][0]
    assert span["status"] == "error" and span["events"][0]["waited_for"] == "tick"
    assert eng.free_kv_blocks == eng.total_kv_blocks


def test_a_failed_decode_releases_and_completes_every_live_request():
    eng = _engine()
    try:
        def broken(*a, **kw):
            raise RuntimeError("decode program refused")

        eng._decode = broken
        reqs = [eng.submit(eng.tokenizer.encode(f"in flight {i}"), max_new_tokens=8)
                for i in range(2)]
        for r in reqs:
            assert r.done.wait(120) and "decode program refused" in r.error
    finally:
        eng.close()
    st = _stats(eng)
    assert st["release"][1] == st["complete"][1] == 2
    assert "emit" not in st and "decode_sync" not in st


def test_preemption_releases_without_completing():
    eng = _engine(slots=4, kv_blocks=20, kv_overcommit="on")
    released = _releases(eng)
    try:
        prompts = [eng.tokenizer.encode(f"victim ordering probe {i}") for i in range(4)]
        reqs = [eng.submit(p, max_new_tokens=64) for p in prompts]
        for r in reqs:
            assert r.done.wait(300) and r.error is None
    finally:
        eng.close()
    parked = (eng.preempt_stats.get("exported", 0)
              + eng.preempt_stats.get("requeued_prefill", 0))
    assert parked >= 1, "the pool never contended: the test proves nothing"
    st = _stats(eng)
    assert st["complete"][1] == len(reqs)
    # every slot given up is in one span: a preemption's alone, those of the
    # requests a chunk ended together
    assert sum(released) == len(reqs) + parked
    assert parked + 1 <= st["release"][1] == len(released) <= len(reqs) + parked
    marks = [(e, d) for r in reqs for _, e, d in r.timeline]
    assert all("tick" in d for _, d in marks)
    assert sum(1 for e, _ in marks if e == "preempt") == parked
    assert sum(1 for e, _ in marks if e == "resume") == eng.preempt_stats.get("resumed", 0)
    for r in reqs:
        ticks = [d["tick"] for _, _, d in r.timeline]
        assert ticks == sorted(ticks)


@pytest.fixture()
def http_engine(served):
    """The closed engine behind a real serving handler: its trace ring and
    its table still answer."""
    from datatunerx_tpu.serving import server as serving

    old_engine, old_model = serving.STATE.engine, serving.STATE.model_path
    serving.STATE.engine, serving.STATE.model_path = served[0], MODEL
    srv = ThreadingHTTPServer(("127.0.0.1", 0), serving.Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()
    serving.STATE.engine, serving.STATE.model_path = old_engine, old_model


def test_debug_trace_shows_why_a_request_queued_and_in_which_tick(http_engine, served):
    with urllib.request.urlopen(http_engine + "/debug/trace/phase-4", timeout=10) as r:
        doc = json.load(r)
    events = doc["spans"][0]["events"]
    admit = next(e for e in events if e["name"] == "admit")
    assert admit["waited_for"] == "slot" and admit["waited_ticks"] > 1
    ticked = [e for e in events if e["name"] != "first_token"]
    assert all(isinstance(e["tick"], int) for e in ticked)
    assert admit["tick"] == _admit(served[1][4])["tick"]


def test_metrics_state_the_table_under_the_spans_names(http_engine, served):
    from tests.test_prometheus_exposition import parse_exposition

    with urllib.request.urlopen(http_engine + "/metrics", timeout=10) as r:
        text = r.read().decode()
    samples, types = parse_exposition(text)
    assert types["dtx_serving_sched_seconds_total"] == "counter"
    assert types["dtx_serving_sched_phases_total"] == "counter"
    st = _stats(served[0])
    seconds = {dict(lb)["phase"]: v for (n, lb), v in samples.items()
               if n == "dtx_serving_sched_seconds_total"}
    counts = {dict(lb)["phase"]: v for (n, lb), v in samples.items()
              if n == "dtx_serving_sched_phases_total"}
    assert set(seconds) == set(counts) == set(st)
    for name, (secs, n) in st.items():
        assert seconds[name] == pytest.approx(secs) and counts[name] == n
    # the operator's reading: the share of its time the replica had nothing to do
    assert 0.0 <= seconds["wait_empty"] / seconds["tick"] <= 1.0


def test_an_engine_without_the_table_exports_the_families_empty():
    import types

    from datatunerx_tpu.obs.metrics import Registry, export_sched_stats

    reg = Registry()
    export_sched_stats(reg, types.SimpleNamespace())
    text = reg.expose()
    assert "# TYPE dtx_serving_sched_seconds_total counter" in text
    assert "dtx_serving_sched_seconds_total{" not in text
    export_sched_stats(reg, types.SimpleNamespace(
        sched_stats={"dtx_engine_wait_empty": [1.5, 3]}))
    text = reg.expose()
    assert 'dtx_serving_sched_seconds_total{phase="wait_empty"} 1.5' in text
    assert 'dtx_serving_sched_phases_total{phase="wait_empty"} 3' in text


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_greedy_outputs_do_not_depend_on_tracing(paged):
    kw = {} if paged else {"kv_block_size": 0}
    on, off = _engine(tracing=True, **kw), _engine(tracing=False, **kw)
    try:
        prompts = [on.tokenizer.encode(f"the tracer must not move token {i}")
                   for i in range(3)]
        got = {}
        for name, eng in (("on", on), ("off", off)):
            reqs = [eng.submit(list(p), max_new_tokens=12) for p in prompts]
            for r in reqs:
                assert r.done.wait(300) and r.error is None
            got[name] = [r.tokens for r in reqs]
            assert all(bool(r.timeline) == (name == "on") for r in reqs)
        assert got["on"] == got["off"]
        assert len(off.trace_store) == 0 and len(on.trace_store) == 3
    finally:
        on.close()
        off.close()
    # the table is kept whether or not request tracing is on
    assert _stats(off)["complete"][1] == _stats(on)["complete"][1] == 3
