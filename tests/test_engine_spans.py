"""The scheduler's tick-phase spans and the names inside the programs.

Host side: a debug-size paged engine with a dynamic adapter pool serves a few
requests inside ``jax.profiler.start_trace`` on the CPU; the ``.xplane.pb`` is
read back with ``jax.profiler.ProfileData`` and every span of the engine's
table is looked up under its bare name (keywords of an annotation land in the
event's stats, not in its name). Device side: the decode program and a training
step are lowered at debug size and every ``dtx.`` scope is looked up in the
HLO's ``op_name`` metadata, every ``pallas_call``'s ``name`` in the jaxpr.
"""

import dataclasses
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from datatunerx_tpu.models.config import PRESETS
from datatunerx_tpu.models.llama import forward, init_params
from datatunerx_tpu.serving.batched_engine import BatchedEngine

MODEL = "preset:debug"

TICK = "dtx_engine_tick"
# children of a tick, by the scheduler thread's stack
TICK_PHASES = (
    "dtx_engine_migrate", "dtx_engine_resume", "dtx_engine_admit",
    "dtx_engine_prefill_chunk", "dtx_engine_activate", "dtx_engine_grow",
    "dtx_engine_decode", "dtx_engine_decode_sync", "dtx_engine_emit",
    "dtx_engine_wait",
)


@pytest.fixture(scope="module")
def host_events(tmp_path_factory):
    """[(name, start_ns, end_ns, thread, stats)] of every ``dtx_`` span the
    engine wrote while it served four requests under the profiler."""
    from datatunerx_tpu.serving.adapters import make_adapter_sweep

    work = tmp_path_factory.mktemp("spans")
    cks = make_adapter_sweep(str(work / "adapters"), MODEL, 2, ranks=(2,))
    eng = BatchedEngine(MODEL, adapters=cks, adapter_pool=2,
                        adapter_rank_max=8, template="vanilla",
                        max_seq_len=256, slots=2, decode_chunk=4,
                        kv_block_size=16, prefill_chunk=64,
                        prefill_token_budget=64)
    trace_dir = str(work / "trace")
    try:
        short = eng.tokenizer.encode("the quick brown fox")
        long = (eng.tokenizer.encode("long context ") * 40)[:150]
        # compile outside the trace: the spans of a tick that compiles tell
        # nothing about a tick
        eng.generate(short, max_new_tokens=4)
        eng.generate(long, max_new_tokens=4)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            reqs = [eng.submit(short, max_new_tokens=6)]
            reqs += [eng.submit(long if i else short, max_new_tokens=6,
                                adapter=name)
                     for i, name in enumerate(sorted(cks))]
            reqs.append(eng.submit(short, max_new_tokens=5, temperature=0.8,
                                   seed=3))
            for r in reqs:
                assert r.done.wait(300) and r.error is None, r.error
            time.sleep(0.3)  # idle ticks: the wait span
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        # a line is a host thread; on the CPU they all carry one name, so
        # the line's index is the thread's identity here
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("dtx_"):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns, thread,
                                   dict(ev.stats)))
    # the passes under way when the session opened and when it closed have
    # phases in the trace and no tick: keep the ticks the session saw whole
    first = min(e[1] for e in events if e[0] == TICK)
    last = max(e[2] for e in events if e[0] == TICK)
    return [e for e in events
            if first <= e[1] and e[2] <= last or e[0] == "dtx_adapter_load"]


def _ticks(events):
    return [e for e in events if e[0] == TICK]


def _inside(ev, outer):
    return outer[3] == ev[3] and outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.mark.parametrize("name", TICK_PHASES)
def test_tick_phase_is_present_under_its_bare_name_inside_a_tick(
        host_events, name):
    found = [e for e in host_events if e[0] == name]
    assert found, f"{name} never written; have {sorted({e[0] for e in host_events})}"
    ticks = _ticks(host_events)
    for ev in found:
        assert any(_inside(ev, t) for t in ticks), f"{name} outside any tick"


def test_tick_span_is_one_per_pass_and_never_nested(host_events):
    ticks = sorted(_ticks(host_events), key=lambda e: e[1])
    assert len(ticks) >= 4
    assert len({t[3] for t in ticks}) == 1  # the scheduler's one thread
    for a, b in zip(ticks, ticks[1:]):
        assert a[2] <= b[1]


def test_adapter_acquire_lies_inside_admit(host_events):
    acquires = [e for e in host_events if e[0] == "dtx_engine_adapter_acquire"]
    admits = [e for e in host_events if e[0] == "dtx_engine_admit"]
    assert acquires
    for ev in acquires:
        assert any(_inside(ev, a) for a in admits)


def test_adapter_load_runs_on_a_thread_of_its_own(host_events):
    loads = [e for e in host_events if e[0] == "dtx_adapter_load"]
    assert len(loads) == 2  # two adapters, each a miss once
    # not on the scheduler's stack: decode keeps ticking while it reads
    assert _ticks(host_events)[0][3] not in {ev[3] for ev in loads}


def test_existing_span_names_read_exactly_and_keywords_go_to_stats(host_events):
    names = {e[0] for e in host_events}
    # benchmarks/readers.py matches these two strings with ==
    assert "dtx_engine_decode" in names and "dtx_engine_prefill_chunk" in names
    assert not [n for n in names if "#" in n or "=" in n]
    decode = [e for e in host_events if e[0] == "dtx_engine_decode"]
    assert all(1 <= int(e[4]["live"]) <= 2 for e in decode)
    chunks = [e for e in host_events if e[0] == "dtx_engine_prefill_chunk"]
    # the 150-token prompt is padded to 192 and prefilled in chunks of 64
    assert sorted(int(e[4]["tokens"]) for e in chunks).count(64) >= 3
    assert {int(e[4]["slot"]) for e in chunks} <= {0, 1}


def test_children_cover_every_tick_that_dispatched_a_decode(host_events):
    """A tick that dispatches a decode HAS its named children, in the
    scheduler's order and one after the other, and no engine span lies in
    what they leave uncovered: every other ``dtx_engine_*`` span of the tick
    is inside one of them. (Not a share of the tick's wall clock: what the OS
    and the profiler take between two spans is no span's.)"""
    head = ["dtx_engine_migrate", "dtx_engine_resume", "dtx_engine_admit"]
    tail = ["dtx_engine_grow", "dtx_engine_decode", "dtx_engine_decode_sync",
            "dtx_engine_emit"]
    decoded = 0
    for t in _ticks(host_events):
        inside = sorted((e for e in host_events
                         if e[0].startswith("dtx_engine_") and e[0] != TICK
                         and _inside(e, t)), key=lambda e: (e[1], e[2]))
        kids = [e for e in inside if e[0] in TICK_PHASES]
        names = [e[0] for e in kids]
        if "dtx_engine_decode" not in names:
            continue
        decoded += 1
        assert names[:3] == head and names[-4:] == tail, names
        assert set(names[3:-4]) <= {"dtx_engine_prefill_chunk",
                                    "dtx_engine_activate"}, names
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
        for e in inside:
            assert e[0] in TICK_PHASES or any(_inside(e, k) for k in kids), (
                f"{e[0]} lies under no child of its tick: {names}")
    assert decoded >= 3


# ------------------------------------------------ names inside the programs

LAYER_SCOPES = ("dtx.layers", "dtx.qkv", "dtx.kv_write", "dtx.attn",
                "dtx.attn_out", "dtx.mlp", "dtx.unembed", "dtx.lora",
                "dtx.sample")


@pytest.fixture(scope="module")
def decode_program_text():
    """The engine's decode program, lowered at debug size with the Pallas
    paged kernel and the fused sampler (interpret mode on the CPU): compiled
    HLO text with ``op_name`` metadata, and the jaxpr."""
    from datatunerx_tpu.ops.paged_attention import init_paged_cache
    from datatunerx_tpu.serving.batched_engine import MAX_STOP, _Programs

    cfg = dataclasses.replace(PRESETS["debug"], paged_kernel=True)
    slots, rank, adapters = 2, 4, 3
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    cache = init_paged_cache(cfg, slots, num_blocks=8, block_size=16,
                             blocks_per_slot=4)
    L = cfg.num_layers
    lora = ({t: {"a": jnp.zeros((L, adapters, cfg.hidden_size, rank), jnp.bfloat16),
                 "b": jnp.zeros((L, adapters, rank, d), jnp.bfloat16)}
             for t, d in (("q_proj", cfg.q_dim), ("v_proj", cfg.kv_dim))},
            jnp.ones((adapters,), jnp.float32))
    progs = _Programs(cfg, 64, None, epilogue="kernel")
    args = (params, lora, cache, jnp.zeros((slots, cfg.vocab_size), jnp.float32),
            jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), jnp.int32),
            jnp.ones((slots,), bool), jnp.zeros((slots, 2), jnp.uint32),
            jnp.ones((slots,), jnp.float32), jnp.ones((slots,), jnp.float32),
            jnp.full((slots, MAX_STOP), -1, jnp.int32),
            jnp.zeros((slots,), jnp.int32))
    lowered = progs.decode.lower(*args, K=2, mode="simple")
    jaxpr = jax.make_jaxpr(
        lambda *a: progs._decode_impl(*a, K=2, mode="simple"))(*args)
    # the compiled text: XLA has inlined the scan bodies there, so an op's
    # op_name is its whole path, as the device trace's HLO has it
    return lowered.compile().as_text(), str(jaxpr)


@pytest.mark.parametrize("scope", LAYER_SCOPES)
def test_decode_program_carries_each_scope_in_op_name_metadata(
        decode_program_text, scope):
    hlo, _ = decode_program_text
    assert f"/{scope}/" in hlo or f"/{scope}\"" in hlo, scope


def test_decode_program_nests_layer_regions_under_dtx_layers(decode_program_text):
    hlo, _ = decode_program_text
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = {tuple(p for p in path.split("/") if p.startswith("dtx"))
              for path in paths if path.startswith("jit(_decode_impl)/")}
    for inner in ("dtx.qkv", "dtx.kv_write", "dtx.attn", "dtx.attn_out", "dtx.mlp"):
        assert ("dtx.layers", inner) in scopes, inner
    assert ("dtx.layers", "dtx.qkv", "dtx.lora") in scopes
    assert ("dtx.layers", "dtx.attn", "dtx_paged_decode") in scopes
    # what the scan itself moves carries dtx.layers and no inner scope
    assert ("dtx.layers",) in scopes
    # the sampler and the unembedding are outside the layer scan
    assert ("dtx.sample",) in scopes and ("dtx.unembed",) in scopes
    assert not [s for s in scopes if s[:1] == ("dtx.layers",)
                and {"dtx.sample", "dtx.unembed"} & set(s)]


@pytest.mark.parametrize("kernel", ("dtx_paged_decode", "dtx_fused_sample"))
def test_decode_program_names_its_pallas_calls(decode_program_text, kernel):
    hlo, jaxpr = decode_program_text
    assert f"name={kernel}" in jaxpr
    assert f"/{kernel}/" in hlo or f"/{kernel}\"" in hlo


def test_training_step_carries_scopes_through_remat_and_transpose():
    cfg = dataclasses.replace(PRESETS["debug"], remat="full")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    L, r = cfg.num_layers, 4
    lora = {t: {"a": jnp.ones((L, cfg.hidden_size, r), jnp.bfloat16) * 0.01,
                "b": jnp.zeros((L, r, d), jnp.bfloat16)}
            for t, d in (("q_proj", cfg.q_dim), ("v_proj", cfg.kv_dim))}
    tokens = jnp.zeros((2, 32), jnp.int32)

    def loss(lora):
        logits, _ = forward(params, tokens, cfg, lora=(lora, 2.0),
                            compute_dtype=jnp.bfloat16)
        return jnp.mean(logits ** 2)

    hlo = jax.jit(jax.grad(loss)).lower(lora).compile().as_text()
    for scope in ("dtx.layers", "dtx.qkv", "dtx.attn", "dtx.attn_out",
                  "dtx.mlp", "dtx.unembed", "dtx.lora"):
        assert f"{scope}/" in hlo or f"{scope})" in hlo, scope
    # the recomputed forward is marked by jax.checkpoint's own name, which is
    # what train.recompute_share reads, and keeps the scopes inside it
    assert "rematted_computation/dtx.attn/" in hlo
    assert "transpose(jvp(dtx.layers))" in hlo


@pytest.mark.parametrize("module,names", [
    ("pallas_paged_attention", ("dtx_paged_decode", "dtx_paged_multitoken")),
    ("pallas_sampling", ("dtx_fused_sample",)),
    ("flash_attention", ("dtx_flash_fwd", "dtx_flash_bwd_dq", "dtx_flash_bwd_dkv")),
    ("pallas_lora", ("dtx_lora_fused",)),
    ("pallas_quant", ("dtx_quant_int8", "dtx_quant_nf4", "dtx_quant_nf4_t")),
])
def test_every_pallas_call_is_given_a_name(module, names):
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "datatunerx_tpu", "ops", module + ".py")) as f:
        src = f.read()
    calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(", src)]
    given = re.findall(r'\bname="(dtx_[a-z0-9_]+)"', src)
    assert len(calls) == len(names) and sorted(given) == sorted(names)


@pytest.mark.parametrize("window,built_with", [(32, 32), (512, None)])
def test_decode_path_reports_the_path_a_windowed_model_takes(
        monkeypatch, capfd, window, built_with):
    windowed = dataclasses.replace(PRESETS["debug"], name="debug-window",
                                   sliding_window=window)
    monkeypatch.setitem(PRESETS, "debug-window", windowed)
    eng = BatchedEngine("preset:debug-window", template="vanilla",
                        max_seq_len=256, slots=2, decode_chunk=4,
                        kv_block_size=16, paged_kernel="on")
    try:
        # forward() hands the token step to the kernel, window and all; one
        # the cache of 256 lanes cannot exceed is dropped there
        assert eng.paged_kernel and eng.decode_path == "pallas"
        assert eng.decode_paths == {"window": "pallas"}
        assert eng.decode_window == built_with
        lines = [ln for ln in capfd.readouterr().err.splitlines()
                 if ln.startswith("[engine] ")]
        assert json.loads(lines[0][len("[engine] "):])[
            "decode_window"] == built_with
        assert f"sliding_window={window}" in lines[1]
        assert ("dropped" in lines[1]) == (built_with is None)
        from datatunerx_tpu.serving import server as serving

        monkeypatch.setattr(serving.STATE, "engine", eng)
        text = serving.metrics_text()
        assert 'dtx_serving_decode_path{kind="window",path="pallas"} 1' in text
        assert [ln for ln in text.splitlines()
                if ln.startswith("dtx_serving_decode_window")] == (
            ["dtx_serving_decode_window 32"] if built_with else [])
        assert eng.generate(eng.tokenizer.encode("a b c"), max_new_tokens=3)
    finally:
        eng.close()


@pytest.mark.parametrize("preset,engine_kw,paths,word", [
    # a sink-less global kind takes the kernel, a window kind with a sink its view
    ("debug-hybrid", dict(paged_kernel="on"), {"global": "pallas", "window": "gather"},
     "gather+pallas"),
    ("debug-hybrid", dict(paged_kernel="off"), {"global": "gather", "window": "gather"}, "gather"),
    # state-space layers attend to nothing; the attention layers take the kernel
    ("debug-granite", dict(paged_kernel="on"), {"global": "pallas"}, "pallas"),
    # a latent pool has no kernel: a gathered view or its chosen rows
    ("debug-ling", dict(paged_kernel="on"), {"mla": "gather"}, "gather"),
    ("debug-granite", dict(paged_kernel="auto", kv_block_size=0), {"global": "dense"}, "dense"),
])
def test_decode_path_reports_what_each_attending_kinds_token_step_takes(
        monkeypatch, capfd, preset, engine_kw, paths, word):
    """The ``[engine]`` line, ``decode_paths`` / ``decode_path`` and the gauge
    say per attending kind what ``forward`` does with a token step, not what
    the flag asked for."""
    eng = BatchedEngine("preset:" + preset, **dict(dict(
        max_seq_len=128, slots=2, decode_chunk=4, kv_block_size=8), **engine_kw))
    try:
        assert eng.decode_paths == paths and eng.decode_path == word
        assert eng.decode_window is None
        line = [ln for ln in capfd.readouterr().err.splitlines()
                if ln.startswith("[engine] {")][0]
        doc = json.loads(line[len("[engine] "):])
        assert doc["decode_paths"] == paths and doc["decode_path"] == word
        from datatunerx_tpu.serving import server as serving

        monkeypatch.setattr(serving.STATE, "engine", eng)
        assert sorted(ln for ln in serving.metrics_text().splitlines()
                      if ln.startswith("dtx_serving_decode_path{")) == sorted(
            'dtx_serving_decode_path{kind="%s",path="%s"} 1' % kv
            for kv in paths.items())
    finally:
        eng.close()
