"""A model whose layers are of several kinds (models/hybrid.py): window and
global attention layers with KV geometry of their own, sparse experts of which
this chip holds a share. Every numeric test is against the plain reference
``benchmarks/reference/mimo_v2.py`` (float32, no cache, no batching), at the
``debug-hybrid`` size on seeded weights."""

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from reference import mimo_v2 as ref  # noqa: E402

from datatunerx_tpu.models import forward, get_config, init_params  # noqa: E402
from datatunerx_tpu.models.config import layer_runs  # noqa: E402
from datatunerx_tpu.models.llama import init_cache  # noqa: E402
from datatunerx_tpu.ops import moe  # noqa: E402
from datatunerx_tpu.ops.paged_attention import (  # noqa: E402
    init_paged_cache,
    kv_leaf_keys,
    paged_extract_row,
    paged_insert_row,
)

TOL = 2e-5  # float32 program against float32 reference: rounding order only
T = 70


@pytest.fixture(scope="module")
def model():
    cfg = get_config("debug-hybrid")
    params = init_params(cfg, jax.random.PRNGKey(0))
    # a drawn router bias and sinks, so that both matter
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, cfg.vocab_size)
    return cfg, dataclasses.asdict(cfg), params, tokens


def _ref_logits(mc, params, tokens, **kw):
    return jnp.stack([ref.sequence_logits(params, mc, [int(t) for t in row],
                                          list(range(len(row))), **kw)
                      for row in np.asarray(tokens)])


@pytest.fixture(scope="module")
def want(model):
    _, mc, params, tokens = model
    return _ref_logits(mc, params, tokens)


def _positions(lo, hi, batch=2):
    return jnp.broadcast_to(jnp.arange(lo, hi, dtype=jnp.int32)[None], (batch, hi - lo))


@jax.jit
def _step(params, tokens, cache, positions, mask=None):
    """One forward through the cache, compiled once per shape (the debug
    preset is closed over: a test drives twenty decode steps through it)."""
    return forward(params, tokens, get_config("debug-hybrid"), cache=cache,
                   positions=positions, attention_mask=mask)


@jax.jit
def _step_in_place(params, tokens, cache, positions, mask=None):
    """``_step`` for an engine that asked for the paged kernels: over a paged
    cache a token step's global kind (no sink) reads its blocks in place
    through the decode kernel, interpreted here; the window kind, with its
    sink, and every other step read the gathered view."""
    return forward(params, tokens, get_config("debug-hybrid", paged_kernel=True),
                   cache=cache, positions=positions, attention_mask=mask)


STEPS = {"gather": _step, "kernel": _step_in_place}


def test_runs_of_like_layers(model):
    cfg = model[0]
    runs = layer_runs(cfg)
    assert [(r.mixer.name, r.ffn, r.count, r.kind_start) for r in runs] == [
        ("global", "dense", 1, 0), ("window", "experts", 3, 0), ("global", "experts", 1, 1)]
    assert runs[0].mixer.num_kv_heads == 1 and runs[1].mixer.num_kv_heads == 2
    assert runs[1].mixer.rotary_dim == 8 and runs[1].mixer.window == 24
    assert ref.runs_of(model[1]) == [(r.mixer.name, r.ffn, r.count) for r in runs]


def test_full_forward_equals_reference(model, want):
    cfg, _, params, tokens = model
    got, cache = forward(params, tokens, cfg)
    assert cache is None
    np.testing.assert_allclose(got, want, atol=TOL)


def test_dense_cache_prefill_then_decode_equals_reference(model, want):
    """The window (24) is shorter than the prompt (50)."""
    cfg, _, params, tokens = model
    cache = init_cache(cfg, 2, 128, dtype=jnp.float32, per_slot=True)
    assert cache["k_global"].shape == (2, 2, 128, 1 * 24)   # heads and width are one axis
    assert cache["v_window"].shape == (3, 2, 128, 2 * 16)
    out, cache = _step(params, tokens[:, :50], cache, _positions(0, 50))
    outs = [out]
    for t in range(50, T):
        out, cache = _step(params, tokens[:, t:t + 1], cache, _positions(t, t + 1))
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=TOL)
    decode, prefill = np.asarray(cache["moe_stats"])
    assert decode[3] == 4 * (T - 50) and prefill[3] == 4  # expert layers a step


@pytest.mark.parametrize("path", sorted(STEPS))
@pytest.mark.parametrize("block_size,chunks", [
    (8, ((0, 32), (32, 50))),   # a block boundary inside the window; chunks wider than it
    (16, ((0, 50),)),
    (4, ((0, 16), (16, 32), (32, 50))),
])
def test_paged_pool_chunked_prefill_then_decode_equals_reference(model, want, block_size, chunks, path):
    cfg, _, params, tokens = model
    _step = STEPS[path]
    nbps = 128 // block_size
    cache = init_paged_cache(cfg, 2, 2 * nbps + 3, block_size, nbps, dtype=jnp.float32)
    # slot 1's blocks first, so that tables are no identity
    cache["block_tables"] = jnp.asarray(
        np.stack([np.arange(nbps) + nbps, np.arange(nbps)]), jnp.int32)
    outs = []
    for lo, hi in chunks:
        assert hi - lo > 0
        out, cache = _step(params, tokens[:, lo:hi], cache, _positions(lo, hi))
        outs.append(out)
    for t in range(50, T):
        out, cache = _step(params, tokens[:, t:t + 1], cache, _positions(t, t + 1))
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=TOL)


@pytest.mark.parametrize("path", sorted(STEPS))
def test_left_padded_rows_read_the_window_view(model, want, path):
    """Pads lie at a row's left: linear index and position differ by a constant
    (and the kernel's walk is bounded by the lane cursor, not the position)."""
    cfg, _, params, tokens = model
    _step = STEPS[path]
    pad = 6
    cache = init_paged_cache(cfg, 2, 40, 8, 16, dtype=jnp.float32)
    cache["block_tables"] = jnp.asarray(np.arange(32).reshape(2, 16), jnp.int32)
    ids = jnp.concatenate([jnp.zeros((2, pad), tokens.dtype), tokens[:, :50]], axis=1)
    mask = jnp.concatenate([jnp.zeros((2, pad), jnp.int32), jnp.ones((2, 50), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.zeros((2, pad), jnp.int32), _positions(0, 50)], axis=1)
    out, cache = _step(params, ids, cache, pos, mask)
    outs = [out[:, pad:]]
    for t in range(50, T):
        out, cache = _step(params, tokens[:, t:t + 1], cache, _positions(t, t + 1))
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=TOL)


def _traced(cfg, cache, n):
    """The program ``forward`` traces for a step of ``n`` tokens, as text."""
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return str(jax.make_jaxpr(lambda p, ids, c: forward(
        p, ids, cfg, cache=c, positions=_positions(40, 40 + n)))(
            params, jnp.zeros((2, n), jnp.int32), cache))


@pytest.mark.parametrize("step,calls", [("paged_token", 2), ("paged_chunk", 0), ("dense_token", 0),
                                        ("dense_chunk", 0)])
def test_only_a_token_step_over_a_paged_cache_takes_the_kernel(model, step, calls):
    """An engine that asked for the paged kernels traces the program it traced
    without them for every step but one: a single token over a paged cache,
    where each run of global layers (two: one with a dense feed-forward, one
    with experts) calls the decode kernel and no view of that kind is gathered
    ([2, 128, 1, 24]: every slot's table through k_global). The window kind's
    view stays."""
    cfg = model[0]
    asked = dataclasses.replace(cfg, paged_kernel=True)
    kind, n = step.split("_")
    cache = (init_paged_cache(cfg, 2, 35, 8, 16, dtype=jnp.float32) if kind == "paged"
             else init_cache(cfg, 2, 128, dtype=jnp.float32, per_slot=True))
    plain, got = (_traced(c, cache, 1 if n == "token" else 8) for c in (cfg, asked))
    assert got.count("dtx_paged_decode") == calls and "dtx_paged_decode" not in plain
    if calls:
        assert "f32[2,128,1,24]" in plain and "f32[2,128,1,24]" not in got
        assert "f32[2,32,2,24]" in got  # the window kind's view: 4 columns of 8 a slot
    else:
        assert got == plain


@pytest.mark.parametrize("name,change,least", [
    ("sink", {"window_sink": False}, 1e-2),
    ("partial_rotation", {"partial_rotary_factor": 1.0}, 1e-3),
    ("value_scale", {"attention_value_scale": 1.0}, 1e-2),
    ("two_thetas", {"window_rope_theta": 1e7}, 5e-4),
    ("correction_bias_in_selection", {"no_correction": True}, 1e-2),
    ("correction_bias_not_in_weights", {"correction_in_weights": True}, 1e-3),
    ("weights_normalised", {"norm_topk_prob": False}, 1e-3),
])
def test_each_mechanism_matters(model, want, name, change, least):
    """The reference with the mechanism off differs from the program by far
    more than the tolerance: the agreement above is not blind to it."""
    cfg, mc, params, tokens = model
    off = _ref_logits(dict(mc, **change), params, tokens[:1])
    got, _ = forward(params, tokens[:1], cfg)
    assert float(jnp.abs(off - got).max()) > max(least, 20 * TOL), name


def test_shares_add_up_to_the_uncut_layer(model):
    """The parts that all experts_total / experts_held shares give for one
    expert layer add up to the uncut reference's layer."""
    cfg, mc, _, _ = model
    whole = dataclasses.replace(cfg, experts_held=cfg.experts_total)
    params = init_params(whole, jax.random.PRNGKey(5))
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["run1"])
    h = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.hidden_size), jnp.float32)
    uncut = ref.expert_ffn(h, lp, dict(mc, experts_held=cfg.experts_total), "f32") - h
    normed = ref.rms_norm(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    total, rows = jnp.zeros_like(h), 0
    shares = cfg.experts_total // cfg.experts_held
    for s in range(shares):
        first = s * cfg.experts_held
        held = dict(lp, experts=jax.tree_util.tree_map(
            lambda a: a[first:first + cfg.experts_held], lp["experts"]))
        part, stats = moe.expert_layer(
            normed, None, held, experts_total=cfg.experts_total,
            experts_held=cfg.experts_held, first_held=first,
            top_k=cfg.experts_per_token, normalize=True, scaling=1.0)
        assert float(jnp.abs(part).max()) > 0
        total, rows = total + part, rows + int(stats[0])
    assert rows == 40 * cfg.experts_per_token  # every pair is some share's
    np.testing.assert_allclose(total, uncut, atol=TOL)
    # and a single share is the reference's share
    one = ref.expert_ffn(h, dict(lp, experts=jax.tree_util.tree_map(
        lambda a: a[4:8], lp["experts"])), dict(mc, first_held=4), "f32") - h
    np.testing.assert_allclose(part, one, atol=TOL)


def test_rows_of_pads_are_routed_nowhere(model):
    cfg, _, params, _ = model
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["run1"])
    h = jax.random.normal(jax.random.PRNGKey(7), (16, cfg.hidden_size), jnp.float32)
    valid = jnp.arange(16) < 10
    kw = dict(experts_total=8, experts_held=4, first_held=0, top_k=2,
              normalize=True, scaling=1.0)
    y, stats = moe.expert_layer(h, valid, lp, **kw)
    y10, stats10 = moe.expert_layer(h[:10], None, lp, **kw)
    np.testing.assert_allclose(y[:10], y10, atol=1e-6)
    assert float(jnp.abs(y[10:]).max()) == 0.0
    assert list(np.asarray(stats)) == list(np.asarray(stats10))


def test_extract_insert_round_trips_both_pools(model):
    cfg, _, params, tokens = model
    cache = init_paged_cache(cfg, 2, 40, 8, 16, dtype=jnp.float32)
    cache["block_tables"] = jnp.asarray(np.arange(32).reshape(2, 16), jnp.int32)
    _, cache = _step(params, tokens[:, :50], cache, _positions(0, 50))
    row = paged_extract_row(cache, 1, 50, width=56)
    assert sorted(kv_leaf_keys(row)) == ["k_global", "k_window", "v_global", "v_window"]
    assert row["k_window"].shape == (3, 1, 56, 2 * 24) and row["v_global"].shape == (2, 1, 56, 1 * 16)
    fresh = init_paged_cache(cfg, 2, 40, 8, 16, dtype=jnp.float32)
    table = jnp.asarray(list(range(20, 27)) + [-1] * 9, jnp.int32)
    fresh = paged_insert_row(fresh, 0, table, row)
    fresh["len"] = fresh["len"].at[0].set(50)
    back = paged_extract_row(fresh, 0, 50, width=56)
    for key in kv_leaf_keys(row) + ["pos"]:
        np.testing.assert_array_equal(back[key], row[key])
    # and decode goes on from the moved row as from the original
    a, _ = forward(params, tokens[1:2, 50:51], cfg, positions=_positions(50, 51, 1),
                   cache={k: (v[1:2] if k in ("len", "block_tables") else v)
                          for k, v in cache.items()})
    b, _ = forward(params, tokens[1:2, 50:51], cfg, positions=_positions(50, 51, 1),
                   cache={k: (v[0:1] if k in ("len", "block_tables") else v)
                          for k, v in fresh.items()})
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_migration_wire_carries_every_pool(model):
    from datatunerx_tpu.serving import migration as mig

    cfg, _, params, tokens = model
    cache = init_paged_cache(cfg, 2, 40, 8, 16, dtype=jnp.bfloat16)
    cache["block_tables"] = jnp.asarray(np.arange(32).reshape(2, 16), jnp.int32)
    _, cache = forward(params, tokens[:, :50], cfg, cache=cache, positions=_positions(0, 50),
                       compute_dtype=jnp.bfloat16)
    row = paged_extract_row(cache, 0, 50, width=64)
    doc = json.loads(json.dumps(mig.pack_kv_row(row, 50, "bf16")))
    assert set(doc["pools"]) == set(kv_leaf_keys(row)) and doc["width"] == 50
    back = mig.unpack_kv_row(doc, full_width=128, quantize=None)
    for key in kv_leaf_keys(row):
        assert back[key].shape[2] == 128
        np.testing.assert_array_equal(np.asarray(back[key][:, :, :50], np.float32),
                                      np.asarray(row[key][:, :, :50], np.float32))
    with pytest.raises(ValueError, match="bf16 only"):
        mig.pack_kv_row(row, 50, "int8")
    sig = mig.model_signature(cfg, None)
    assert sig["pools"] == {"global": [2, 1, 24, 16], "window": [3, 2, 24, 16]}
    other = dataclasses.replace(cfg, window_num_kv_heads=4)
    with pytest.raises(ValueError, match="incompatible model"):
        mig.check_signature({"model_sig": sig, "kind": mig.PAYLOAD_KIND,
                             "version": mig.PAYLOAD_VERSION}, other)


# ------------------------------------------------------------ the engine

@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    d = tmp_path_factory.mktemp("hybrid_adapters")
    adapters = {f"ad{i}": make_adapter_checkpoint(
        str(d / f"ad{i}"), "preset:debug-hybrid", seed=10 + i, rank=4) for i in range(2)}
    eng = BatchedEngine("preset:debug-hybrid", adapters=adapters, slots=4, decode_chunk=4,
                        kv_block_size=8, kv_blocks=64, max_seq_len=256, prefill_chunk=64)
    yield eng
    eng.close()


def test_engine_serves_greedy_tokens_of_a_plain_loop(engine):
    assert engine.decode_path == "gather"
    v = engine.lora_stack[0]["layers"]
    assert v["run0"]["v_proj"]["b"].shape[-1] == 16      # 1 KV head of width 16
    assert v["run1"]["v_proj"]["b"].shape[-1] == 32      # 2 KV heads
    rng = np.random.default_rng(0)
    work = []
    for i, name in enumerate(["", "ad0", "ad1", "ad0", "", "ad1"]):
        prompt = rng.integers(10, 3000, size=int(rng.integers(20, 150))).tolist()
        work.append((prompt, name, engine.submit(prompt, max_new_tokens=10, adapter=name)))
    for _, _, req in work:
        assert req.done.wait(300) and req.error is None, req.error
    # the plain loop: the whole sequence through ``forward`` for every token,
    # at one padded length (causal, so a tail of padding is inert)
    @jax.jit
    def last_logits(ids, n, idx):
        logits, _ = forward(engine.params, ids, engine.cfg, lora=engine.lora_stack,
                            lora_adapter_idx=idx, compute_dtype=jnp.bfloat16)
        return logits[0, n - 1]

    for prompt, name, req in work:
        ids, want = list(prompt), []
        idx = jnp.asarray([engine.adapter_ids[name]], jnp.int32)
        for _ in range(10):
            padded = jnp.asarray([ids + [0] * (192 - len(ids))], jnp.int32)
            want.append(int(jnp.argmax(last_logits(padded, len(ids), idx))))
            ids.append(want[-1])
        assert req.tokens == want, (name, len(prompt))
    stats = engine.moe_stats
    assert stats["decode_layer_steps"] % 4 == 0 and stats["decode_layer_steps"] > 0
    assert stats["prefill_local_rows"] > 0
    assert 0 < stats["decode_experts_hit"] <= 4 * stats["decode_layer_steps"]
    assert stats["decode_max_rows"] <= stats["decode_local_rows"]


def _slots_reused(eng):
    """Seven requests over two slots, base and two adapters, prompts on and off
    the chunk's bucket (left pads inside rows): every slot is released and
    taken again."""
    rng = np.random.default_rng(3)
    work = [(rng.integers(10, 500, size=n).tolist(), name) for n, name in (
        (5, ""), (64, "ad0"), (70, "ad1"), (150, ""), (33, "ad1"), (129, "ad0"), (8, ""))]
    return [(p, name, eng.submit(p, max_new_tokens=6 + 3 * i, adapter=name))
            for i, (p, name) in enumerate(work)]


def _preempted(eng):
    """Four sessions growing toward 7 blocks each on a pool of 20: a preempted
    session's rows of BOTH kinds and its cursor are exported and written back
    into other blocks."""
    work = [(list(range(20 * i + 5, 20 * i + 35)), "") for i in range(4)]
    work = [(p, name, eng.submit(p, max_new_tokens=80)) for p, name in work]
    for _, _, r in work:
        assert r.done.wait(600)
    assert eng.preempt_stats.get("exported", 0) >= 1, eng.preempt_stats
    assert eng.free_kv_blocks == eng.total_kv_blocks
    return work


_KERNEL_PATHS = {
    "slots_reused": (dict(kv_block_size=8, kv_blocks=64), _slots_reused),
    "preempted": (dict(slots=4, kv_block_size=16, kv_blocks=20, kv_overcommit="on"), _preempted),
}


@pytest.mark.parametrize("path", sorted(_KERNEL_PATHS))
def test_engine_serves_the_same_tokens_with_the_global_kind_on_the_kernel(path, tmp_path, capfd):
    """Two engines that differ in ``paged_kernel`` alone, adapters on ``q_proj``
    and ``o_proj``, through the paths that set a cursor or a table (the engine
    refuses a prefix cache for a model of several kinds): the greedy streams are equal, or part where the reference's first two choices
    lie within bf16's rounding of each other, each engine serving one of them
    (the kernel sums a softmax's terms in another order than XLA does, so one
    probability in thousands rounds the other way; at this width the logits'
    spread is 0.16 and a tie within 0.005 comes every few dozen tokens). And
    the engine says which kind's token step took what."""
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    extra, scenario = _KERNEL_PATHS[path]
    adapters = {f"ad{i}": make_adapter_checkpoint(
        str(tmp_path / f"ad{i}"), "preset:debug-hybrid", seed=30 + i, rank=4,
        targets=("q_proj", "o_proj")) for i in range(2)}
    served = {}
    for mode in ("off", "on"):
        capfd.readouterr()
        eng = BatchedEngine("preset:debug-hybrid", adapters=adapters, paged_kernel=mode,
                            **dict(dict(slots=2, decode_chunk=4, max_seq_len=256,
                                        prefill_chunk=64), **extra))
        try:
            took = "pallas" if mode == "on" else "gather"
            assert eng.decode_paths == {"global": took, "window": "gather"}
            assert eng.decode_path == ("gather+pallas" if mode == "on" else "gather")
            assert eng.decode_window is None  # the window kind's step reads its view
            line = [ln for ln in capfd.readouterr().err.splitlines() if ln.startswith("[engine] {")]
            assert json.loads(line[0][len("[engine] "):])["decode_paths"] == eng.decode_paths
            work = scenario(eng)
            for _, _, r in work:
                assert r.done.wait(600) and r.error is None, r.error
            served[mode] = [(p, name, list(r.tokens)) for p, name, r in work]
            if mode == "on":
                params, mc = eng.params, dataclasses.asdict(eng.cfg)
                stack, scales = eng.lora_stack
                ids = dict(eng.adapter_ids)
        finally:
            eng.close()
    assert all(tokens for _, _, tokens in served["off"])
    for (prompt, name, a), (_, _, b) in zip(served["off"], served["on"]):
        if a == b:
            continue
        at = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        lora = jax.tree_util.tree_map(lambda w: w[:, ids[name]], stack["layers"]) if name else None
        logits = ref.sequence_logits(params, mc, prompt + a[:at], [len(prompt) + at - 1], lora,
                                     float(scales[ids[name]]) if name else 0.0)[0]
        first, second = (int(i) for i in jnp.argsort(logits)[-1:-3:-1])
        assert {a[at], b[at]} == {first, second}, (len(prompt), name, at)
        assert float(logits[first] - logits[second]) < 0.005, (len(prompt), name, at)


def test_engine_counts_blocks_behind_the_window(engine):
    req = engine.submit(list(range(100, 220)), max_new_tokens=40)
    seen = None
    deadline = time.monotonic() + 120
    while not req.done.is_set() and time.monotonic() < deadline:
        w = engine.kv_window_stats()
        if w and w["behind_bytes"]:
            seen = w
        time.sleep(0.005)
    assert req.done.wait(60) and req.error is None
    # 3 window layers x 8 tokens x 2 heads x (24 + 16) x bf16 a block
    assert seen is not None and seen["behind_bytes"] % (3 * 8 * 2 * 40 * 2) == 0
    assert seen["behind_bytes"] <= seen["live_bytes"]


def test_engine_moves_a_live_session_between_replicas(engine):
    """Export mid-decode, import on a second engine: both pools travel and the
    continuation is the undisturbed run's."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    prompt = list(range(200, 290))
    want = engine.submit(prompt, max_new_tokens=24)
    assert want.done.wait(300) and want.error is None
    dst = BatchedEngine("preset:debug-hybrid", slots=2, decode_chunk=4, kv_block_size=8,
                        kv_blocks=64, max_seq_len=256, prefill_chunk=64)
    orig = engine._decode
    try:
        def slow(*a, **k):
            time.sleep(0.05)
            return orig(*a, **k)

        engine._decode = slow
        req = engine.submit(prompt, max_new_tokens=24)
        deadline = time.monotonic() + 60
        while len(req.tokens) < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        doc = engine.export_sessions()
        engine._decode = orig
        assert len(doc["sessions"]) == 1, doc
        payload = json.loads(json.dumps(doc["sessions"][0]))
        assert set(payload["kv"]["pools"]) == {"k_global", "v_global", "k_window", "v_window"}
        meta = dst.import_session(payload)
        handle = meta.pop("_request")
        assert handle.done.wait(300) and handle.error is None, handle.error
        assert handle.tokens == want.tokens
    finally:
        engine._decode = orig
        dst.close()


def test_metrics_name_the_expert_counters(engine):
    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats

    reg = Registry()
    export_moe_stats(reg, engine)
    text = reg.expose()
    for name in ("dtx_serving_moe_local_rows", "dtx_serving_moe_experts_hit",
                 "dtx_serving_moe_max_rows", "dtx_serving_kv_behind_window_bytes"):
        assert name in text
    assert 'dtx_serving_moe_local_rows{phase="decode"}' in text


# --------------------------------------------------------------- geometry

def test_adapter_pool_takes_the_geometry_of_each_kind(model):
    from datatunerx_tpu.adapters.store import AdapterStore, hbm_bytes
    from datatunerx_tpu.models.lora import init_lora_params

    cfg = model[0]
    store = AdapterStore(cfg, pool_slots=2, rank_max=4, targets=("q_proj", "v_proj"))
    tree = store.tree[0]["layers"]
    assert tree["run0"]["v_proj"]["b"].shape == (1, 3, 4, 16)
    assert tree["run1"]["v_proj"]["b"].shape == (3, 3, 4, 32)
    assert store.nbytes() == hbm_bytes(cfg, 2, 4)
    lora = init_lora_params(cfg, jax.random.PRNGKey(2), rank=2, targets=("q_proj", "v_proj"))
    assert store.insert(1, lora["layers"], 4.0, name="a") == 2
    got = store.tree[0]["layers"]["run2"]["q_proj"]["a"]
    np.testing.assert_array_equal(got[:, 1, :, :2], lora["layers"]["run2"]["q_proj"]["a"])
    # a dense feed-forward exists in the leading run only: one buffer, not three
    dense = AdapterStore(cfg, pool_slots=1, rank_max=4, targets=("gate_proj",))
    assert list(dense.tree[0]["layers"]) == ["run0"]


@pytest.mark.parametrize("entry", ["trainer", "memory", "hf_import", "hf_export", "admission",
                                   "kv_quant", "prefix_cache", "training_args"])
def test_entries_that_handle_one_kind_refuse_by_name(model, entry):
    cfg, _, params, tokens = model
    with pytest.raises(NotImplementedError, match="debug-hybrid") as err:
        if entry == "trainer":
            from datatunerx_tpu.training.train_lib import TrainConfig, Trainer

            Trainer(cfg, TrainConfig())
        elif entry == "memory":
            from datatunerx_tpu.parallel.memory import estimate_footprint
            from datatunerx_tpu.training.train_lib import TrainConfig

            estimate_footprint(cfg, TrainConfig(), batch=1, seq=64)
        elif entry == "hf_import":
            from datatunerx_tpu.utils.hf_convert import convert_hf_state_dict

            convert_hf_state_dict({}, cfg)
        elif entry == "hf_export":
            from datatunerx_tpu.utils.hf_convert import export_hf_state_dict

            export_hf_state_dict(params, cfg)
        elif entry == "admission":
            from datatunerx_tpu.operator.capacity import check_admission

            verdict = check_admission("preset:debug-hybrid", {}, n_chips=1)
            assert verdict is not None and verdict[1] == {}
            raise NotImplementedError(verdict[0])
        elif entry == "kv_quant":
            init_cache(cfg, 1, 64, quantize="int8")
        elif entry == "prefix_cache":
            from datatunerx_tpu.serving.batched_engine import BatchedEngine

            BatchedEngine("preset:debug-hybrid", prefix_cache=4)
        else:
            forward(params, tokens, cfg, segment_ids=jnp.zeros_like(tokens))
    assert "several" in str(err.value) or "per mixer kind" in str(err.value)
