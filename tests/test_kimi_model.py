"""A model whose every mixer is DENSE latent attention (a low-rank query, no
indexer, no head gate: every query reads every cached row; v heads narrower than
q/k) under YaRN-scaled RoPE, with sigmoid-routed experts and a shared one, served
under a PREFIX CACHE whose entries are copy-on-write blocks of the latent pool
(models/hybrid.py, ops/rope.py, ops/mla.py, serving/batched_engine.py,
serving/kv_pool.py). Every model-level test is against the plain reference
``benchmarks/reference/kimi_k2.py`` (float32, expanded heads, YaRN written from
the published formulas, no cache), at the ``debug-kimi`` size on seeded weights,
on the LOGITS. The engines are module-scoped: one set of cases an engine."""

import dataclasses
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from reference import kimi_k2 as ref  # noqa: E402

from datatunerx_tpu.models import forward, get_config, hybrid, init_params  # noqa: E402
from datatunerx_tpu.models.config import YarnScaling, layer_runs, mixer_kinds  # noqa: E402
from datatunerx_tpu.ops import mla, moe  # noqa: E402
from datatunerx_tpu.ops.paged_attention import init_paged_cache, kv_leaf_keys  # noqa: E402
from datatunerx_tpu.ops.rope import rope_cos_sin, yarn_mscale, yarn_ramp  # noqa: E402


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """As tests/test_glm_model.py: ``forward`` op by op loads a program a
    primitive and shape, and a process may hold 65,530 memory maps."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _the_modules_step(request, monkeypatch):
    """``engines`` holds the step of a chunk's view at 32 lanes for as long as
    its second pair lives; a test that does not use the pair runs at the
    module's own, wherever the order puts it."""
    if "engines" not in request.fixturenames:
        monkeypatch.setattr(mla, "VIEW_STEP_LANES", MODULE_STEP)


MODULE_STEP = mla.VIEW_STEP_LANES
TOL = 2e-5  # float32 program against float32 reference: rounding order only
T = 150
PUBLISHED = YarnScaling(factor=64.0, original_max_len=4096, beta_fast=32.0, beta_slow=1.0,
                        mscale=1.0, mscale_all_dim=1.0)


# ---------------------------------------------------------------------- YaRN

def _closed_form(d, base, y, positions):
    """The issue's equations in float64: (cos, sin) [positions, d / 2]."""
    corr = lambda n: d * math.log(y.original_max_len / (2 * math.pi * n)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(corr(y.beta_fast)), 0), min(math.ceil(corr(y.beta_slow)), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freq = base ** (-2 * i / d) * ((1 - ramp) + ramp / y.factor)
    m = (0.1 * y.mscale * math.log(y.factor) + 1) / (0.1 * y.mscale_all_dim * math.log(y.factor) + 1)
    ang = np.asarray(positions, np.float64)[:, None] * freq
    return np.cos(ang) * m, np.sin(ang) * m, (low, high)


@pytest.mark.parametrize("positions", [
    (0, 1, 17, 1000, 4095),             # below the length the frequencies were trained at
    (4096, 5000, 12287),                # between it and the cell's longest context
    (65536, 200000, 262143)])           # far beyond: to the model's 262,144 positions
def test_yarn_tables_are_the_closed_form_at_the_published_numbers(positions):
    """64 rope lanes, base 50000, 4,096 positions scaled 64 times. A float32
    angle is good to position x 2^-23 (the tolerance's first term)."""
    cos, sin = rope_cos_sin(jnp.asarray([positions], jnp.int32), 64, theta=50000.0, yarn=PUBLISHED)
    want_cos, want_sin, (low, high) = _closed_form(64, 50000.0, PUBLISHED, positions)
    assert (low, high) == (8, 20)
    tol = 4e-7 * np.asarray(positions)[:, None] + 2e-6
    assert (np.abs(np.asarray(cos[0]) - want_cos) <= tol).all()
    assert (np.abs(np.asarray(sin[0]) - want_sin) <= tol).all()
    # the reference's own YaRN, written apart from ops/rope.py, says the same
    f, m = ref.yarn_frequencies(64, 50000.0, (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0))
    ang = np.asarray(positions, np.float64)[:, None] * np.asarray(f, np.float64)
    assert m == 1.0 and (np.abs(np.cos(ang) - want_cos) <= tol).all()


def test_yarn_keeps_fast_pairs_and_divides_slow_ones():
    ramp = yarn_ramp(64, 50000.0, PUBLISHED)
    assert ramp.shape == (32,) and (ramp[:9] == 0).all() and (ramp[20:] == 1).all()
    np.testing.assert_allclose(ramp[8:21], np.arange(13) / 12, atol=1e-7)
    plain = rope_cos_sin(jnp.asarray([[3000]], jnp.int32), 64, theta=50000.0)
    scaled = rope_cos_sin(jnp.asarray([[3000]], jnp.int32), 64, theta=50000.0, yarn=PUBLISHED)
    np.testing.assert_array_equal(np.asarray(plain[0])[..., :9], np.asarray(scaled[0])[..., :9])
    slow = rope_cos_sin(jnp.asarray([[3000 * 64]], jnp.int32), 64, theta=50000.0, yarn=PUBLISHED)
    np.testing.assert_allclose(np.asarray(slow[1])[..., 20:], np.asarray(plain[1])[..., 20:], atol=1e-5)


@pytest.mark.parametrize("all_dim,table,scores", [
    (1.0, 1.0, 192 ** -0.5 * 1.4158883 ** 2),   # published: the temperature on the scores, squared
    (0.0, 1.4158883, 192 ** -0.5)])             # none stated for all lanes: on the tables instead
def test_the_temperature_goes_to_the_scores_or_to_the_tables(all_dim, table, scores):
    y = dataclasses.replace(PUBLISHED, mscale_all_dim=all_dim)
    assert yarn_mscale(64.0, 1.0) == pytest.approx(0.1 * math.log(64) + 1) == pytest.approx(1.4158883)
    cos, _ = rope_cos_sin(jnp.zeros((1, 1), jnp.int32), 64, theta=50000.0, yarn=y)
    np.testing.assert_allclose(np.asarray(cos), table, rtol=1e-6)
    cfg = dataclasses.replace(get_config("debug-kimi"), qk_nope_head_dim=128, qk_rope_head_dim=64,
                              rope_scaling_factor=64.0, rope_mscale_all_dim=all_dim)
    assert mixer_kinds(cfg)["mla"].score_scale == pytest.approx(scores, rel=1e-6)
    if all_dim:
        assert scores == pytest.approx(0.07217 * 2.00474, rel=1e-4)  # the issue's numbers


# ----------------------------------------------------------------- the model

@pytest.fixture(scope="module")
def model():
    cfg = get_config("debug-kimi")
    params = init_params(cfg, jax.random.PRNGKey(0))
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


def test_runs_name_their_mixer_and_a_pool_of_whole_lane_tiles(model):
    cfg, _, params, _ = model
    runs = layer_runs(cfg)
    assert [(r.mixer.name, r.ffn, r.count) for r in runs] == [("mla", "dense", 1), ("mla", "experts", 4)]
    kind = mixer_kinds(cfg)["mla"]
    # 32 latent + 8 rotated-key values a row, stored as one lane tile: the latent
    # pool is this model's whole cache. A model that mixes latent layers among
    # others keeps its rows as they are (debug-ling: 40)
    assert kind.pools() == {"k_mla": 128} and kind.whole_tiles and kind.states(cfg) == {}
    assert mixer_kinds(get_config("debug-ling"))["mla"].pools() == {"k_mla": 40}
    assert kind.yarn == YarnScaling(8.0, 64, 32.0, 1.0, 1.0, 1.0)
    assert not kind.index_topk and not kind.head_gate and kind.q_lora_rank == 48
    assert kind.v_head_dim == 16 and kind.nope_dim + kind.rope_dim == 24   # v narrower than q/k
    run1 = params["layers"]["run1"]
    assert run1["q_b_proj"]["kernel"].shape == (4, 48, 4 * 24)
    assert run1["kv_b_proj"]["kernel"].shape == (4, 32, 4 * (16 + 16))
    assert run1["o_proj"]["kernel"].shape == (4, 4 * 16, 64)
    assert "indexer" not in run1 and "g_proj" not in run1
    cache = init_paged_cache(cfg, 2, 24, 8, 24, dtype=jnp.float32)
    assert cache["k_mla"].shape == (5, 24, 8, 128) and kv_leaf_keys(cache) == ["k_mla"]


@pytest.mark.parametrize("length", [T, 1])
def test_full_forward_equals_reference(model, want, length):
    cfg, _, params, tokens = model
    got, _ = forward(params, tokens[:, :length], cfg)
    np.testing.assert_allclose(got, want[:, :length], atol=TOL)


def test_paged_pool_chunked_prefill_then_decode_equals_reference(model, want):
    """Prefill in chunks of 64 (the last of 22) through a paged latent pool
    whose blocks lie in no order, then eight token steps: each step's logits
    against the reference's FULL forward at that position. Float32 both sides:
    the tolerance is rounding order (absorbed against expanded heads)."""
    cfg, _, params, tokens = model
    cache = init_paged_cache(cfg, 2, 64, 8, 24, dtype=jnp.float32)
    table = np.random.default_rng(0).permutation(64)[:48].reshape(2, 24)
    cache["block_tables"] = jnp.asarray(table, jnp.int32)
    step = jax.jit(lambda ids, cache, pos: forward(params, ids, cfg, cache=cache, positions=pos))
    n = T - 8
    for lo in range(0, n, 64):
        hi = min(lo + 64, n)
        got, cache = step(tokens[:, lo:hi], cache, _positions(lo, hi))
        np.testing.assert_allclose(got, want[:, lo:hi], atol=TOL)
    for t in range(n, T):
        got, cache = step(tokens[:, t:t + 1], cache, _positions(t, t + 1))
        np.testing.assert_allclose(got[:, 0], want[:, t], atol=TOL)
    assert list(np.asarray(cache["len"])) == [T, T]
    # a row's tail past [c | kR] is zeros
    assert float(jnp.abs(cache["k_mla"][..., 40:]).max()) == 0.0


STEP = 32  # lanes a step of a chunk's view in these tests (the module's constant is 1,024; debug-kimi's tables hold 512)


@pytest.mark.parametrize("edge", [-1, 0, 1])
def test_a_chunk_views_what_its_context_reaches(model, want, monkeypatch, edge):
    """Chunks whose reach (``len + T``) sits one lane before, on and one lane
    past each step of 32 lanes, the last one left-padded and reaching past the
    table's last whole step (152 lanes: four steps and three quarters), in a table
    whose columns past the prompt have no block, then eight token steps: the
    lanes every layer reads follow the reach (a token step's stay the
    table's), and the logits are the reference's and the table-wide view's."""
    cfg, _, params, tokens = model
    block_size, nbps, pad, n = 8, 19, 2, T - 8
    cuts = [0] + [STEP * k + edge for k in range(1, 5)] + [n]
    table = np.full((2, nbps), -1, np.int32)
    held = -(-(T + pad) // block_size)
    table[:, :held] = np.random.default_rng(edge + 1).permutation(2 * nbps)[:2 * held].reshape(2, held)
    read, real_attention = [], hybrid.xla_attention  # the lanes each layer's attention read

    def spy_attention(q, k, v, bias, **kw):
        jax.debug.callback(lambda _: read.append(k.shape[1]), q[0, 0, 0, 0])  # the branch taken speaks
        return real_attention(q, k, v, bias, **kw)

    monkeypatch.setattr(hybrid, "xla_attention", spy_attention)
    monkeypatch.setattr(mla, "VIEW_STEP_LANES", STEP)
    assert mla.view_steps(64, nbps, block_size, 0) == (4, 8, 12, 16, 19)

    def serve(stepped):
        cache = init_paged_cache(cfg, 2, 2 * nbps, block_size, nbps, dtype=jnp.float32)
        cache["block_tables"] = jnp.asarray(table)
        cache["k_mla"] = cache["k_mla"] - 7.0  # what earlier requests left in the pool
        outs = []
        for lo, hi in zip(cuts, cuts[1:]):
            n_pad = pad if hi == n else 0
            ids = jnp.concatenate([jnp.full((2, n_pad), 7, tokens.dtype), tokens[:, lo:hi]], axis=1)
            mask = jnp.concatenate([jnp.zeros((2, n_pad), jnp.int32), jnp.ones((2, hi - lo), jnp.int32)], axis=1)
            pos = jnp.concatenate([jnp.zeros((2, n_pad), jnp.int32), _positions(lo, hi)], axis=1)
            out, cache = forward(params, ids, cfg, cache=cache, positions=pos, attention_mask=mask)
            outs.append(out[:, n_pad:])
            jax.effects_barrier()
            reach = hi + n_pad
            lanes = mla.view_lanes(lo, reach - lo, 0, block_size, nbps) if stepped else nbps * block_size
            assert read == [lanes] * 5 and lanes == (min(-(-reach // STEP) * STEP, 152) if stepped else 152), (lo, hi)
            read.clear()
        assert 4 * STEP < reach == n + pad and T + pad == nbps * block_size  # the last chunk: the branch cut at the table
        for t in range(n, T):  # a token step: the table, whatever the cursor
            out, cache = forward(params, tokens[:, t:t + 1], cfg, cache=cache, positions=_positions(t, t + 1))
            outs.append(out)
            jax.effects_barrier()
            assert read == [nbps * block_size] * 5
            read.clear()
        assert int(cache["len"][0]) == T + pad
        return jnp.concatenate(outs, axis=1)

    got = serve(True)
    np.testing.assert_allclose(got, want, atol=TOL)
    # the rule turned off (as the step was before its view followed its reach): the same tokens
    monkeypatch.setattr(mla, "view_steps", lambda *a: ())
    wide = serve(False)
    np.testing.assert_allclose(got, wide, atol=TOL)
    np.testing.assert_array_equal(jnp.argmax(got, -1), jnp.argmax(wide, -1))


@pytest.mark.parametrize("name,change,least", [
    ("the temperature on the scores", dict(rope_mscale_all_dim=0.0), 1e-3),
    ("yarn at all", dict(rope_scaling_type=None), 1e-3),
    ("the factor", dict(rope_scaling_factor=2.0), 1e-4),
    ("the trained length", dict(rope_original_max_len=512), 1e-4),
    ("the shared expert", dict(no_shared_expert=True), 1e-3),
    ("the routing scale", dict(routed_scaling_factor=1.0), 1e-3)])
def test_each_mechanism_matters(model, want, name, change, least):
    """A reference computed otherwise is no longer what the program computes:
    the comparison above is tight enough to see each (``mscale_all_dim`` 0 moves
    the temperature from all 24 score lanes to the 8 rope lanes of the tables)."""
    cfg, mc, params, tokens = model
    off = _ref_logits(dict(mc, **change), params, tokens[:1])
    got, _ = forward(params, tokens[:1], cfg)
    assert float(jnp.abs(off - got).max()) > max(least, 20 * TOL), name
    if "no_shared_expert" not in change:  # and the program follows its own config
        moved, _ = forward(params, tokens[:1], dataclasses.replace(cfg, **change))
        np.testing.assert_allclose(moved, off, atol=TOL)


def test_the_int8_control_is_not_the_reference(model, want):
    _, mc, params, tokens = model
    low = _ref_logits(mc, params, tokens[:1], precision="int8")
    assert float(jnp.abs(low - want[:1]).max()) > 50 * TOL


def test_the_32_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """32 experts in one group, one expert a share, top-8, sigmoid scores with a
    selection-only bias, x 2.827: as 32 chips share a layer of the benchmark's
    configuration (12 experts a chip there). The parts all 32 shares give, with
    the shared expert counted once, are the uncut reference's layer; each
    share's part is the reference's share."""
    cfg = dataclasses.replace(get_config("debug-kimi"), experts_total=32, experts_held=1,
                              experts_per_token=8)
    mc = dataclasses.asdict(cfg)
    whole = dataclasses.replace(cfg, experts_held=32)
    lp = jax.tree_util.tree_map(lambda a: a[1], init_params(whole, jax.random.PRNGKey(5))["layers"]["run1"])
    h = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.hidden_size), jnp.float32)
    uncut = ref.expert_ffn(h, lp, dict(mc, experts_held=32), "f32") - h
    normed = ref.rms_norm(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    total, pairs = ref.swiglu(normed, lp["shared_expert"], "f32"), 0
    for first in range(32):
        mine = dict(lp, experts=jax.tree_util.tree_map(lambda a: a[first:first + 1], lp["experts"]))
        part, stats = moe.expert_layer(
            normed, None, mine, experts_total=32, experts_held=1, first_held=first, top_k=8,
            normalize=True, scaling=cfg.routed_scaling_factor, n_group=1, topk_group=1)
        total, pairs = total + part, pairs + int(stats[0])
        one = ref.expert_ffn(h, mine, dict(mc, first_held=first, no_shared_expert=True), "f32") - h
        np.testing.assert_allclose(part, one, atol=TOL)
    assert pairs == 40 * 8  # every pair is some share's
    np.testing.assert_allclose(total, uncut, atol=TOL)


# --------------------------------------------- the prefix cache, latent pool

ENGINE = dict(slots=3, decode_chunk=4, kv_block_size=8, kv_blocks=160, max_seq_len=512,
              prefill_chunk=64, dtype=jnp.float32)
GAP = 1e-4  # a float32 engine against the float32 reference: a served token is the reference's first


@pytest.fixture(scope="module", params=[0, STEP], ids=["table", f"step{STEP}"])
def engines(request):
    """(an engine with a prefix cache over copy-on-write blocks, one without),
    once as the presets' tables give them (512 lanes are within one step of a
    chunk's view: every chunk views its table) and once with the step set to
    32 lanes: every chunk then views as far as its slot's lane cursor reaches.
    The step stays set while the pair lives (an engine traces its programs
    again after ``jax.clear_caches``), and the pair has a memo of programs of
    its own."""
    import collections

    from datatunerx_tpu.serving import batched_engine
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    with pytest.MonkeyPatch.context() as patch:
        if request.param:
            patch.setattr(mla, "VIEW_STEP_LANES", request.param)
            patch.setattr(batched_engine, "_PROGRAM_MEMO", collections.OrderedDict())
        cow = BatchedEngine("preset:debug-kimi", kv_overcommit="on", prefix_cache=6, **ENGINE)
        cold = BatchedEngine("preset:debug-kimi", **ENGINE)
        assert cow.prefill_view_step() == cold.engine_line["prefill_view_step"] == request.param
        yield cow, cold
        cow.close()
        cold.close()


def _serve(engine, prompt, n=12):
    req = engine.submit(prompt, max_new_tokens=n)
    assert req.done.wait(600) and req.error is None, req.error
    return req


def _gaps(engine, prompt, req):
    mc = dataclasses.asdict(engine.cfg)
    tokens = list(prompt) + list(req.tokens)
    rows = list(range(len(prompt) - 1, len(tokens) - 1))
    logits = ref.sequence_logits(engine.params, mc, tokens, rows)
    got = jnp.take_along_axis(logits, jnp.asarray(req.tokens)[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(logits, axis=-1) - got)


def _idle(engine):
    deadline = time.monotonic() + 60
    while any(r is not None for r in engine._slot_req) and time.monotonic() < deadline:
        time.sleep(0.02)
    time.sleep(0.1)


def _up(n):
    """``n`` tokens in whole buckets of 64 lanes."""
    return -(-n // 64) * 64


def _admits(engine, since=0):
    return [e[3] for e in list(engine.sched_trace)[since:] if e[0] == "admit"]


@pytest.fixture(scope="module")
def session(engines):
    """Three turns of one session through both engines: a cold turn of 100
    tokens, then twice the history, the answer served and a tool result."""
    cow, cold = engines
    rng = np.random.default_rng(0)
    history, turns = rng.integers(10, 500, size=100).tolist(), []
    before, since, lanes = cow.prefix_stats, len(cow.sched_trace), dict(cow.dsa_stats)
    for tool in (0, 37, 70):
        history = history + rng.integers(10, 500, size=tool).tolist()
        a, b = _serve(cow, history), _serve(cold, history)
        turns.append((list(history), a, b))
        history = history + list(a.tokens)
    _idle(cow)
    # tests of one module share the engines in whatever order a worker runs them: deltas
    turns.append(({k: v - before[k] for k, v in cow.prefix_stats.items()}, _admits(cow, since),
                  {k: cow.dsa_stats[k] - lanes[k] for k in ("prefill_view_lanes", "prefill_table_lanes")}))
    return turns


def test_later_turns_extend_their_history_through_shared_blocks(engines, session):
    cow, _ = engines
    got, admits, lanes = session[3]
    assert admits == ["chunked", "cow_extend", "cow_extend"]
    assert cow.decode_paths == {"mla": "gather"} and cow.cow
    # turn 2 shares turn 1's 100 tokens and prefills the answer and 37 more; turn 3 shares turn
    # 2's prompt and prefills its answer and 70 more (an answer ends early at the tokenizer's eos)
    n1, n2, n3 = (len(p) for p, _, _ in session[:3])
    assert n1 == 100 and n2 == n1 + len(session[0][1].tokens) + 37
    assert (got["cold"], got["extensions"], got["hits"]) == (1, 2, 0)
    assert got["shared_tokens"] == n1 + n2 and got["prefilled_tokens"] == n3
    for prompt, a, b in session[:3]:
        # pads at a suffix's left lie MID-ROW (turn 2's tokens end at lane 192 of 128 + 64):
        # masked by position, the served tokens are the cold path's and the reference's first
        assert a.tokens == b.tokens and len(a.tokens) > 0
        assert _gaps(cow, prompt, a).max() < GAP, len(prompt)
    # what the chunks viewed, counted as the program sizes it: by the slot's LANE cursor (a suffix
    # starts at its shared base, a bucket's pads before it), in whole steps; a cursor on a block's
    # edge and on a step's (64, 128, 192); nothing where every chunk views its table
    step = cow.prefill_view_step()
    cursors = [c for base, n in ((0, n1), (_up(n1), n2 - n1), (_up(n1) + _up(n2 - n1), n3 - n2))
               for c in range(base, base + _up(n), 64)]
    assert lanes == ({"prefill_view_lanes": sum(-(-(c + 64) // step) * step for c in cursors),
                      "prefill_table_lanes": 512 * len(cursors)} if step else
                     {"prefill_view_lanes": 0, "prefill_table_lanes": 0})
    assert not step or cursors[:3] == [0, 64, 128] and lanes["prefill_view_lanes"] < lanes["prefill_table_lanes"]


def test_logits_through_a_hit_equal_the_cold_paths(engines, session):
    """What a prefill leaves for the first token, kept in the entry it publishes:
    through two suffix extensions against one cold prefill of the same tokens
    in an engine whose cache has never seen them."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    cow, _ = engines
    prompt = session[2][0]
    key = (tuple(prompt), 0)
    through = cow._prefix.get(key)
    fresh = BatchedEngine("preset:debug-kimi", kv_overcommit="on", prefix_cache=2, **ENGINE)
    try:
        _serve(fresh, prompt, 1)
        direct = fresh._prefix.get(key)
        assert _admits(fresh) == ["chunked"]
        # cold: the prompt in whole buckets, its pads at the left; through the extensions a
        # bucket's pads before each suffix too
        n1, n2, n3 = (len(p) for p, _, _ in session[:3])
        assert direct["cursor"] == _up(n3) < through["cursor"] == _up(n1) + _up(n2 - n1) + _up(n3 - n2)
        np.testing.assert_allclose(np.asarray(through["logits"]), np.asarray(direct["logits"]), atol=TOL)
    finally:
        fresh.close()


def test_an_exact_hit_maps_the_blocks_and_a_second_owner_decodes_as_the_first(engines, session):
    """Two requests for a prompt the cache holds, in flight together: each maps
    the entry's full blocks and copies its partial tail block ONCE into a block
    of its own, so neither's decode writes where the other, or the entry, reads."""
    cow, _ = engines
    prompt, first, _ = session[1]
    ent = cow._prefix.get((tuple(prompt), 0))
    assert ent["cursor"] == 192 and (ent["full"], ent["rem"]) == (24, 0) and len(ent["blocks"]) == 24
    before = cow.prefix_stats
    since = len(cow.sched_trace)
    a, b = cow.submit(prompt, max_new_tokens=12), cow.submit(prompt, max_new_tokens=12)
    deadline = time.monotonic() + 60
    while (sum(r is not None for r in cow._slot_req) < 2 and not (a.done.is_set() and b.done.is_set())
           and time.monotonic() < deadline):
        time.sleep(0.001)
    alloc = cow._pool.allocator
    shared = [alloc.refcount(blk) for blk in ent["blocks"][:ent["full"]]]
    assert a.done.wait(600) and b.done.wait(600) and a.error is None and b.error is None
    assert a.tokens == b.tokens == first.tokens
    assert _admits(cow, since) == ["cow", "cow"]
    assert cow.prefix_stats["hits"] == before["hits"] + 2
    assert cow.prefix_stats["shared_tokens"] == before["shared_tokens"] + 2 * len(prompt)
    assert max(shared) >= 2  # the entry and a slot at least, while they decoded
    _idle(cow)
    # the owners gone, the entry's blocks are the entry's (and its neighbours') again
    assert all(alloc.refcount(blk) >= 1 for blk in ent["blocks"])


def test_eviction_frees_the_blocks_entries_hold():
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    cow = BatchedEngine("preset:debug-kimi", kv_overcommit="on", prefix_cache=2, **ENGINE)
    try:
        rng = np.random.default_rng(7)
        for _ in range(4):  # past the cache's two entries: the earlier ones go
            _serve(cow, rng.integers(10, 500, size=60).tolist(), 4)
        _idle(cow)
        assert len(cow._prefix) == 2 and cow.prefix_stats["entries_evicted"] == 2
        # two entries of one 64-token prompt each: 8 blocks an entry, nothing else held
        assert cow._pool.free == cow._pool.total - 2 * 8
        cow._prefix.drop_adapter(0)
        assert cow._pool.free == cow._pool.total
    finally:
        cow.close()


@pytest.mark.parametrize("preset,kw", [
    # ROADMAP D20's own pool: 20 blocks of 16, a 150-token request (192 lanes: 12 blocks, kept
    # by its entry), then a 90-token one (128 lanes and a tick's growth: 9 blocks) that the
    # pool cannot admit until the idle entry gives its blocks back: it timed out before
    ("debug", dict(max_seq_len=256, kv_block_size=16, kv_blocks=20, prefix_cache=4)),
    ("debug-kimi", dict(max_seq_len=256, kv_block_size=16, kv_blocks=20, prefix_cache=4))])
def test_a_cold_admission_reclaims_the_blocks_idle_entries_hold(preset, kw):
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine(f"preset:{preset}", slots=2, decode_chunk=4, kv_overcommit="on",
                        prefill_chunk=64, **kw)
    try:
        rng = np.random.default_rng(3)
        _serve(eng, rng.integers(10, 500, size=150).tolist(), 8)
        _idle(eng)
        assert eng._pool.free == 8  # the entry holds 12 of 20 blocks, no slot maps them
        # a table within one step of a chunk's view (or a model with no latent kind): no stepped view, nothing counted
        assert eng.engine_line["prefill_view_step"] == 0 and eng._dsa_chunk_marks(0, 64) == {}
        assert eng.dsa_stats["prefill_table_lanes"] == 0
        req = eng.submit(rng.integers(10, 500, size=90).tolist(), max_new_tokens=8)
        assert req.done.wait(120) and req.error is None, "the head waited for blocks idle entries held"
        got = eng.prefix_stats
        assert got["blocks_reclaimed_at_admission"] == 12 and got["cold"] == 2
        waited = [d for _, e, d in req.timeline if e == "admit"]
        assert waited and waited[-1]["waited_for"] != "blocks"
        assert any(e[0] == "reclaim_entry" for e in eng.sched_trace)
    finally:
        eng.close()


@pytest.mark.parametrize("preset,names", [
    ("debug-hybrid", "window reads a window-wide view"),      # a window kind
    ("debug-glm", "mla selects the cached tokens it reads"),  # a selecting kind
    ("debug-ling", "kda keeps a recurrent state per slot"),   # recurrent state
    ("debug-granite", "ssm keeps a recurrent state per slot"),
    ("debug-kimi", "needs --kv_block_size > 0 and --kv_overcommit on")])  # entries are blocks
def test_the_prefix_cache_refuses_by_name_what_it_cannot_share(preset, names):
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    kw = dict(kv_block_size=8, kv_blocks=96, max_seq_len=256, prefill_chunk=64)
    if preset != "debug-kimi":
        kw["kv_overcommit"] = "on"
    with pytest.raises(NotImplementedError, match=preset) as err:
        BatchedEngine(f"preset:{preset}", slots=2, prefix_cache=4, **kw)
    assert names in str(err.value) and "several kinds" in str(err.value)


def test_spans_counters_and_metrics_name_the_prefix_cache(engines, session):
    from datatunerx_tpu.obs.metrics import Registry
    from datatunerx_tpu.serving import server

    cow, cold = engines
    marks = [d for _, e, d in session[2][1].timeline if e == "admit"]
    assert marks[-1]["mode"] == "cow_extend" and marks[-1]["shared_tokens"] == len(session[1][0])
    assert [d["shared_tokens"] for _, e, d in session[0][1].timeline if e == "admit"] == [0]
    # an engine with no cache counts what it prefilled and nothing shared
    assert cold.prefix_stats["cold"] >= 3 and cold.prefix_stats["shared_tokens"] == 0
    assert cold.prefix_stats["prefilled_tokens"] >= sum(len(p) for p, _, _ in session[:3])
    old_engine, old_registry = server.STATE.engine, server.STATE.registry
    server.STATE.engine, server.STATE.registry = cow, Registry()
    try:
        text = server.metrics_text()
    finally:
        server.STATE.engine, server.STATE.registry = old_engine, old_registry
    got = cow.prefix_stats
    assert f"dtx_serving_prefix_shared_tokens_total {got['shared_tokens']}" in text
    assert f"dtx_serving_prefix_prefilled_tokens_total {got['prefilled_tokens']}" in text
    assert "dtx_serving_prefix_blocks_reclaimed_total 0" in text


def test_the_engine_line_spans_and_metrics_name_a_chunks_view(engines):
    """What says how often the stepped view engages: the engine's line, the
    chunk span's keywords, the engine's sums and ``/metrics``, counted by the
    program's own rule; all silent where every chunk views its table. And the
    program itself: one switch a run of like layers, over the table's steps."""
    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats

    _, cold = engines
    step = cold.prefill_view_step()
    assert cold.engine_line["index_topk"] == 0 and cold.index_pool_bytes() == 0 and cold._dsa_marks() == {}
    before, spans, real_phase = dict(cold.dsa_stats), [], cold._phase
    cold._phase = lambda name, **detail: (spans.append((name, detail)), real_phase(name, **detail))[1]
    try:
        _serve(cold, list(range(30, 160)), 2)  # 130 tokens in 192 lanes: chunks of 64 at lanes 0, 64, 128
    finally:
        cold._phase = real_phase
    _idle(cold)
    chunks = [d for name, d in spans if name == "dtx_engine_prefill_chunk"]
    reg = Registry()
    export_moe_stats(reg, cold)
    text = reg.expose()
    lowered = cold._prefill_chunk_fn.lower(
        cold.params, cold._lora_arg(), cold._cache, jnp.asarray(0, jnp.int32), *(jnp.zeros((1, 64), jnp.int32),) * 3,
        jnp.asarray(0, jnp.int32), chunk_len=64).as_text()
    if step:
        assert [(d["tokens"], d["view"], d["table"]) for d in chunks] == [(64, 64, 512), (64, 128, 512), (64, 192, 512)]
        assert all(d["view"] == mla.view_lanes(64 * i, 64, 0, 8, 64) for i, d in enumerate(chunks))
        assert cold.dsa_stats["prefill_view_lanes"] - before["prefill_view_lanes"] == 64 + 128 + 192
        assert cold.dsa_stats["prefill_table_lanes"] - before["prefill_table_lanes"] == 3 * 512
        for name in ("view", "table"):
            assert f"# TYPE dtx_serving_dsa_prefill_{name}_lanes_total counter" in text
            assert f"dtx_serving_dsa_prefill_{name}_lanes_total {cold.dsa_stats[f'prefill_{name}_lanes']}\n" in text
        assert lowered.count("stablehlo.case") == 2  # a dense run and a run with experts, sixteen widths each
    else:
        assert len(chunks) == 3 and all(d.keys() == {"tokens", "slot"} for d in chunks)
        assert cold.dsa_stats == before and cold._dsa_chunk_marks(0, 64) == {}
        assert "\ndtx_serving_dsa_prefill_view_lanes_total " not in text
        assert "stablehlo.case" not in lowered
    assert "dtx_serving_dsa_steps{" not in text and "\ndtx_serving_index_pool_bytes " not in text  # nothing selects
