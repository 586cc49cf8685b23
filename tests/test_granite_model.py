"""A model whose mixers are state-space layers (Mamba-2: a recurrent state per
slot, no rows) and softmax attention WITHOUT positions, with Granite's four
multipliers, a dense feed-forward in every layer and a tied head
(models/hybrid.py, ops/ssm.py). The chunk (SSD) form is tested first against
the one-token step and the token-by-token recurrence: everything else in the
Mamba path rests on it. Every model-level test is against the plain reference
``benchmarks/reference/granite_v4.py`` (float32, recurrence token by token, no
cache), at the ``debug-granite`` size on the benchmark's own seeded draw."""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import weights_granite_v4 as draw  # noqa: E402
from reference import granite_v4 as ref  # noqa: E402

from datatunerx_tpu.models import forward, get_config  # noqa: E402
from datatunerx_tpu.models.config import layer_runs, mixer_kinds  # noqa: E402
from datatunerx_tpu.models.llama import init_cache  # noqa: E402
from datatunerx_tpu.ops import kda, ssm  # noqa: E402
from datatunerx_tpu.ops.paged_attention import (  # noqa: E402
    init_paged_cache,
    kv_leaf_keys,
    paged_extract_row,
    paged_insert_row,
    state_leaf_keys,
)

# float32 program against float32 reference: rounding order only. The chunk
# form sums a masked [T, T] product where the recurrence adds T rank-one
# updates; with logits of a few hundredths that is a few ulp, under 2e-6. A
# recurrent state STORED in bfloat16 reads 1e-4 and more (a test below).
TOL = 2e-6
T = 150
# the benchmark draws layer weights at normal 0.02, which at hidden 64 is a
# branch of a hundredth of the stream: times 12, every branch carries weight
# and a wrong mixer moves the logits by far more than a tolerance
LAYER_GAIN = 12.0


# ------------------------------------------------------ the equations alone

def _draw(rng, B, T_, H=4, P=8, N=16, G=2, left_pad=(0, 0)):
    """x, B, C, dt, dA (log decay <= 0), D, S0; pads at the left carry dt 0, dA 0."""
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, Bm, Cm = f(B, T_, H, P), f(B, T_, G, N), f(B, T_, G, N)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(B, T_, H)), jnp.float32)
    dA = -dt * jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    valid = jnp.asarray(np.arange(T_)[None, :] >= np.asarray(left_pad)[:, None])
    dt, dA = jnp.where(valid[..., None], dt, 0.0), jnp.where(valid[..., None], dA, 0.0)
    return (f(B, H, P, N), x, Bm, Cm, dt, dA, f(H)), valid


@pytest.mark.parametrize("T_,chunk,pads", [(64, 256, (0, 5)), (128, 32, (37, 0)), (200, 64, (0, 130)),
                                           (256, 256, (63, 200)), (7, 4, (0, 3)), (96, 96, (0, 0))])
def test_chunk_form_equals_the_recurrence(T_, chunk, pads):
    """Across sub-chunk boundaries (``chunk`` < T), with a ragged tail, with
    left pads. 2e-5 of values of order 10: float32 sums in another order."""
    (S0, *xs), _ = _draw(np.random.default_rng(T_), 2, T_, left_pad=pads)
    want, S_want = ssm.recurrence(S0, *xs)
    got, S_got = ssm.chunk_states(S0, *xs, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(S_got, S_want, atol=2e-5 * float(jnp.abs(S_want).max()))


def test_one_token_steps_equal_the_chunk_form_and_a_pad_moves_nothing():
    (S0, x, Bm, Cm, dt, dA, D), valid = _draw(np.random.default_rng(1), 2, 40, left_pad=(0, 6))
    want, S_want = ssm.chunk_states(S0, x, Bm, Cm, dt, dA, D)
    S, outs = S0, []
    for t in range(40):
        before = S
        y, S = ssm.state_step(S, x[:, t], Bm[:, t], Cm[:, t], dt[:, t], dA[:, t], D)
        outs.append(y)
        if t < 6:  # row 1 is a pad here: bit for bit
            np.testing.assert_array_equal(S[1], before[1])
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=1e-4)
    np.testing.assert_allclose(S, S_want, atol=1e-4)


def test_discretize_is_softplus_without_a_clamp():
    dt, dA = ssm.discretize(jnp.asarray([[-30.0, 0.0, 30.0]]), jnp.zeros(3), jnp.log(jnp.asarray([1., 4., 16.])))
    np.testing.assert_allclose(dt[0], [np.log1p(np.exp(-30.0)), np.log(2.0), 30.0], rtol=1e-6)
    np.testing.assert_allclose(dA[0], -dt[0] * np.asarray([1., 4., 16.]), rtol=1e-6)
    assert dt.dtype == dA.dtype == jnp.float32 and float(dA.max()) <= 0.0


@pytest.mark.parametrize("cuts,pad", [((150,), 0), ((64, 150), 0), ((7, 8, 9, 150), 0), ((64, 150), 11)])
def test_conv_state_with_bias_carries_across_chunk_boundaries(cuts, pad):
    """The shared short convolution (ops/kda.py) with a bias: chunks, then
    single rows, equal one pass; the first chunk may be left-padded."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 160, 24)), jnp.float32)
    w, b = jnp.asarray(rng.normal(size=(24, 4)), jnp.float32), jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    want, _ = kda.short_conv(x, w, None, None, bias=b)
    assert float(jnp.abs(want - kda.short_conv(x, w, None, None)[0]).max()) > 0.1
    state, outs, lo = None, [], 0
    for i, hi in enumerate(cuts):
        rows, valid = x[:, lo:hi], None
        if i == 0 and pad:
            rows = jnp.concatenate([jnp.full((1, pad, 24), 9.0), rows], axis=1)
            valid = jnp.asarray(np.arange(rows.shape[1])[None] >= pad)
        y, state = kda.short_conv(rows, w, state, valid, bias=b)
        outs.append(y[:, pad:] if i == 0 else y)
        lo = hi
    for t in range(lo, 160):
        y, state = kda.short_conv(x[:, t:t + 1], w, state, None, bias=b)
        outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=1e-5)


# ------------------------------------------------------- the model, float32

def _gained(params):
    """The benchmark's draw with every layer kernel times ``LAYER_GAIN``."""
    def gain(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        on = names[0] == "layers" and names[-1] == "kernel" and "conv" not in names
        return leaf * LAYER_GAIN if on else leaf

    return jax.tree_util.tree_map_with_path(gain, params)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("debug-granite")
    mc = dataclasses.asdict(cfg)
    params = _gained(draw.draw_params(mc, 11, dtype=jnp.float32))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, T), 10, cfg.vocab_size)
    return cfg, mc, params, tokens


def _ref_logits(mc, params, tokens, **kw):
    return jnp.stack([ref.sequence_logits(params, mc, np.asarray(row).tolist(),
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
    return forward(params, tokens, get_config("debug-granite"), cache=cache,
                   positions=positions, attention_mask=mask)


@jax.jit
def _step_in_place(params, tokens, cache, positions, mask=None):
    """``_step`` for an engine that asked for the paged kernels: a token step
    over a paged cache reads the attention layer's blocks in place through the
    decode kernel (interpreted here; scores x ``attention_multiplier``) and
    gathers no view, not of the positions either."""
    return forward(params, tokens, get_config("debug-granite", paged_kernel=True),
                   cache=cache, positions=positions, attention_mask=mask)


def test_runs_name_their_mixers_and_every_scalar_is_away_from_its_default(model):
    cfg = model[0]
    runs = layer_runs(cfg)
    assert [(r.mixer.name, r.ffn, r.count, r.kind_start) for r in runs] == [
        ("ssm", "dense", 2, 0), ("global", "dense", 1, 0), ("ssm", "dense", 3, 2)]
    assert ref.runs_of(model[1]) == [(r.mixer.name, r.ffn, r.count) for r in runs]
    kinds = mixer_kinds(cfg)
    assert kinds["ssm"].pools() == {} and kinds["global"].states(cfg) == {}
    assert kinds["ssm"].states(cfg) == {"state_ssm": ((8, 16, 32), "float32"),
                                        "state_ssm_conv": ((3, 8 * 16 + 2 * 32), None)}
    assert kinds["global"].rotary_dim == 0 and kinds["global"].scale == 0.125 != 16 ** -0.5
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (12.0, 0.22, 8.0)
    assert cfg.tie_word_embeddings and "lm_head" not in model[2]
    # a model without these fields is today's: every default is the old behaviour
    plain = get_config("debug-hybrid")
    assert (plain.embedding_multiplier, plain.attention_multiplier, plain.residual_multiplier,
            plain.logits_scaling) == (1.0, None, 1.0, 1.0)
    assert mixer_kinds(plain)["global"].scale is None


def test_full_forward_equals_reference(model, want):
    cfg, _, params, tokens = model
    got, cache = forward(params, tokens, cfg)
    assert cache is None and float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(got, want, atol=TOL)


def test_dense_cache_prefill_then_decode_equals_reference(model, want):
    cfg, _, params, tokens = model
    cache = init_cache(cfg, 2, 192, dtype=jnp.float32, per_slot=True)
    assert cache["k_global"].shape == cache["v_global"].shape == (1, 2, 192, 2 * 16)
    assert cache["state_ssm"].shape == (5, 2, 8, 16, 32) and cache["state_ssm"].dtype == jnp.float32
    assert cache["state_ssm_conv"].shape == (5, 2, 3, 192)
    assert kv_leaf_keys(cache) == ["k_global", "v_global"]
    assert state_leaf_keys(cache) == ["state_ssm", "state_ssm_conv"]
    out, cache = _step(params, tokens[:, :130], cache, _positions(0, 130))
    outs = [out]
    for t in range(130, T):
        out, cache = _step(params, tokens[:, t:t + 1], cache, _positions(t, t + 1))
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=TOL)


def _paged(cfg, block_size, dtype=jnp.float32):
    nbps = 192 // block_size
    cache = init_paged_cache(cfg, 2, 2 * nbps + 3, block_size, nbps, dtype=dtype)
    cache["block_tables"] = jnp.asarray(
        np.stack([np.arange(nbps) + nbps, np.arange(nbps)]), jnp.int32)
    return cache


def _through(params, tokens, cache, chunks, step=_step):
    outs = []
    for lo, hi in chunks:
        out, cache = step(params, tokens[:, lo:hi], cache, _positions(lo, hi))
        outs.append(out)
    for t in range(chunks[-1][1], T):
        out, cache = step(params, tokens[:, t:t + 1], cache, _positions(t, t + 1))
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("path", ["gather", "kernel"])
@pytest.mark.parametrize("block_size,chunks", [
    (8, ((0, 64), (64, 130))),          # two chunk programs, the state handed over
    (16, ((0, 130),)),                  # one chunk
    (4, ((0, 3), (3, 70), (70, 130))),  # a chunk shorter than the convolution
])
def test_paged_pool_chunked_prefill_then_decode_equals_reference(model, want, block_size, chunks, path):
    cfg, _, params, tokens = model
    cache = _paged(cfg, block_size)
    # what an earlier request left in the slots: a cursor at 0 reads it as zero
    cache["state_ssm"] = cache["state_ssm"] + 3.0
    cache["state_ssm_conv"] = cache["state_ssm_conv"] - 2.0
    step = _step_in_place if path == "kernel" else _step
    np.testing.assert_allclose(_through(params, tokens, cache, chunks, step), want, atol=TOL)


@pytest.mark.parametrize("step,calls", [("paged_token", 1), ("paged_chunk", 0), ("dense_token", 0)])
def test_only_a_token_step_over_a_paged_cache_takes_the_kernel(model, step, calls):
    """Asked for the paged kernels, ``forward`` traces the program it traced
    without them for a chunk and for a dense cache; a token step over a paged
    cache calls the decode kernel in its one run of attention layers and
    gathers no view of the pools ([2, 192, 2, 16])."""
    cfg = model[0]
    asked = dataclasses.replace(cfg, paged_kernel=True)
    n = 1 if step.endswith("token") else 8
    cache = (_paged(cfg, 8) if step.startswith("paged")
             else init_cache(cfg, 2, 192, dtype=jnp.float32, per_slot=True))
    plain, got = (str(jax.make_jaxpr(lambda p, ids, ch, c=c: forward(
        p, ids, c, cache=ch, positions=_positions(40, 40 + n)))(
            model[2], jnp.zeros((2, n), jnp.int32), cache)) for c in (cfg, asked))
    assert got.count("dtx_paged_decode") == calls and "dtx_paged_decode" not in plain
    if calls:
        assert "f32[2,192,2,16]" in plain and "f32[2,192,2,16]" not in got
    else:
        assert got == plain


def test_a_state_stored_in_bfloat16_fails_the_tolerance(model, want):
    """The nearest precision below the one the configuration states, in the
    one leaf that carries memory from token to token: it must not pass."""
    cfg, _, params, tokens = model
    cache = _paged(cfg, 8)
    cache["state_ssm"] = cache["state_ssm"].astype(jnp.bfloat16)
    got = _through(params, tokens, cache, ((0, 64), (64, 130)))
    assert float(jnp.abs(got - want).max()) > 50 * TOL


def test_left_pads_and_idle_rows_leave_the_state_alone(model, want):
    """Pads lie at a row's left and move neither state; a decode step whose
    row is idle (mask 0) leaves that slot's state bit for bit."""
    cfg, _, params, tokens = model
    pad = 14
    cache = init_paged_cache(cfg, 2, 60, 8, 24, dtype=jnp.float32)
    cache["block_tables"] = jnp.asarray(np.arange(48).reshape(2, 24), jnp.int32)
    ids = jnp.concatenate([jnp.full((2, pad), 7, tokens.dtype), tokens[:, :130]], axis=1)
    mask = jnp.concatenate([jnp.zeros((2, pad), jnp.int32), jnp.ones((2, 130), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.zeros((2, pad), jnp.int32), _positions(0, 130)], axis=1)
    out, cache = _step(params, ids, cache, pos, mask)
    np.testing.assert_allclose(out[:, pad:], want[:, :130], atol=TOL)
    before = {k: np.asarray(cache[k]) for k in state_leaf_keys(cache)}
    idle = jnp.asarray([[1], [0]], jnp.int32)
    out, cache = _step(params, tokens[:, 130:131], cache, _positions(130, 131), idle)
    np.testing.assert_allclose(out[0], want[0, 130:131], atol=TOL)
    for key, was in before.items():
        np.testing.assert_array_equal(np.asarray(cache[key])[:, 1], was[:, 1])
        assert np.abs(np.asarray(cache[key])[:, 0] - was[:, 0]).max() > 0


def test_extract_insert_moves_a_slots_state_with_its_rows(model):
    cfg, _, params, tokens = model
    cache = init_paged_cache(cfg, 2, 60, 8, 24, dtype=jnp.float32)
    cache["block_tables"] = jnp.asarray(np.arange(48).reshape(2, 24), jnp.int32)
    _, cache = _step(params, tokens[:, :50], cache, _positions(0, 50))
    row = paged_extract_row(cache, 1, 50, width=56)
    assert row["state_ssm"].shape == (5, 1, 8, 16, 32) and row["k_global"].shape == (1, 1, 56, 32)
    fresh = init_paged_cache(cfg, 2, 60, 8, 24, dtype=jnp.float32)
    table = jnp.asarray(list(range(20, 27)) + [-1] * 17, jnp.int32)
    fresh = paged_insert_row(fresh, 0, table, row)
    fresh["len"] = fresh["len"].at[0].set(50)
    np.testing.assert_array_equal(fresh["state_ssm"][:, 0], cache["state_ssm"][:, 1])
    np.testing.assert_array_equal(fresh["state_ssm_conv"][:, 0], cache["state_ssm_conv"][:, 1])

    def one(c, s):  # the cache as one slot sees it: pools whole, its own cursor, table and state
        return {k: (v[s:s + 1] if k in ("len", "block_tables") else
                    v[:, s:s + 1] if k.startswith("state_") else v) for k, v in c.items()}

    tok = tokens[1:2, 50:51]
    a, _ = forward(params, tok, cfg, positions=_positions(50, 51, 1), cache=one(cache, 1))
    b, _ = forward(params, tok, cfg, positions=_positions(50, 51, 1), cache=one(fresh, 0))
    np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("name,change", [
    ("embedding_multiplier", dict(embedding_multiplier=1.0)),
    ("attention_multiplier", dict(attention_multiplier=None)),
    ("residual_multiplier", dict(residual_multiplier=1.0)),
    ("logits_scaling", dict(logits_scaling=1.0)),
    ("no positions", dict(partial_rotary_factor=1.0)),
    ("convolution bias", ("conv", "bias")),
    ("D", ("D",)),
])
def test_each_mechanism_matters(model, want, name, change):
    """Each of Granite's scalars at its default, the rotation put back, the
    convolution's bias or ``D`` zeroed: the comparison fails."""
    cfg, _, params, tokens = model
    if isinstance(change, tuple):
        def zero(path, leaf):
            names = tuple(getattr(p, "key", None) for p in path)
            return jnp.zeros_like(leaf) if names[-len(change):] == change else leaf

        params = jax.tree_util.tree_map_with_path(zero, params)
        change = {}
    got, _ = forward(params, tokens[:1, :64], dataclasses.replace(cfg, **change))
    assert float(jnp.abs(got - want[:1, :64]).max()) > 1000 * TOL, name


# ------------------------------------------------------------ the engine

ENGINE = dict(slots=3, decode_chunk=4, kv_block_size=8, kv_blocks=96, max_seq_len=256, prefill_chunk=64)
TARGETS = ("in_proj", "q_proj", "o_proj")


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    d = tmp_path_factory.mktemp("granite_adapters")
    adapters = {"ad0": make_adapter_checkpoint(
        str(d / "ad0"), "preset:debug-granite", seed=10, rank=4, targets=TARGETS)}
    eng = BatchedEngine("preset:debug-granite", adapters=adapters, **ENGINE)
    # serve the benchmark's draw (the preset's own has every decay alike)
    eng.params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        _gained(draw.draw_params(dataclasses.asdict(eng.cfg), 12, dtype=jnp.float32)))
    yield eng
    eng.close()


def _gaps(engine, prompt, req, name=""):
    """How far each served token's logit lies below the reference's best, over
    the request's own full forward pass (the benchmark's comparison): logits,
    not tokens."""
    mc = dataclasses.asdict(engine.cfg)
    tokens = list(prompt) + list(req.tokens)
    rows = list(range(len(prompt) - 1, len(tokens) - 1))
    lora, scale = None, 0.0
    if name:
        stack, scales = engine.lora_stack
        i = engine.adapter_ids[name]
        lora = jax.tree_util.tree_map(lambda a: a[:, i], stack["layers"])
        scale = float(scales[i])
    logits = ref.sequence_logits(engine.params, mc, tokens, rows, lora, scale)
    got = jnp.take_along_axis(logits, jnp.asarray(req.tokens)[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(logits, axis=-1) - got)


def test_engine_serves_what_the_reference_puts_first(engine):
    """Prefill in chunks of 64 (left-padded to a bucket), then decode through
    the cache in steps of 4, six requests over three slots so that every slot
    is used twice, base and an adapter on ``in_proj`` / ``q_proj`` / ``o_proj``.
    The engine computes in bf16 and the reference in float32: a served token
    may differ from the reference's first where two logits lie within bf16's
    rounding of each other, so what is held is the GAP of logits, as the
    benchmark holds it: 0.004 at this width, where logits are a few hundredths
    and the reference's first and second choices lie 0.003 apart in the median
    (the sound engine reads 0.0012 at most; an adapter left out of the
    comparison reads 0.06 and more, a stale slot or a state not reset likewise)."""
    assert engine.decode_path == "gather"
    stack = engine.lora_stack[0]["layers"]
    assert stack["run0"]["in_proj"]["b"].shape[-1] == 2 * 128 + 2 * 32 + 8   # [z | x B C | dt]
    assert stack["run0"]["o_proj"]["a"].shape[-2] == 128 and "q_proj" not in stack["run0"]
    assert sorted(stack["run1"]) == ["o_proj", "q_proj"]                     # the attention run
    rng = np.random.default_rng(0)
    work = []
    for n, name in ((5, ""), (70, "ad0"), (130, ""), (33, "ad0"), (90, ""), (64, "ad0")):
        prompt = rng.integers(10, 500, size=n).tolist()
        work.append((prompt, name, engine.submit(prompt, max_new_tokens=12, adapter=name)))
    for prompt, name, req in work:
        assert req.done.wait(600) and req.error is None, req.error
        gaps = _gaps(engine, prompt, req, name)
        assert len(req.tokens) == 12 and gaps.max() < 0.004, (len(prompt), name, gaps)
        if name:  # the adapter carries weight: without it the comparison fails
            assert _gaps(engine, prompt, req).max() > 0.02
        # the choice is a contest, not a copy of the last token (a tied head under a large embedding multiplier)
        assert np.mean(np.asarray(req.tokens[1:]) == np.asarray(req.tokens[:-1])) < 0.5
    # five Mamba layers x three slots x (8 heads x 16 x 32 float32 + 3 rows x 192 bf16)
    assert engine.state_bytes() == 5 * 3 * (8 * 16 * 32 * 4 + 3 * 192 * 2)


def test_engine_serves_the_same_tokens_with_the_attention_layer_on_the_kernel(engine, tmp_path):
    """An engine that differs from the fixture's in ``paged_kernel`` alone (the
    same weights, the adapter on ``in_proj`` / ``q_proj`` / ``o_proj``): seven
    requests over three slots, so that every slot is released and taken again,
    prompts on and off the chunk's bucket, serve the same greedy tokens (or
    part at a tie of the reference's, each serving one of the two)."""
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    adapters = {"ad0": make_adapter_checkpoint(
        str(tmp_path / "ad0"), "preset:debug-granite", seed=10, rank=4, targets=TARGETS)}
    kernel = BatchedEngine("preset:debug-granite", adapters=adapters, paged_kernel="on", **ENGINE)
    try:
        kernel.params = engine.params
        assert kernel.decode_paths == {"global": "pallas"} and kernel.decode_path == "pallas"
        assert engine.decode_paths == {"global": "gather"}
        rng = np.random.default_rng(5)
        work = [(rng.integers(10, 500, size=n).tolist(), ad) for n, ad in (
            (5, ""), (64, "ad0"), (70, ""), (150, "ad0"), (33, ""), (129, "ad0"), (8, ""))]
        served = {}
        for name, eng in (("gather", engine), ("kernel", kernel)):
            reqs = [eng.submit(prompt, max_new_tokens=8 + 2 * i, adapter=ad)
                    for i, (prompt, ad) in enumerate(work)]
            for r in reqs:
                assert r.done.wait(600) and r.error is None, r.error
            served[name] = [r.tokens for r in reqs]
    finally:
        kernel.close()
    assert all(len(t) == 8 + 2 * i for i, t in enumerate(served["gather"]))
    # equal, or parted where the reference's first two choices lie within bf16's
    # rounding of each other (the kernel sums a softmax in another order than XLA)
    mc = dataclasses.asdict(engine.cfg)
    stack, scales = engine.lora_stack
    for (prompt, ad), a, b in zip(work, served["gather"], served["kernel"]):
        if a == b:
            continue
        at = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        i = engine.adapter_ids[ad]
        lora = jax.tree_util.tree_map(lambda w: w[:, i], stack["layers"]) if ad else None
        logits = ref.sequence_logits(engine.params, mc, prompt + a[:at], [len(prompt) + at - 1],
                                     lora, float(scales[i]) if ad else 0.0)[0]
        first, second = (int(t) for t in jnp.argsort(logits)[-1:-3:-1])
        assert {a[at], b[at]} == {first, second}, (len(prompt), ad, at)
        assert float(logits[first] - logits[second]) < 0.004, (len(prompt), ad, at)


def test_a_used_slot_serves_a_new_request_as_a_fresh_engine_does(engine):
    """A slot released and taken again starts from zero: its state leaves hold
    what the last request left, and the cursor at 0 reads them as zero."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    prompt = list(range(100, 177))
    first = engine.submit(prompt, max_new_tokens=9)
    assert first.done.wait(600) and first.error is None
    again = engine.submit(prompt, max_new_tokens=9)  # every slot has been used by now
    assert again.done.wait(600) and again.error is None
    fresh = BatchedEngine("preset:debug-granite", **dict(ENGINE, slots=1))
    try:
        fresh.params = engine.params
        new = fresh.submit(prompt, max_new_tokens=9)
        assert new.done.wait(600) and new.error is None
    finally:
        fresh.close()
    assert first.tokens == again.tokens == new.tokens
    assert float(jnp.abs(engine._cache["state_ssm"]).max()) > 0  # and the leaves are not zero


def test_an_idle_slots_state_does_not_move(engine):
    """One request decodes in one slot: the other slots' state leaves are, bit
    for bit, what they were."""
    for _ in range(100):
        if not any(r is not None for r in engine._slot_req):
            break
        time.sleep(0.05)
    before = {k: np.asarray(engine._cache[k]) for k in state_leaf_keys(engine._cache)}
    req = engine.submit(list(range(50, 90)), max_new_tokens=10)
    assert req.done.wait(600) and req.error is None
    after = {k: np.asarray(engine._cache[k]) for k in state_leaf_keys(engine._cache)}
    moved = [s for s in range(3)
             if any(not np.array_equal(after[k][:, s], before[k][:, s]) for k in before)]
    assert len(moved) == 1, moved


def test_metrics_count_the_new_state_leaves(engine):
    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats

    reg = Registry()
    export_moe_stats(reg, engine)
    text = reg.expose()
    assert f"dtx_serving_state_bytes {float(engine.state_bytes())}" in text or \
        f"dtx_serving_state_bytes {engine.state_bytes()}" in text
    assert "state-space" in text
    assert engine.engine_line["state_bytes"] == engine.state_bytes() > 0


@pytest.mark.parametrize("entry", ["prefix_cache", "spec_draft", "kv_overcommit", "export", "import",
                                   "migration_wire", "kv_quant", "trainer"])
def test_what_needs_a_snapshot_of_state_refuses_by_name(model, engine, entry):
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    cfg = model[0]
    with pytest.raises(NotImplementedError, match="debug-granite") as err:
        if entry == "prefix_cache":
            BatchedEngine("preset:debug-granite", prefix_cache=4, **ENGINE)
        elif entry == "spec_draft":
            BatchedEngine("preset:debug-granite", spec_draft="take:2", **ENGINE)
        elif entry == "kv_overcommit":
            BatchedEngine("preset:debug-granite", kv_overcommit="on", **ENGINE)
        elif entry == "export":
            engine.export_sessions()
        elif entry == "import":
            engine.import_session({})
        elif entry == "migration_wire":
            from datatunerx_tpu.serving import migration as mig

            mig.check_signature({}, cfg)
        elif entry == "kv_quant":
            init_cache(cfg, 1, 64, quantize="int8")
        elif entry == "trainer":
            from datatunerx_tpu.training.train_lib import TrainConfig, Trainer

            Trainer(cfg, TrainConfig())
    said = str(err.value)
    if entry in ("kv_quant", "trainer", "prefix_cache", "spec_draft"):  # they handle one kind of layer
        assert "several kinds" in said or "per mixer kind" in said
    else:  # the one message, and it names the kind that keeps the state
        assert "recurrent state per slot" in said and "(ssm)" in said and "linear" not in said


# ------------------------------------------- the token step as a Pallas kernel

@pytest.fixture(scope="module")
def wide():
    """``debug-granite`` with a state of 128: the width at which the token
    step's kernel has its tiles (ops/pallas_ssm.py; ``debug-granite``'s 32 and
    every test above take ``ssm.state_step``)."""
    from datatunerx_tpu.models.config import PRESETS

    cfg = dataclasses.replace(get_config("debug-granite"), name="debug-granite-n128", ssm_state=128)
    PRESETS[cfg.name] = cfg
    params = _gained(draw.draw_params(dataclasses.asdict(cfg), 13, dtype=jnp.float32))
    yield cfg, params
    del PRESETS[cfg.name]


def test_decode_through_the_kernel_equals_the_xla_step_and_the_reference(wide, monkeypatch):
    """Two slots of a paged cache admitted at different times: slot 0 is
    prefilled and decodes three tokens while slot 1 is idle at cursor 0 over
    what an earlier request left; slot 1 is prefilled; both decode five more.
    The kernel (interpret) against ``ssm.state_step`` forced, logits and state
    leaves, and both against the plain reference."""
    from datatunerx_tpu.ops import pallas_ssm

    cfg, params = wide
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 48), 10, cfg.vocab_size)
    n0, n1 = 40, 24  # the prompts' lengths

    def serve():
        step = jax.jit(lambda tokens, cache, positions, mask: forward(
            params, tokens, cfg, cache=cache, positions=positions, attention_mask=mask))
        cache = init_paged_cache(cfg, 2, 40, 8, 16, dtype=jnp.float32)
        cache["block_tables"] = jnp.asarray(np.arange(32).reshape(2, 16), jnp.int32)
        cache["state_ssm"] = cache["state_ssm"].at[:, 1].add(3.0)
        only = lambda s, T_: jnp.asarray(np.arange(2)[:, None] == s, jnp.int32) * jnp.ones((2, T_), jnp.int32)  # noqa: E731
        rows = [[], []]
        out, cache = step(tokens[:, :n0], cache, _positions(0, n0), only(0, n0))
        rows[0].append(out[0])
        for t in range(n0, n0 + 3):  # slot 1 stays where it was: idle, cursor 0
            cache["len"] = cache["len"].at[1].set(0)
            out, cache = step(tokens[:, t:t + 1], cache, _positions(t, t + 1), only(0, 1))
            rows[0].append(out[0])
        cache["len"] = cache["len"].at[1].set(0)
        out, cache = step(tokens[:, :n1], cache, _positions(0, n1), only(1, n1))
        rows[1].append(out[1])
        for k in range(5):
            ids = jnp.stack([tokens[0, n0 + 3 + k], tokens[1, n1 + k]])[:, None]
            pos = jnp.asarray([[n0 + 3 + k], [n1 + k]], jnp.int32)
            out, cache = step(ids, cache, pos, jnp.ones((2, 1), jnp.int32))
            rows[0].append(out[0])
            rows[1].append(out[1])
        return [jnp.concatenate(r) for r in rows], cache

    assert pallas_ssm.step_kernel(jax.ShapeDtypeStruct((5, 2, 8, 16, 128), jnp.float32), 1) == ("dtx_ssm_step", 8)
    got, got_cache = serve()
    monkeypatch.setattr(pallas_ssm, "step_kernel", lambda *a: ("xla", None))
    want, want_cache = serve()
    mc = dataclasses.asdict(cfg)
    for s, n in ((0, n0 + 8), (1, n1 + 5)):
        # rounding order of one 128-term sum a layer a token
        np.testing.assert_allclose(got[s], want[s], atol=TOL)
        ref_logits = ref.sequence_logits(params, mc, np.asarray(tokens[s, :n]).tolist(), list(range(n)))
        np.testing.assert_allclose(got[s], ref_logits, atol=2 * TOL)
        assert float(jnp.abs(ref_logits).max()) > 0.05
    for key in state_leaf_keys(got_cache):
        np.testing.assert_allclose(got_cache[key], want_cache[key], rtol=1e-5, atol=2e-5)
    assert float(jnp.abs(got_cache["state_ssm"]).max()) > 0


def test_the_engine_says_which_state_step_it_runs(wide, engine, capfd):
    """``engine.state_kernel``, the ``[engine]`` line and the gauge: the kernel
    at its head tile where the leaf's shapes give it tiles, the XLA step at
    ``debug-granite``'s state of 32; and the kernel's engine serves what the
    reference puts first, two requests admitted at different times."""
    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    cfg, _ = wide
    assert engine.state_kernel == engine.engine_line["state_kernel"] == {"decode": ("xla", None)}
    eng = BatchedEngine("preset:" + cfg.name, **dict(ENGINE, slots=2))
    try:
        assert eng.state_kernel == {"decode": ("dtx_ssm_step", 8)}
        line = [ln for ln in capfd.readouterr().err.splitlines() if ln.startswith("[engine] {")][-1]
        assert '"state_kernel": {"decode": ["dtx_ssm_step", 8]}' in line
        reg = Registry()
        export_moe_stats(reg, eng)
        assert 'dtx_serving_state_head_tile{kernel="dtx_ssm_step",phase="decode"} 8' in reg.expose()
        # the benchmark's draw at the engine's own vocabulary (the tokenizer's)
        eng.params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            _gained(draw.draw_params(dataclasses.asdict(eng.cfg), 13, dtype=jnp.float32)))
        first_prompt, second_prompt = list(range(100, 170)), list(range(300, 333))
        first = eng.submit(first_prompt, max_new_tokens=16)
        while not first.tokens and not first.done.is_set():  # the second joins a decoding engine
            time.sleep(0.01)
        second = eng.submit(second_prompt, max_new_tokens=12)
        for prompt, req, n in ((first_prompt, first, 16), (second_prompt, second, 12)):
            assert req.done.wait(600) and req.error is None, req.error
            gaps = _gaps(eng, prompt, req)
            assert len(req.tokens) == n and gaps.max() < 0.004, gaps
    finally:
        eng.close()
