"""A model whose every mixer is latent attention with a LEARNED SELECTION of
the cached tokens it reads (DeepSeek sparse attention: an indexer scores every
cached token, a query attends to its ``index_topk`` best), a low-rank query and
no head gate, with sigmoid-routed experts and a shared one (models/hybrid.py,
ops/dsa.py, ops/mla.py, ops/moe.py). The selection is tested first, against a
plain loop: everything else rests on it. Every model-level test is against the
plain reference ``benchmarks/reference/glm_5.py`` (float32, expanded heads, a
stable sort, no cache), at the ``debug-glm`` size on seeded weights, on the
LOGITS and on the SELECTED SETS."""

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

from reference import glm_5 as ref  # noqa: E402

from datatunerx_tpu.models import forward, get_config, init_params  # noqa: E402
from datatunerx_tpu.models import hybrid  # noqa: E402
from datatunerx_tpu.models.config import layer_runs, mixer_kinds  # noqa: E402
from datatunerx_tpu.models.llama import init_cache  # noqa: E402
from datatunerx_tpu.ops import dsa, mla  # noqa: E402
from datatunerx_tpu.ops.paged_attention import (  # noqa: E402
    init_paged_cache,
    kv_leaf_keys,
    paged_copy_block,
    paged_extract_row,
    paged_insert_row,
    row_trim,
    state_leaf_keys,
)

@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """These tests run ``forward`` op by op, a program a primitive and shape:
    some 50,000 memory maps of loaded executables by the file's end in ONE
    worker, and the kernel allows a process 65,530 (``vm.max_map_count``):
    past it XLA's loader segfaults inside jax's compilation cache. Dropped
    after each test, they come back from the session's persistent cache."""
    yield
    jax.clear_caches()


TOL = 2e-5  # float32 program against float32 reference: rounding order only
T = 150  # over index_topk (32): every later row selects
TOPK = 32


# ------------------------------------------------------------ the selection

def np_select(scores, visible, k):
    """Per row the k visible lanes of largest score, a tie to the lower lane:
    a plain loop that takes the best remaining lane k times."""
    out = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        left = [s for s in range(scores.shape[1]) if visible[r, s]]
        for _ in range(min(k, len(left))):
            best = max(left, key=lambda s: (scores[r, s], -s))
            out[r, best] = True
            left.remove(best)
    return out


def _tied_scores(seed, rows, lanes, levels):
    """Few distinct values, so that most rows tie at the cut."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, levels, size=(rows, lanes)).astype(np.float32) / 4


@pytest.mark.parametrize("seed,levels,k", [(0, 3, 8), (1, 2, 5), (2, 50, 8), (3, 1, 7), (4, 4, 40)])
def test_the_tie_rule_is_the_earlier_position(seed, levels, k):
    """``top_lanes`` and ``top_mask`` against the plain loop,
    on scores with few distinct values; a row that sees fewer than k lanes
    picks all it sees (k 40 of 24 lanes; the causal rows below k)."""
    S = 24
    scores = _tied_scores(seed, S, S, levels)
    scores[::2][scores[::2] == 0] = -0.0  # both zeros occur (negative head weights), and are one score
    visible = np.tril(np.ones((S, S), bool))
    visible[:, 5] = False  # a lane no row sees (a pad)
    want = np_select(scores, visible, k)
    lanes, real = dsa.top_lanes(jnp.asarray(scores)[None], jnp.asarray(visible)[None], min(k, S))
    got = np.zeros((S, S), bool)
    for r in range(S):
        got[r, np.asarray(lanes[0, r])[np.asarray(real[0, r])]] = True
    np.testing.assert_array_equal(got, want)
    # the same set with no sort, eagerly and compiled
    for top_mask in (dsa.top_mask, jax.jit(dsa.top_mask, static_argnums=2)):
        np.testing.assert_array_equal(
            np.asarray(top_mask(jnp.asarray(scores)[None], jnp.asarray(visible)[None], k)[0]), want)
    # the reference's stable sort says the same
    np.testing.assert_array_equal(np.asarray(ref.select(jnp.asarray(scores), jnp.asarray(visible), k)), want)
    assert int(want.sum(-1).max()) == min(k, S - 1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_mask_without_a_sort_is_the_sorts_set_on_any_float(seed):
    """Negative scores, both zeros, huge and tiny magnitudes, ``+inf`` among
    the scores themselves (``-inf`` is what an unseen lane ranks as: an index
    score is a finite sum), rows that see nothing, k 1 and k over the view."""
    rng = np.random.default_rng(seed)
    scores = (rng.normal(size=(2, 9, 200)) * 10.0 ** rng.integers(-30, 30, size=(2, 9, 200))).astype(np.float32)
    scores[0, 0, :50] = 0.0
    scores[0, 0, 50:90] = -0.0
    scores[0, 1, ::3] = np.inf
    visible = rng.uniform(size=scores.shape) < 0.8
    visible[1, 0] = False
    for k in (1, 17, 64, 200):
        lanes, real = dsa.top_lanes(jnp.asarray(scores), jnp.asarray(visible), k)
        want = np.zeros(scores.shape, bool)
        np.put_along_axis(want, np.asarray(lanes), np.asarray(real), axis=-1)
        np.testing.assert_array_equal(np.asarray(dsa.top_mask(jnp.asarray(scores), jnp.asarray(visible), k)), want)
        assert (want.sum(-1) == np.minimum(k, visible.sum(-1))).all()


@pytest.mark.parametrize("tokens,width,topk,path", [
    (1, 8704, 2048, "gather"), (256, 8704, 2048, "mask"), (1, 2048, 2048, "all"),
    (256, 1024, 2048, "all"), (1, 2064, 2048, "gather"), (150, 150, 32, "mask"),
    # the widths a chunk of cell 7 reaches (mla.view_steps): only the first is within the selection
    (256, 2048, 2048, "all"), (256, 4096, 2048, "mask"), (64, 6144, 2048, "mask"), (192, 8192, 2048, "mask")])
def test_a_steps_path_follows_from_its_shapes(tokens, width, topk, path):
    assert dsa.selection_path(tokens, width, topk) == path


@pytest.mark.parametrize("tokens,columns,block_size,topk,steps", [
    # cell 7: a chunk of a table of 8,704 lanes reads 2,048 / 4,096 / 6,144 / 8,192 / 8,704 of them
    (256, 544, 16, 2048, (128, 256, 384, 512, 544)),
    (64, 544, 16, 2048, (128, 256, 384, 512, 544)),
    (256, 512, 16, 2048, (128, 256, 384, 512)),
    (256, 129, 16, 2048, (128, 129)),     # one column over a step
    (256, 128, 16, 2048, ()),             # a table of one step: as before
    (256, 64, 16, 2048, ()),
    (1, 544, 16, 2048, ()),               # a token step gathers its picks
    # no indexer (0): a latent kind that reads all it sees steps by the module's constant, 1,024 lanes
    (256, 544, 16, 0, (64, 128, 192, 256, 320, 384, 448, 512, 544)),
    (256, 768, 16, 0, tuple(range(64, 832, 64))),   # cell 8: Kimi's table of 12,288 lanes, twelve widths
    (64, 768, 16, 0, tuple(range(64, 832, 64))),
    (256, 96, 16, 0, (64, 96)),           # cell 5: Ling's table of 1,536 lanes, a step and a half
    (256, 64, 16, 0, ()),                 # a table of one step
    (256, 64, 8, 0, ()),                  # debug-kimi's 512 lanes
    (1, 768, 16, 0, ()),                  # one token a row: sixteen slots share the program, the longest decides
    (64, 24, 8, 32, (4, 8, 12, 16, 20, 24)),    # debug-glm, the tests' table
    (8, 10, 16, 40, (2, 4, 6, 8, 10)),          # a step is whole blocks: 32 lanes of 40
    (8, 5, 16, 8, (1, 2, 3, 4, 5))])            # and at least one
def test_a_chunks_view_grows_in_steps_of_the_selection(tokens, columns, block_size, topk, steps):
    """The widths, in table columns, a chunk's view may take."""
    assert mla.view_steps(tokens, columns, block_size, topk) == steps


@pytest.mark.parametrize("cursor,tokens,lanes", [
    (0, 256, 2048), (1792, 256, 2048), (1793, 256, 4096), (1792, 64, 2048), (2047, 2, 4096),
    (3840, 256, 4096), (3841, 256, 6144), (5888, 256, 6144), (5889, 256, 8192), (7936, 256, 8192),
    (7937, 256, 8704), (8448, 256, 8704), (9000, 256, 8704),   # a reach past the table: the table
    (0, 1, 8704), (5000, 1, 8704)])                            # a token step views no less
def test_the_views_width_is_its_reach_in_whole_steps(cursor, tokens, lanes):
    """``len + T`` rounded up to whole steps of 2,048 lanes, cut at the
    table: what the scheduler counts is the branch the program's switch
    takes."""
    assert mla.view_lanes(cursor, tokens, 2048, 16, 544) == lanes
    steps = mla.view_steps(tokens, 544, 16, 2048)
    if steps:  # the program's branch, from a traced reach (models/hybrid.py); lax.switch holds it to the last
        taken = int(jnp.clip((jnp.asarray(cursor + tokens) - 1) // 2048, 0, len(steps) - 1))
        assert steps[taken] * 16 == lanes
    # a kind that does not select steps by the module's constant, half as many lanes
    assert mla.VIEW_STEP_LANES == 1024 and mla.view_lanes(cursor, tokens, 0, 16, 544) in (lanes, lanes - 1024)


@pytest.mark.parametrize("cursor,tokens,lanes", [
    (0, 256, 1024), (768, 256, 1024), (769, 256, 2048), (1792, 256, 2048), (1793, 256, 3072), (6144, 256, 7168),
    (7936, 256, 8192), (7937, 256, 9216), (10240, 256, 11264), (12032, 256, 12288), (12288, 256, 12288),  # past the table
    # a suffix under the prefix cache starts at its shared base, a lane cursor with its pads: three chunks
    (8448, 256, 9216), (8704, 256, 9216), (8960, 256, 9216), (10176, 64, 10240), (10177, 64, 11264),
    (0, 1, 12288), (9000, 1, 12288)])                          # a token step views no less
def test_a_kind_that_reads_all_it_sees_views_its_reach_in_steps_of_its_own(cursor, tokens, lanes):
    """Cell 8's table (768 columns of 16) under a kind with no indexer: the
    scheduler's count and the branch the program's switch takes."""
    assert mla.view_lanes(cursor, tokens, 0, 16, 768) == lanes
    steps = mla.view_steps(tokens, 768, 16, 0)
    if steps:
        taken = int(jnp.clip((jnp.asarray(cursor + tokens) - 1) // mla.VIEW_STEP_LANES, 0, len(steps) - 1))
        assert steps[taken] * 16 == lanes
    assert mla.view_lanes(cursor, tokens, 0, 16, 96) == (1024 if tokens > 1 and cursor + tokens <= 1024 else 1536)  # Ling's


@pytest.mark.parametrize("prompt,chunk,share", [
    (8192, 256, 0.5882), (6400, 256, 0.4894), (4096, 256, 0.3529), (4160, 256, 0.3737), (2048, 256, 0.2353)])
def test_a_prompts_chunks_view_a_share_of_the_table_that_follows_from_its_length(prompt, chunk, share):
    """A prompt walked chunk by chunk (the last one its remainder) under cell
    7's table: the lanes its chunks view over the lanes of as many tables, as
    the engine's counter adds them up; within the selection it is one step's."""
    cuts = list(range(0, prompt, chunk)) + [prompt]
    views = [mla.view_lanes(lo, hi - lo, 2048, 16, 544) for lo, hi in zip(cuts, cuts[1:])]
    assert all(v in (2048, 4096, 6144, 8192) for v in views) and views == sorted(views)
    assert views[0] == 2048 and views[-1] == min(-(-prompt // 2048) * 2048, 8704)
    assert round(sum(views) / (len(views) * 8704), 4) == share


def test_rows_are_gathered_through_the_block_table():
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(3, 10, 4, 6)), jnp.float32)
    tables = jnp.asarray([[3, 1, 7, -1], [9, 0, 2, 5]], jnp.int32)
    lanes = jnp.asarray([[0, 5, 11, 2], [15, 3, 8, 9]], jnp.int32)
    got = dsa.gather_rows(pool, jnp.int32(1), lanes, tables)
    for b in range(2):
        for j, lane in enumerate(np.asarray(lanes[b])):
            np.testing.assert_array_equal(got[b, j], pool[1, tables[b, lane // 4], lane % 4])
    dense = jnp.asarray(rng.normal(size=(3, 2, 16, 6)), jnp.float32)
    got = dsa.gather_rows(dense, jnp.int32(2), lanes)
    np.testing.assert_array_equal(got[1, 0], dense[2, 1, 15])


def test_index_scores_are_the_weighted_relu_of_the_products():
    rng = np.random.default_rng(1)
    q, w = rng.normal(size=(1, 5, 3, 8)), rng.normal(size=(1, 5, 3))
    k = rng.normal(size=(1, 11, 8))
    want = np.einsum("th,ths->ts", w[0], np.maximum(np.einsum("thd,sd->ths", q[0], k[0]), 0))
    got = dsa.index_scores(jnp.asarray(q, jnp.float32), jnp.asarray(w, jnp.float32),
                           jnp.asarray(k, jnp.float32))
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    np.testing.assert_allclose(ref.index_scores(jnp.asarray(q[0], jnp.float32), jnp.asarray(w[0], jnp.float32),
                                                jnp.asarray(k[0], jnp.float32), "f32"), want, atol=1e-5)


# ------------------------------------------------------ the whole forward

@pytest.fixture(scope="module")
def model():
    cfg = get_config("debug-glm")
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


@pytest.fixture(scope="module")
def want_sets(model):
    """[row][position] -> the set of frozensets the five layers select there."""
    _, mc, params, tokens = model
    out = []
    for row in np.asarray(tokens):
        chosen = np.asarray(ref.sequence_selected(params, mc, [int(t) for t in row]))
        out.append([{frozenset(np.flatnonzero(chosen[layer, t])) for layer in range(chosen.shape[0])}
                    for t in range(len(row))])
    return out


def _positions(lo, hi, batch=2):
    return jnp.broadcast_to(jnp.arange(lo, hi, dtype=jnp.int32)[None], (batch, hi - lo))


@pytest.fixture()
def picks(monkeypatch):
    """Every selection the program makes while the test runs, whichever form
    took it (the lanes of a token step, the mask of a chunk), as masks
    [B, T, S], in some order of layers."""
    seen = []
    real_lanes, real_mask = dsa.top_lanes, dsa.top_mask

    def note_lanes(lanes, real, width):
        mask = np.zeros(lanes.shape[:2] + (int(width),), bool)
        np.put_along_axis(mask, np.asarray(lanes), np.asarray(real), axis=-1)
        seen.append(mask)

    def spy_lanes(scores, visible, k):
        lanes, real = real_lanes(scores, visible, k)
        jax.debug.callback(note_lanes, lanes, real, scores.shape[-1])
        return lanes, real

    def spy_mask(scores, visible, k):
        mask = real_mask(scores, visible, k)
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), mask)
        return mask

    monkeypatch.setattr(dsa, "top_lanes", spy_lanes)
    monkeypatch.setattr(dsa, "top_mask", spy_mask)
    return seen


def _check_sets(seen, want_sets, lo, hi):
    """The program's selections of positions lo..hi-1 are the reference's, in
    every layer (the layers as a set: callbacks come in no promised order)."""
    jax.effects_barrier()
    assert len(seen) == 5, len(seen)
    for b in range(2):
        for j, t in enumerate(range(lo, hi)):
            got = {frozenset(np.flatnonzero(mask[b, j])) for mask in seen}
            assert got == want_sets[b][t], (b, t)
    seen.clear()


def test_runs_name_their_mixer_and_both_pools(model):
    cfg, mc, _, _ = model
    runs = layer_runs(cfg)
    assert [(r.mixer.name, r.ffn, r.count, r.kind_start) for r in runs] == [
        ("mla", "dense", 1, 0), ("mla", "experts", 4, 1)]
    assert ref.runs_of(mc) == [(r.mixer.name, r.ffn, r.count) for r in runs]
    kind = mixer_kinds(cfg)["mla"]
    # the latent row [c 32 | kR 8] is stored a whole lane tile wide: single rows are gathered
    assert kind.pools() == {"k_mla": 128, "k_idx": 16} and kind.states(cfg) == {}
    assert (kind.q_lora_rank, kind.head_gate, kind.index_heads, kind.index_topk) == (48, False, 3, TOPK)
    assert kind.index_rope_dim == kind.rope_dim == 8
    # Ling's kind is what it was: no indexer, one pool, a gate, q straight from x
    ling = mixer_kinds(get_config("debug-ling"))["mla"]
    assert ling.pools() == {"k_mla": 40} and ling.head_gate and not ling.q_lora_rank and not ling.index_topk
    assert set(hybrid.attn_dims(cfg, kind)) == {"q_b_proj", "o_proj"}
    assert set(hybrid.attn_dims(get_config("debug-ling"), ling)) == {"q_proj", "o_proj"}
    shapes = hybrid.mixer_shapes(cfg, kind)
    assert ("g_proj", "kernel") not in shapes and ("q_proj", "kernel") not in shapes
    assert shapes[("indexer", "wq_b", "kernel")] == (48, 3 * 16)
    assert shapes[("indexer", "k_norm", "bias")] == (16,)


@pytest.mark.parametrize("length", [24, TOPK, TOPK + 1, T])
def test_full_forward_equals_reference(model, want, length):
    """Contexts under, at and over ``index_topk``: up to it every row reads
    all it sees (the step never runs the indexer), past it the later rows select."""
    cfg, mc, params, tokens = model
    got, cache = forward(params, tokens[:, :length], cfg)
    assert cache is None
    np.testing.assert_allclose(got, _ref_logits(mc, params, tokens[:, :length]), atol=TOL)
    np.testing.assert_allclose(got, want[:, :length], atol=TOL)  # causal: a prefix is a prefix


def test_dense_cache_prefill_then_decode_equals_reference(model, want, want_sets, picks):
    cfg, _, params, tokens = model
    cache = init_cache(cfg, 2, 192, dtype=jnp.float32, per_slot=True)
    assert cache["k_mla"].shape == (5, 2, 192, 128) and cache["k_idx"].shape == (5, 2, 192, 16)
    assert kv_leaf_keys(cache) == ["k_mla", "k_idx"] and state_leaf_keys(cache) == []
    out, cache = forward(params, tokens[:, :130], cfg, cache=cache, positions=_positions(0, 130))
    _check_sets(picks, want_sets, 0, 130)
    outs = [out]
    for t in range(130, T):
        out, cache = forward(params, tokens[:, t:t + 1], cfg, cache=cache, positions=_positions(t, t + 1))
        _check_sets(picks, want_sets, t, t + 1)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=TOL)


@pytest.mark.parametrize("block_size,chunks", [
    (8, ((0, 64), (64, 130))),
    (16, ((0, 130),)),
    (4, ((0, 3), (3, 70), (70, 130))),
])
def test_paged_pool_chunked_prefill_then_decode_equals_reference(model, want, want_sets, picks,
                                                                 block_size, chunks):
    """Prefill in chunks, then decode a token at a time, through a paged cache
    whose block table is SCATTERED (a seeded permutation of the pool, the two
    slots' blocks interleaved): the logits and, in every layer and at every
    position, the selected sets are the reference's full forward's."""
    cfg, _, params, tokens = model
    nbps = 192 // block_size
    cache = init_paged_cache(cfg, 2, 2 * nbps + 3, block_size, nbps, dtype=jnp.float32)
    table = np.random.default_rng(block_size).permutation(2 * nbps + 3)[:2 * nbps]
    cache["block_tables"] = jnp.asarray(table.reshape(nbps, 2).T.copy(), jnp.int32)
    # what earlier requests left in the pools: no position says it is there
    cache["k_idx"] = cache["k_idx"] + 50.0
    cache["k_mla"] = cache["k_mla"] - 7.0
    outs = []
    for lo, hi in chunks:
        out, cache = forward(params, tokens[:, lo:hi], cfg, cache=cache, positions=_positions(lo, hi))
        if hi > TOPK:  # a chunk that reaches no further than the selection runs no indexer
            _check_sets(picks, want_sets, lo, hi)
        outs.append(out)
    for t in range(130, T):
        out, cache = forward(params, tokens[:, t:t + 1], cfg, cache=cache, positions=_positions(t, t + 1))
        _check_sets(picks, want_sets, t, t + 1)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want, atol=TOL)
    # what the steps did, counted once a step: decode 20 steps of 2 rows at
    # contexts 131..150 selecting 32 each; prefill rows select min(32, context)
    stats = np.asarray(cache["dsa_stats"])
    assert list(stats[0]) == [20, 40, 2 * sum(range(131, 151)), 40 * TOPK]
    assert list(stats[1]) == [len(chunks), 260, 2 * sum(range(1, 131)),
                              2 * sum(min(TOPK, c) for c in range(1, 131))]


@pytest.mark.parametrize("edge", [-1, 0, 1])
def test_a_chunk_views_what_its_context_reaches(model, want, want_sets, picks, monkeypatch, edge):
    """Chunks whose reach (``len + T``) sits just under, at and just over each
    step of 32 lanes, the last one left-padded, in a table whose columns past
    the prompt have no block: the lanes read follow the reach, a chunk within
    the selection runs no indexer, and logits and selected sets are the
    reference's and the table-wide path's."""
    cfg, _, params, tokens = model
    block_size, nbps, pad, end = 8, 24, 5, 140
    cuts = [0] + [32 * k + edge for k in range(1, 5)] + [end]
    table = np.full((2, nbps), -1, np.int32)
    held = -(-(end + pad) // block_size)
    table[:, :held] = np.random.default_rng(edge + 1).permutation(2 * nbps)[:2 * held].reshape(2, held)
    read, real_attention = [], hybrid.xla_attention  # the lanes each layer's attention read

    def spy_attention(q, k, v, bias, **kw):
        jax.debug.callback(lambda _: read.append(k.shape[1]), q[0, 0, 0, 0])  # the branch taken speaks
        return real_attention(q, k, v, bias, **kw)

    monkeypatch.setattr(hybrid, "xla_attention", spy_attention)

    def prefill(stepped):
        cache = init_paged_cache(cfg, 2, 2 * nbps, block_size, nbps, dtype=jnp.float32)
        cache["block_tables"] = jnp.asarray(table)
        cache["k_idx"] = cache["k_idx"] + 50.0  # what earlier requests left in the pools
        cache["k_mla"] = cache["k_mla"] - 7.0
        outs, sets = [], []
        for lo, hi in zip(cuts, cuts[1:]):
            n_pad = pad if hi == end else 0
            ids = jnp.concatenate([jnp.full((2, n_pad), 7, tokens.dtype), tokens[:, lo:hi]], axis=1)
            mask = jnp.concatenate([jnp.zeros((2, n_pad), jnp.int32), jnp.ones((2, hi - lo), jnp.int32)], axis=1)
            pos = jnp.concatenate([jnp.zeros((2, n_pad), jnp.int32), _positions(lo, hi)], axis=1)
            out, cache = forward(params, ids, cfg, cache=cache, positions=pos, attention_mask=mask)
            outs.append(out[:, n_pad:])
            jax.effects_barrier()
            reach = hi + n_pad
            if stepped:  # each of the five layers read as far as the chunk reached, in whole steps
                assert read == [mla.view_lanes(lo, reach - lo, TOPK, block_size, nbps)] * 5 == [-(-reach // 32) * 32] * 5
            else:
                assert read == [nbps * block_size] * 5
            # the indexer ran over as many lanes as attention read (the whole view on the
            # table-wide path), or (a reach within the selection) not at all
            width = -(-reach // 32) * 32 if stepped else nbps * block_size
            assert [m.shape for m in picks] == ([(2, reach - lo, width)] * 5
                                                if reach > TOPK or not stepped else []), (lo, hi)
            for j, t in enumerate(range(lo, hi)):  # a lane past the pads is its position + pad
                sets.append([{frozenset(lane - (n_pad if lane >= lo else 0) for lane in np.flatnonzero(m[b, n_pad + j]))
                              for m in picks} for b in range(2)])
            picks.clear()
            read.clear()
        assert int(cache["len"][0]) == end + pad
        return jnp.concatenate(outs, axis=1), sets

    got, got_sets = prefill(True)
    np.testing.assert_allclose(got, want[:, :end], atol=TOL)
    for t, per_row in enumerate(got_sets):
        for b in range(2):  # no indexer: every visible token, nothing to compare
            assert per_row[b] == (want_sets[b][t] if t >= cuts[1] or edge > 0 else set()), (b, t)
    # the table-wide path (as the step was before its view followed its reach): the same
    monkeypatch.setattr(mla, "view_steps", lambda *a: ())
    wide, wide_sets = prefill(False)
    np.testing.assert_allclose(got, wide, atol=TOL)
    assert all(a[b] in (set(), w[b]) for a, w in zip(got_sets, wide_sets) for b in range(2))
    assert all(w[b] == want_sets[b][t] for t, w in enumerate(wide_sets) for b in range(2))


def test_left_pads_and_idle_rows_select_nothing_of_theirs(model, want):
    """Pads lie at a row's left with the sentinel for a position: no query
    selects them; an idle row of a decode step (mask 0) counts for nothing."""
    cfg, _, params, tokens = model
    pad = 14
    cache = init_paged_cache(cfg, 2, 60, 8, 24, dtype=jnp.float32)
    cache["block_tables"] = jnp.asarray(np.arange(48).reshape(2, 24), jnp.int32)
    ids = jnp.concatenate([jnp.full((2, pad), 7, tokens.dtype), tokens[:, :130]], axis=1)
    mask = jnp.concatenate([jnp.zeros((2, pad), jnp.int32), jnp.ones((2, 130), jnp.int32)], axis=1)
    pos = jnp.concatenate([jnp.zeros((2, pad), jnp.int32), _positions(0, 130)], axis=1)
    out, cache = forward(params, ids, cfg, cache=cache, positions=pos, attention_mask=mask)
    np.testing.assert_allclose(out[:, pad:], want[:, :130], atol=TOL)
    idle = jnp.asarray([[1], [0]], jnp.int32)
    out, cache = forward(params, tokens[:, 130:131], cfg, cache=cache, positions=_positions(130, 131),
                         attention_mask=idle)
    np.testing.assert_allclose(out[0], want[0, 130:131], atol=TOL)
    assert list(np.asarray(cache["dsa_stats"][0])) == [1, 1, 131, TOPK]


@pytest.mark.parametrize("case", ["context within index_topk", "index_topk at max_seq_len"])
def test_a_selection_of_everything_is_plain_latent_attention(model, case):
    """Where a step's view is no wider than ``index_topk`` the program takes
    plain latent attention's path (``selection_path``) and gives its result:
    the same model without an indexer, bit for bit."""
    cfg, _, params, tokens = model
    plain = dataclasses.replace(cfg, index_topk=0)  # the indexer's leaves are then not read
    if case == "context within index_topk":
        ids, sel = tokens[:, :TOPK], cfg
    else:
        ids, sel = tokens, dataclasses.replace(cfg, index_topk=cfg.max_seq_len)
    a, _ = forward(params, ids, sel)
    b, _ = forward(params, ids, plain)
    np.testing.assert_array_equal(a, b)
    # through a cache as wide as the selection, decode too
    wide = dataclasses.replace(cfg, index_topk=64)
    cache = init_paged_cache(wide, 2, 20, 8, 8, dtype=jnp.float32)
    bare = init_paged_cache(plain, 2, 20, 8, 8, dtype=jnp.float32)
    assert "k_idx" in cache and "k_idx" not in bare and "dsa_stats" not in bare
    for c in (cache, bare):
        c["block_tables"] = jnp.asarray(np.arange(16).reshape(2, 8), jnp.int32)
    outs = []
    for c, conf in ((cache, wide), (bare, plain)):
        o1, c = forward(params, tokens[:, :40], conf, cache=c, positions=_positions(0, 40))
        o2, c = forward(params, tokens[:, 40:41], conf, cache=c, positions=_positions(40, 41))
        outs.append(jnp.concatenate([o1, o2], axis=1))
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("name,change", [
    ("no selection", dict(index_topk=0)),
    ("a wider selection", dict(index_topk=48)),
    ("the key norm's bias", "indexer.k_norm.bias"),
    ("the index weights' sign", "indexer.weights_proj.kernel"),
    ("the query's norm", "q_a_layernorm.scale"),
    ("unscaled routing", dict(routed_scaling_factor=1.0)),
])
def test_each_mechanism_matters(model, want, name, change):
    cfg, _, params, tokens = model
    if isinstance(change, str):
        def flip(run):
            run = jax.tree_util.tree_map(lambda a: a, run)
            node, path = run, change.split(".")
            for part in path[:-1]:
                node = node[part]
            node[path[-1]] = -2.0 * node[path[-1]]
            return run
        params = dict(params, layers={k: flip(run) for k, run in params["layers"].items()})
        change = {}
    got, _ = forward(params, tokens[:1], dataclasses.replace(cfg, **change))
    assert float(jnp.abs(got - want[:1]).max()) > 1e-3, name


# ------------------------------------------ whatever moves a row moves both

def _prefilled(model, slots=2):
    cfg, _, params, tokens = model
    cache = init_paged_cache(cfg, slots, 60, 8, 24, dtype=jnp.float32)
    cache["block_tables"] = jnp.asarray(np.arange(48).reshape(2, 24), jnp.int32)
    _, cache = forward(params, tokens[:, :50], cfg, cache=cache, positions=_positions(0, 50))
    return cache


def _one(cache, s):
    """The cache as one slot sees it: pools whole, its own cursor and table."""
    return {k: (v[s:s + 1] if k in ("len", "block_tables") else v) for k, v in cache.items()}


def test_extract_insert_round_trips_both_pools(model):
    cfg, _, params, tokens = model
    cache = _prefilled(model)
    row = paged_extract_row(cache, 1, 50, width=56)
    assert row["k_mla"].shape == (5, 1, 56, 128) and row["k_idx"].shape == (5, 1, 56, 16)
    fresh = init_paged_cache(cfg, 2, 60, 8, 24, dtype=jnp.float32)
    fresh["k_idx"] = fresh["k_idx"] + 99.0  # stale keys where nothing is written
    table = jnp.asarray(list(range(20, 27)) + [-1] * 17, jnp.int32)
    fresh = paged_insert_row(fresh, 0, table, row)
    fresh["len"] = fresh["len"].at[0].set(50)
    back = paged_extract_row(fresh, 0, 50, width=56)
    for key in ("k_mla", "k_idx", "pos"):
        np.testing.assert_array_equal(back[key], row[key])
    tok = tokens[1:2, 50:51]
    a, _ = forward(params, tok, cfg, positions=_positions(50, 51, 1), cache=_one(cache, 1))
    b, _ = forward(params, tok, cfg, positions=_positions(50, 51, 1), cache=_one(fresh, 0))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_copy_on_write_and_trim_carry_the_index_keys(model):
    cfg, _, params, tokens = model
    cache = _prefilled(model)
    # slot 1's blocks are 24..30; its tail block (6 of 8 lanes written) copied onto a free one
    copied = paged_copy_block(cache, 30, 55, 2)
    for key in ("k_mla", "k_idx"):
        np.testing.assert_array_equal(copied[key][:, 55], cache[key][:, 30])
        assert float(jnp.abs(cache[key][:, 30]).max()) > 0
    assert list(np.asarray(copied["pos"][55])) == [48, 49] + [2**30] * 6
    # a second owner of the shared prefix decodes from the copy as the first does from its own
    shared = dict(copied, block_tables=copied["block_tables"].at[0].set(
        copied["block_tables"][1].at[6].set(55)), len=copied["len"].at[0].set(50))
    tok = tokens[1:2, 50:51]
    a, _ = forward(params, tok, cfg, positions=_positions(50, 51, 1), cache=_one(cache, 1))
    b, _ = forward(params, tok, cfg, positions=_positions(50, 51, 1), cache=_one(shared, 0))
    np.testing.assert_allclose(a, b, atol=1e-6)
    row = row_trim(paged_extract_row(cache, 1, 50), 50)
    assert row["k_mla"].shape[2] == row["k_idx"].shape[2] == row["pos"].shape[1] == 50


def test_migration_wire_carries_both_pools(model):
    from datatunerx_tpu.serving import migration as mig

    cfg, _, params, tokens = model
    cache = init_paged_cache(cfg, 2, 60, 8, 24, dtype=jnp.bfloat16)
    cache["block_tables"] = jnp.asarray(np.arange(48).reshape(2, 24), jnp.int32)
    _, cache = forward(params, tokens[:, :50], cfg, cache=cache, positions=_positions(0, 50),
                       compute_dtype=jnp.bfloat16)
    row = paged_extract_row(cache, 0, 50, width=64)
    doc = json.loads(json.dumps(mig.pack_kv_row(row, 50, "bf16")))
    assert set(doc["pools"]) == {"k_mla", "k_idx"} and doc["width"] == 50
    back = mig.unpack_kv_row(doc, full_width=128, quantize=None)
    for key in ("k_mla", "k_idx"):
        np.testing.assert_array_equal(np.asarray(back[key][:, :, :50], np.float32),
                                      np.asarray(row[key][:, :, :50], np.float32))
    sig = mig.model_signature(cfg, None)
    assert sig["pools"] == {"mla": [5, 128, 16]}
    payload = {"model_sig": sig, "kind": mig.PAYLOAD_KIND, "version": mig.PAYLOAD_VERSION}
    mig.check_signature(payload, cfg)  # no recurrent state: a session may move
    with pytest.raises(ValueError, match="incompatible model"):  # an engine without the index keys
        mig.check_signature(payload, dataclasses.replace(cfg, index_topk=0))


# ------------------------------------------------------------ the engine

ENGINE = dict(slots=3, decode_chunk=4, kv_block_size=8, kv_blocks=96, max_seq_len=256, prefill_chunk=64)


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    d = tmp_path_factory.mktemp("glm_adapters")
    adapters = {f"ad{i}": make_adapter_checkpoint(
        str(d / f"ad{i}"), "preset:debug-glm", seed=10 + i, rank=4, targets=("q_b_proj", "o_proj"))
        for i in range(2)}
    eng = BatchedEngine("preset:debug-glm", adapters=adapters, **ENGINE)
    yield eng
    eng.close()


def _gaps(engine, prompt, req, name=""):
    """How far each served token's logit lies below the reference's best, over
    the request's own full forward (the benchmark's comparison)."""
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


def _idle(engine):
    deadline = time.monotonic() + 60
    while any(r is not None for r in engine._slot_req) and time.monotonic() < deadline:
        time.sleep(0.02)
    time.sleep(0.1)


def test_engine_serves_what_the_reference_puts_first(engine, capfd):
    """Prefill in chunks of 64, then decode through both pools in steps of 4,
    seven requests over three slots (every slot used again), the base and two
    adapters on ``q_b_proj`` / ``o_proj``, every context past ``index_topk``
    by its end. The engine computes in bf16 and the reference in float32, so
    what is held is the GAP, as the benchmark holds it."""
    assert engine.decode_path == "gather"
    stack = engine.lora_stack[0]["layers"]
    assert stack["run1"]["q_b_proj"]["a"].shape[-2:] == (48, 4)           # from the bottleneck
    assert stack["run1"]["q_b_proj"]["b"].shape[-1] == 4 * (16 + 8)       # to H * (nope + rope)
    assert sorted(stack["run0"]) == ["o_proj", "q_b_proj"]
    rng = np.random.default_rng(0)
    before = dict(engine.dsa_stats)
    work = []
    for n, name in ((5, ""), (70, "ad0"), (130, "ad1"), (33, "ad0"), (90, ""), (64, "ad1"), (180, "")):
        prompt = rng.integers(10, 500, size=n).tolist()
        work.append((prompt, name, engine.submit(prompt, max_new_tokens=12, adapter=name)))
    for prompt, name, req in work:
        assert req.done.wait(600) and req.error is None, req.error
        gaps = _gaps(engine, prompt, req, name)
        assert len(req.tokens) == 12 and gaps.max() < 0.05, (len(prompt), name, gaps)
    _idle(engine)
    got = {k: engine.dsa_stats[k] - before[k] for k in before}
    # the decode rows: every emitted token is forwarded (the last one's logits
    # go unused), at contexts prompt + 1 ... prompt + 12
    contexts = [len(p) + 1 + i for p, _, _ in work for i in range(12)]
    assert got["decode_rows"] == len(contexts) == 84
    assert got["decode_context"] == sum(contexts)
    assert got["decode_selected"] == sum(min(TOPK, c) for c in contexts)
    assert 0 < got["decode_steps"] <= got["decode_rows"]
    assert got["prefill_rows"] == sum(len(p) for p, _, _ in work)
    assert got["prefill_selected"] == sum(min(TOPK, c) for p, _, _ in work for c in range(1, len(p) + 1))
    assert engine.state_bytes() == 0
    assert engine.index_pool_bytes() == 5 * 96 * 8 * 16 * 2


def test_a_released_slots_stale_index_keys_are_never_selected(engine):
    """Every block of both pools is filled with what would win every selection
    (index keys of 1e4) and every attention (latent rows of 1e4), as rows of
    finished requests would lie there: a new request reads only what it wrote
    itself, and serves what a fresh engine serves."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    prompt = list(range(100, 177))
    first = engine.submit(prompt, max_new_tokens=9)
    assert first.done.wait(600) and first.error is None
    _idle(engine)
    engine._cache = dict(engine._cache,
                         k_idx=jnp.full_like(engine._cache["k_idx"], 1e4),
                         k_mla=jnp.full_like(engine._cache["k_mla"], 1e4))
    again = engine.submit(prompt, max_new_tokens=9)
    assert again.done.wait(600) and again.error is None
    fresh = BatchedEngine("preset:debug-glm", **dict(ENGINE, slots=1))
    try:
        new = fresh.submit(prompt, max_new_tokens=9)
        assert new.done.wait(600) and new.error is None
    finally:
        fresh.close()
    assert first.tokens == again.tokens == new.tokens


def test_engine_moves_a_live_session_between_replicas(engine):
    """Export mid-decode, import on a second engine: both pools travel and the
    continuation is the undisturbed run's."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    prompt = list(range(200, 290))
    want = engine.submit(prompt, max_new_tokens=24)
    assert want.done.wait(300) and want.error is None
    dst = BatchedEngine("preset:debug-glm", **dict(ENGINE, slots=2, kv_blocks=64))
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
        assert set(payload["kv"]["pools"]) == {"k_mla", "k_idx"}
        meta = dst.import_session(payload)
        handle = meta.pop("_request")
        assert handle.done.wait(300) and handle.error is None, handle.error
        assert handle.tokens == want.tokens
    finally:
        engine._decode = orig
        dst.close()


def test_preempted_sessions_resume_with_both_pools():
    """``kv_overcommit`` on a pool too small for its three sessions: blocks
    grow on demand, the youngest sessions are preempted (exported, rows of
    both pools) and resumed; every request ends with the tokens an engine with
    room gives."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    roomy = BatchedEngine("preset:debug-glm", **ENGINE)
    tight = BatchedEngine("preset:debug-glm", **dict(ENGINE, kv_blocks=36, kv_overcommit="on"))
    try:
        prompts = [list(range(20 + 7 * i, 60 + 9 * i)) for i in range(3)]
        want = [roomy.submit(p, max_new_tokens=70) for p in prompts]
        got = [tight.submit(p, max_new_tokens=70) for p in prompts]
        for a, b in zip(want, got):
            assert a.done.wait(600) and b.done.wait(600) and a.error is None and b.error is None
            assert a.tokens == b.tokens
        assert tight.preempt_stats.get("exported", 0) >= 1, tight.preempt_stats
        assert tight.preempt_stats.get("resumed", 0) == tight.preempt_stats.get("exported", 0)
        assert tight.free_kv_blocks == tight.total_kv_blocks == 36
    finally:
        roomy.close()
        tight.close()


def test_the_engine_line_and_metrics_name_the_selection(engine, capfd):
    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    assert engine.engine_line["index_topk"] == TOPK
    assert engine.engine_line["index_pool_bytes"] == engine.index_pool_bytes() > 0
    assert engine.engine_line["prefill_view_step"] == TOPK  # lanes a step: 4 blocks of 8
    reg = Registry()
    export_moe_stats(reg, engine)
    text = reg.expose()
    for name in ("steps", "rows", "context", "selected"):
        assert f'dtx_serving_dsa_{name}{{phase="decode"}}' in text
        assert f'dtx_serving_dsa_{name}{{phase="prefill"}}' in text
    assert f"dtx_serving_index_pool_bytes {float(engine.index_pool_bytes())}" in text or \
        f"dtx_serving_index_pool_bytes {engine.index_pool_bytes()}" in text
    assert 'dtx_serving_moe_rows_here{phase="decode"}' in text
    # the decode span of the scheduler's pass carries the running sums (a traced run reads them there)
    spans, real_phase = [], engine._phase
    engine._phase = lambda name, **detail: (spans.append((name, detail)), real_phase(name, **detail))[1]
    try:
        req = engine.submit(list(range(40, 90)), max_new_tokens=9)
        assert req.done.wait(600) and req.error is None
    finally:
        engine._phase = real_phase
    # a chunk's span says how many lanes its program views and how many its slot's table has,
    # by the program's own rule; the engine adds them up and /metrics states the sums
    before, chunks = dict(engine.dsa_stats), []
    engine._phase = lambda name, **detail: (chunks.append((name, detail)), real_phase(name, **detail))[1]
    try:
        long = engine.submit(list(range(30, 160)), max_new_tokens=2)  # 130 tokens: chunks of 64 at 0, 64, 128
        assert long.done.wait(600) and long.error is None
    finally:
        engine._phase = real_phase
    chunks = [d for name, d in chunks if name == "dtx_engine_prefill_chunk"]
    assert [(d["tokens"], d["view"], d["table"]) for d in chunks] == [(64, 64, 256), (64, 128, 256), (64, 192, 256)]
    assert all(d["view"] == mla.view_lanes(64 * i, 64, TOPK, 8, 32) for i, d in enumerate(chunks))
    assert [d for name, d in spans if name == "dtx_engine_prefill_chunk"] == [
        {"tokens": 64, "slot": chunks[0]["slot"], "view": 64, "table": 256}]
    assert engine.dsa_stats["prefill_view_lanes"] - before["prefill_view_lanes"] == 64 + 128 + 192
    assert engine.dsa_stats["prefill_table_lanes"] - before["prefill_table_lanes"] == 3 * 256
    reg = Registry()
    export_moe_stats(reg, engine)
    text = reg.expose()
    for name in ("view", "table"):
        assert f"# TYPE dtx_serving_dsa_prefill_{name}_lanes_total counter" in text
        assert f"dtx_serving_dsa_prefill_{name}_lanes_total {engine.dsa_stats[f'prefill_{name}_lanes']}\n" in text
    marks = [d for name, d in spans if name == "dtx_engine_decode"]
    assert len(marks) >= 2 and all(d.keys() == {"live", "dsa_context", "dsa_selected"} for d in marks)
    assert marks[-1]["dsa_context"] > marks[0]["dsa_context"]
    assert marks[-1]["dsa_selected"] - marks[0]["dsa_selected"] <= TOPK * 9
    # a model that does not select states neither
    other = BatchedEngine("preset:debug-ling", **ENGINE)
    try:
        assert other.engine_line["index_topk"] == 0 and other.engine_line["index_pool_bytes"] == 0
        assert other.engine_line["prefill_view_step"] == 0 and other._dsa_chunk_marks(0, 64) == {}
        assert other._dsa_marks() == {} and engine._dsa_marks().keys() == {"dsa_context", "dsa_selected"}
        reg = Registry()
        export_moe_stats(reg, other)
        assert "dtx_serving_dsa_steps{" not in reg.expose()
        assert "\ndtx_serving_dsa_prefill_view_lanes_total " not in reg.expose()
    finally:
        other.close()
    assert '"index_topk": 0' in capfd.readouterr().err


@pytest.mark.parametrize("entry", ["prefix_cache", "spec_draft", "kv_quant", "trainer", "memory",
                                   "hf_import", "dense engine"])
def test_every_option_works_with_two_pools_or_refuses_by_name(model, entry):
    """What moves rows by ``kv_leaf_keys`` works (the tests above: release and
    reuse, copy-on-write, trim, migration, preemption); the dense engine here;
    the rest refuses the model by name."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    cfg, _, params, _ = model
    if entry == "dense engine":
        paged = BatchedEngine("preset:debug-glm", **dict(ENGINE, slots=1))
        dense = BatchedEngine("preset:debug-glm", slots=1, decode_chunk=4, max_seq_len=256)
        try:
            assert dense.decode_path == "dense"
            prompt = list(range(30, 100))
            a = paged.submit(prompt, max_new_tokens=10)
            b = dense.submit(prompt, max_new_tokens=10)
            assert a.done.wait(600) and b.done.wait(600) and a.error is None and b.error is None
            assert a.tokens == b.tokens
            # and both what the float32 reference puts first, as the benchmark holds a served token
            assert len(a.tokens) == 10 and _gaps(paged, prompt, a).max() < 0.05
        finally:
            paged.close()
            dense.close()
        return
    with pytest.raises(NotImplementedError, match="debug-glm"):
        if entry == "prefix_cache":
            BatchedEngine("preset:debug-glm", prefix_cache=4, **ENGINE)
        elif entry == "spec_draft":
            BatchedEngine("preset:debug-glm", spec_draft="take:2", **ENGINE)
        elif entry == "kv_quant":
            init_cache(cfg, 1, 64, quantize="int8")
        elif entry == "trainer":
            from datatunerx_tpu.training.train_lib import TrainConfig, Trainer

            Trainer(cfg, TrainConfig())
        elif entry == "memory":
            from datatunerx_tpu.parallel.memory import estimate_footprint
            from datatunerx_tpu.training.train_lib import TrainConfig

            estimate_footprint(cfg, TrainConfig(), batch=1, seq=64)
        elif entry == "hf_import":
            from datatunerx_tpu.utils.hf_convert import convert_hf_state_dict

            convert_hf_state_dict({}, cfg)
