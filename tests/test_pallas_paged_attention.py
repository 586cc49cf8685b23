"""Interpret-mode unit tests for the Pallas in-place paged-decode kernel
(ops/pallas_paged_attention.py): the kernel must reproduce the XLA gather
oracle — gathered linear view + causal bias + xla_attention — through every
cache shape it claims: block-table walk, ragged per-slot lens, -1 sentinel
entries, GQA head mapping, int8 dequant-by-scale, single-block and
full-table slots. Engine-level token parity lives in test_paged_engine.py;
these tests pin the kernel primitive itself."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.ops.attention import (
    kv_dequantize,
    kv_quantize,
    make_causal_bias,
    xla_attention,
)
from datatunerx_tpu.ops.paged_attention import POS_SENTINEL
from datatunerx_tpu.ops.pallas_paged_attention import paged_decode_attention

BS = 8  # block size (tokens per block)
LAYERS, LAYER = 3, 1  # the kernels read one layer of a stacked pool


def _stacked(pool, layer=LAYER):
    """One layer's pool ``[NB, BS, KV(, d)]`` as layer ``layer`` of the
    stacked leaf the kernels take (``[L, NB, BS, KV * d]``; scales keep their
    ``KV`` axis), the other layers holding the same values in another order:
    a kernel that reads a wrong layer offset reads plausible wrong numbers."""
    if pool is None:
        return None
    if pool.ndim == 4:
        pool = pool.reshape(pool.shape[:2] + (-1,))
    decoys = [pool[::-1], jnp.roll(pool, 1, axis=1)]
    layers = [decoys[i % 2] for i in range(LAYERS)]
    layers[layer] = pool
    return jnp.stack(layers)


def _leaves(kp, vp, ks, vs, layer=LAYER):
    out = {"k": _stacked(kp, layer), "v": _stacked(vp, layer)}
    if ks is not None:
        out["k_scale"] = _stacked(ks, layer)
        out["v_scale"] = _stacked(vs, layer)
    return out


def _make_pool(key, B, NB, KV, d, lens, tables, dtype=jnp.float32,
               quant=False):
    """A block pool whose gathered view holds ``lens[b]`` real tokens per
    slot: values written through the tables, positions 0..len-1, sentinel
    elsewhere (exactly what the engine's scrub + writes produce)."""
    kk, kv_, kq = jax.random.split(key, 3)
    k_pool = jnp.zeros((NB, BS, KV, d), jnp.float32)
    v_pool = jnp.zeros((NB, BS, KV, d), jnp.float32)
    pos = jnp.full((NB, BS), POS_SENTINEL, jnp.int32)
    k_rows, v_rows = [], []
    for b in range(B):
        W = tables.shape[1] * BS
        kr = jax.random.normal(jax.random.fold_in(kk, b), (W, KV, d))
        vr = jax.random.normal(jax.random.fold_in(kv_, b), (W, KV, d))
        k_rows.append(kr)
        v_rows.append(vr)
        for i in range(int(lens[b])):
            blk, off = tables[b, i // BS], i % BS
            assert blk >= 0, "test table too short for its len"
            k_pool = k_pool.at[blk, off].set(kr[i])
            v_pool = v_pool.at[blk, off].set(vr[i])
            pos = pos.at[blk, off].set(i)
    if not quant:
        return (k_pool.astype(dtype), v_pool.astype(dtype), None, None, pos,
                k_rows, v_rows)
    kq_pool, ks_pool = kv_quantize(k_pool)
    vq_pool, vs_pool = kv_quantize(v_pool)
    return kq_pool, vq_pool, ks_pool, vs_pool, pos, k_rows, v_rows


def _oracle(q, k_pool, v_pool, ks, vs, tables, pos, q_positions, dtype,
            window=None, scale=None):
    """The gather path, element for element: clamp the table, gather the
    linear view, sentinel-mask the positions, bias (the model's sliding
    ``window`` in it), xla_attention (a kind's score ``scale`` in it; v heads
    as wide as the v pool's are)."""
    B = q.shape[0]
    tbl = jnp.where(tables >= 0, tables, 0)
    k_all = k_pool[tbl].reshape(B, -1, k_pool.shape[-2], k_pool.shape[-1])
    v_all = v_pool[tbl].reshape(B, -1, v_pool.shape[-2], v_pool.shape[-1])
    if ks is not None:
        k_all = kv_dequantize(k_all, ks[tbl].reshape(B, -1, ks.shape[-1]),
                              dtype)
        v_all = kv_dequantize(v_all, vs[tbl].reshape(B, -1, vs.shape[-1]),
                              dtype)
    else:
        k_all, v_all = k_all.astype(dtype), v_all.astype(dtype)
    kv_pos = pos[tbl]  # [B, nbps, BS]
    kv_pos = jnp.where((tables >= 0)[:, :, None], kv_pos, POS_SENTINEL)
    kv_pos = kv_pos.reshape(B, -1)
    bias = make_causal_bias(q_positions[:, None], kv_pos,
                            sliding_window=window)
    return xla_attention(q[:, None].astype(dtype), k_all, v_all, bias,
                         scale=scale)[:, 0]


def _run(B=2, NB=8, nbps=3, KV=2, G=2, d=16, lens=(17, 5), dtype=jnp.float32,
         quant=False, tables=None, seed=0, layer=LAYER):
    H = KV * G
    key = jax.random.PRNGKey(seed)
    if tables is None:
        rows = []
        nxt = 0
        for b in range(B):
            need = -(-int(lens[b]) // BS)
            row = list(range(nxt, nxt + need)) + [-1] * (nbps - need)
            nxt += need
            rows.append(row)
        tables = jnp.asarray(rows, jnp.int32)
    kp, vp, ks, vs, pos, _, _ = _make_pool(key, B, NB, KV, d, lens, tables,
                                           dtype=dtype, quant=quant)
    q = jax.random.normal(jax.random.fold_in(key, 99),
                          (B, H, d)).astype(dtype)
    q_positions = jnp.asarray([int(x) - 1 for x in lens], jnp.int32)
    # the query is the last written token: its lane is the cursor
    got = paged_decode_attention(
        q, *(_stacked(p, layer) for p in (kp, vp, ks, vs)), layer, tables,
        pos, q_positions, jnp.maximum(q_positions, 0))
    want = _oracle(q, kp, vp, ks, vs, tables, pos, q_positions, dtype)
    assert got.dtype == q.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def test_block_table_walk_matches_gather_f32():
    got, want = _run()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
def test_reads_the_layer_it_is_given(layer):
    """First and last layer of the stacked pool, as a traced scalar (the
    layer scan's index): the blocks of layer ``l`` start at row ``l * NB``."""
    got, want = _run(layer=jnp.asarray(layer, jnp.int32), quant=layer > 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_ragged_lens_and_sentinel_entries():
    """Slots at different depths, tables padded with -1: unallocated entries
    contribute nothing, mid-block raggedness masks by pos sentinel."""
    got, want = _run(B=3, NB=10, nbps=4, lens=(25, 9, 1))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_single_block_and_full_table_slots():
    # slot 0: exactly one block; slot 1: every table entry live
    got, want = _run(B=2, NB=8, nbps=3, lens=(BS, 3 * BS))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_gqa_head_mapping():
    """H = KV * G with G > 1: each query-head group must read ITS kv head —
    a mapping bug would still produce plausible numbers, so compare against
    the oracle with distinctly-keyed heads."""
    got, want = _run(KV=4, G=3, d=8, lens=(11, 20), nbps=3, NB=8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_no_gqa_single_group():
    got, want = _run(KV=2, G=1, lens=(13, 6))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_int8_dequant_inside_kernel():
    got, want = _run(quant=True, dtype=jnp.float32, lens=(19, 7))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_bf16_pools_match_oracle_bitwise():
    """bf16 is the serving dtype: the kernel's phase-1 probs quantization
    replicates xla_attention's probs.astype(bf16), so outputs round to the
    SAME bf16 values (the engine token-parity guarantee)."""
    got, want = _run(dtype=jnp.bfloat16, lens=(17, 5))
    np.testing.assert_array_equal(got, want)


def test_aliased_tables_shared_prefix_blocks():
    """COW prefix sharing (kv_overcommit): several slots' tables map the
    SAME physical blocks for their shared prefix, diverging only in their
    owned tails. Kernel reads walk each slot's own table, so aliasing must
    be invisible — pinned against the oracle over genuinely shared blocks
    (the shared region's positions 0..15 coincide across slots, exactly
    what a mapped prefix-cache entry produces)."""
    tables = jnp.asarray([[0, 1, 2, -1],   # donor: prefix + own tail
                          [0, 1, 3, -1],   # sharer at a different depth
                          [0, 1, 4, 5]],   # deeper sharer, two own blocks
                         jnp.int32)
    got, want = _run(B=3, NB=8, nbps=4, lens=(21, 17, 30), tables=tables)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # bf16 serving dtype: aliased reads must stay BITWISE oracle-equal
    got, want = _run(B=3, NB=8, nbps=4, lens=(21, 17, 30), tables=tables,
                     dtype=jnp.bfloat16)
    np.testing.assert_array_equal(got, want)


def test_bf16_int8_pools_match_oracle_bitwise():
    got, want = _run(dtype=jnp.bfloat16, quant=True, lens=(12, 23))
    np.testing.assert_array_equal(got, want)


def test_bf16_nonpow2_head_dim_matches_oracle_bitwise():
    """d=96: 1/sqrt(d) is where python-double vs f32 scale arithmetic
    diverges by an ulp — the kernel must use the oracle's f32 formula."""
    got, want = _run(d=96, dtype=jnp.bfloat16, lens=(17, 5))
    np.testing.assert_array_equal(got, want)


def test_empty_slot_yields_finite_output():
    """A slot with no valid block (all -1): the kernel returns zeros, never
    NaN — the engine's emit mask discards the row either way, but NaNs must
    not leak into the batch."""
    tables = jnp.asarray([[0, 1, -1], [-1, -1, -1]], jnp.int32)
    got, _ = _run(B=2, NB=4, nbps=3, lens=(10, 0), tables=tables)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[1], 0.0)


# ------------------------------------------------- the walk's bound (cursor)

# one compile a (shape, dtype) for the cases below, which differ in values
_jitted_decode = jax.jit(paged_decode_attention,
                         static_argnames=("window", "scale"))


def _cursor_run(cursors, pads=None, nbps=5, KV=2, G=2, d=16,
                dtype=jnp.float32, quant=False, past=None, seed=0,
                window=None, gaps=None, holes=(), poison_before=None,
                dv=None, scale=None):
    """Slots whose lane cursors are ``cursors``: slot ``b`` has lanes
    ``0 .. cursors[b]`` written, the query its last one. Its first ``pads[b]``
    lanes are a prompt's left padding (sentinel position, junk K/V), the rope
    positions count from the lane after them. ``past`` fills every table
    column past the cursor with a VALID block of sentinel positions holding
    ``"finite"`` large values or ``"nan"`` (NaN scales for the int8 pools,
    which hold none): the oracle reads the table cut at the cursor.

    ``window`` is the model's sliding window, given to kernel and oracle
    alike. ``gaps[b] = (lane, n)`` puts ``n`` more pad lanes mid-row, from
    ``lane`` on (what a prefix-cache extension leaves). ``holes`` lists
    ``(slot, column)`` table entries set to -1 after the writes, for both.
    ``poison_before[b]`` columns at the head of slot ``b``'s table hold NaN
    in the kernel's pools (NaN scales for int8) and zeros in the oracle's,
    whose mask drops them: the kernel must not have copied them.

    ``dv`` is the v heads' width where it is not ``d`` and ``scale`` the
    scores' where it is not ``d ** -0.5``: kernel and oracle get both."""
    B, H = len(cursors), KV * G
    dv = dv or d
    pads = pads or (0,) * B
    gaps = gaps or ((0, 0),) * B
    NB = B * nbps
    rng = np.random.default_rng(seed)
    junk = {None: 0.0, "finite": 3e4, "nan": np.nan}[past]
    k_pool = np.full((NB, BS, KV, d), junk, np.float32)
    v_pool = np.full((NB, BS, KV, dv), junk, np.float32)
    pos = np.full((NB, BS), POS_SENTINEL, np.int32)
    tables = np.full((B, nbps), -1, np.int32)
    cut = tables.copy()
    for b, (c, pad) in enumerate(zip(cursors, pads)):
        assert pad <= c < nbps * BS
        gap_at, gap_n = gaps[b]
        held = c // BS + 1
        tables[b, :held if past is None else nbps] = np.arange(
            b * nbps, b * nbps + (held if past is None else nbps))
        cut[b, :held] = tables[b, :held]
        for lane in range(c + 1):
            blk, off = tables[b, lane // BS], lane % BS
            k_pool[blk, off] = rng.standard_normal((KV, d))
            v_pool[blk, off] = rng.standard_normal((KV, dv))
            if lane >= pad and not gap_at <= lane < gap_at + gap_n:
                pos[blk, off] = lane - pad - gap_n * (lane >= gap_at)
        # the unwritten lanes of the cursor's own block are a scrubbed block's
        k_pool[tables[b, c // BS], c % BS + 1:] = 0.0
        v_pool[tables[b, c // BS], c % BS + 1:] = 0.0
    # the oracle's pools hold what the cursors reach and zeros elsewhere
    reached = np.unique(cut[cut >= 0])
    beyond = np.setdiff1d(np.arange(NB), reached)
    keep = lambda a: jnp.zeros_like(a).at[reached].set(a[reached])  # noqa: E731
    kp, vp = jnp.asarray(k_pool).astype(dtype), jnp.asarray(v_pool).astype(dtype)
    clean = pools = [keep(kp), keep(vp), None, None]
    if quant:
        kq, ks = kv_quantize(keep(jnp.asarray(k_pool)))
        vq, vs = kv_quantize(keep(jnp.asarray(v_pool)))
        clean = [kq, vq, ks, vs]
        # an int8 block holds no NaN: its scales do, and dequantize to one
        pools = [kq.at[beyond].set(127), vq.at[beyond].set(127),
                 ks.at[beyond].set(junk), vs.at[beyond].set(junk)]
    elif past is not None:
        pools = [kp, vp, None, None]
    if poison_before:
        bad = np.concatenate([tables[b, :n] for b, n in
                              enumerate(poison_before)])
        clean = [a if a is None or a.dtype == jnp.int8
                 else a.at[bad].set(0) for a in clean]
        pools = [a if a is None or a.dtype == jnp.int8
                 else a.at[bad].set(np.nan) for a in pools]
    for b, col in holes:
        tables[b, col] = cut[b, col] = -1
    q = jnp.asarray(rng.standard_normal((B, H, d))).astype(dtype)
    cursor = jnp.asarray(cursors, jnp.int32)
    q_positions = cursor - jnp.asarray(
        [pad + n for pad, (_, n) in zip(pads, gaps)], jnp.int32)
    tables, cut, pos = jnp.asarray(tables), jnp.asarray(cut), jnp.asarray(pos)
    got = _jitted_decode(
        q, *(_stacked(a) for a in pools), LAYER, tables, pos, q_positions,
        cursor, window=window, scale=scale)
    want = _oracle(q, *clean, cut, pos, q_positions, dtype, window=window,
                   scale=scale)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


# around a block's edge is where a bound off by one drops or adds a column
_CURSORS = (0, 1, BS - 1, BS, BS + 1, 3 * BS + 5, 5 * BS - 1)


@pytest.mark.parametrize("past", [None, "finite", "nan"],
                         ids=["table_ends_at_cursor", "finite_past_cursor",
                              "nan_past_cursor"])
@pytest.mark.parametrize("i", range(len(_CURSORS)),
                         ids=[f"cursor{c}" for c in _CURSORS])
def test_walk_covers_the_cursor_and_nothing_past_it(i, past):
    """Slots of different cursors in one batch equal the oracle over the
    table cut at each cursor: f32 to rounding, bf16 and int8-under-bf16
    bitwise. With valid blocks in the columns past the cursor (what admission
    reserves), their contents change nothing: they are never read."""
    cursors = tuple(_CURSORS[(i + k) % len(_CURSORS)] for k in (0, 1, 3))
    got, want = _cursor_run(cursors, past=past)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for quant in (False, True):
        got, want = _cursor_run(cursors, past=past, dtype=jnp.bfloat16,
                                quant=quant)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pad", [1, BS - 1, BS + 3])
def test_left_padded_rows_are_bounded_by_the_cursor(pad, dtype):
    """A prompt left-padded inside its chunk: ``pad`` sentinel lanes, then
    rope positions 0 .. n. The query's lane is ``pad + n``, past where its
    rope position points, so a bound taken from ``q_positions`` would drop
    the newest columns."""
    n = 2 * BS
    got, want = _cursor_run((pad + n, pad + 3, pad), pads=(pad,) * 3,
                            dtype=dtype, past="nan")
    if dtype == jnp.bfloat16:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ------------------------------ a kind's own v width and score scale

_KIND_CASES = {
    # v heads of 128 under q/k heads of 192, sixteen query heads a KV head
    "v128_under_d192_g16": dict(KV=2, G=16, d=192, dv=128),
    # heads of 64 whose scores are scaled by 1/64, where d ** -0.5 is 1/8
    "scale_64th_d64": dict(KV=2, G=4, d=64, scale=0.015625),
    "v24_over_d16_scaled": dict(KV=2, G=2, d=16, dv=24, scale=0.3),
    "v_equals_d_no_scale": dict(KV=2, G=2, d=16),
}


@pytest.mark.parametrize("pad", [0, 1, BS - 1])
@pytest.mark.parametrize("pools", ["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_KIND_CASES))
def test_a_kinds_v_width_and_scale_match_the_oracle_bitwise(case, pools, pad):
    """Slots of 3 blocks and a bit (5 more pads mid-row, as a prefix-cache
    extension leaves them), 4 tokens and 1 behind ``pad`` left pads, and one
    released with the cursor it kept (a table of -1: zeros), finite junk past
    every cursor. The oracle is ``xla_attention(..., scale=)`` over the
    gathered views, v's of its own width."""
    cursors = (pad + 3 * BS + 2, pad + 3, pad, pad + BS)
    got, want = _cursor_run(
        cursors, pads=(pad,) * 4, dtype=jnp.bfloat16, quant=pools == "int8",
        past="finite", holes=tuple((3, c) for c in range(5)),
        gaps=((pad + BS + 1, 5),) + ((0, 0),) * 3, **_KIND_CASES[case])
    kw = _KIND_CASES[case]
    assert got.shape == (4, kw["KV"] * kw["G"], kw.get("dv", kw["d"]))
    np.testing.assert_array_equal(got[:3], want[:3])
    np.testing.assert_array_equal(got[3], 0.0)


@pytest.mark.parametrize("case", sorted(_KIND_CASES))
def test_a_kinds_slot_with_nothing_written_stays_finite(case):
    """A slot admitted and not yet written (blocks held, every position the
    sentinel, NaN past the cursor's column) beside a live one: its row is
    the oracle's uniform garbage, finite, and the live row is the oracle's."""
    kw = _KIND_CASES[case]
    KV, G, d, dv = kw["KV"], kw["G"], kw["d"], kw.get("dv", kw["d"])
    nbps, NB = 5, 10
    rng = np.random.default_rng(0)
    draw = lambda w: jnp.asarray(  # noqa: E731
        rng.standard_normal((NB, BS, KV, w)), jnp.bfloat16)
    kp, vp = draw(d), draw(dv)
    kp, vp = kp.at[nbps + 1:].set(jnp.nan), vp.at[nbps + 1:].set(jnp.nan)
    tables = jnp.arange(2 * nbps, dtype=jnp.int32).reshape(2, nbps)
    pos = np.full((NB, BS), POS_SENTINEL, np.int32)
    pos[:nbps] = np.arange(nbps * BS).reshape(nbps, BS)
    q = jnp.asarray(rng.standard_normal((2, KV * G, d)), jnp.bfloat16)
    q_positions = jnp.asarray([nbps * BS - 1, 0], jnp.int32)
    got = _jitted_decode(q, _stacked(kp), _stacked(vp), None, None, LAYER,
                         tables, jnp.asarray(pos), q_positions, q_positions,
                         scale=kw.get("scale"))
    want = _oracle(q, kp, vp, None, None, tables, jnp.asarray(pos),
                   q_positions, jnp.bfloat16, scale=kw.get("scale"))
    assert got.shape == (2, KV * G, dv)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_one_width_and_no_scale_lower_to_the_call_without_them(quant):
    """``scale=None`` over pools of one width is the program a call with no
    ``scale`` argument lowers to (the parent's call, text for text:
    scripts/hash_programs.py compares it with the parent commit's), and so
    is the default spelled out; another scale or a v pool of another width
    is another program."""
    B, KV, G, d, nbps, NB = 4, 2, 4, 16, _W_NBPS, 8
    dtype = jnp.int8 if quant else jnp.bfloat16
    pool = jnp.zeros((LAYERS, NB, BS, KV * d), dtype)
    wider = jnp.zeros((LAYERS, NB, BS, KV * (d + 8)), dtype)
    scales = jnp.zeros((LAYERS, NB, BS, KV), jnp.float32) if quant else None
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    q = jnp.zeros((B, KV * G, d), jnp.bfloat16)

    def lowered(v_pool=pool, **kw):
        return jax.jit(lambda q: paged_decode_attention(
            q, pool, v_pool, scales, scales, LAYER, ints(B, nbps),
            ints(NB, BS), ints(B), ints(B), **kw)).lower(q).as_text()

    base = lowered()
    assert lowered(scale=None) == base
    assert lowered(scale=float(np.float32(1.0) / np.sqrt(np.float32(d)))) == base
    assert lowered(scale=0.125) != base
    assert lowered(v_pool=wider) != base
    for window in (72, nbps * BS):
        assert lowered(window=window, scale=None) == lowered(window=window)


# ------------------------------------------- the walk's first trip (window)

# a table of 40 columns is 320 lanes: two whole trips of 128 and half a one
_W_NBPS = 40
_W_LANES = 128
_W_HEADS = {"gqa4": dict(KV=2, G=4), "mha": dict(KV=4, G=1)}


def _window_of(name, n_tokens):
    """None; behind the longest slot's query; that slot's token count, which
    admits every key by one; wider than the cache, which is dropped."""
    return {"none": None, "short": 100, "len": n_tokens,
            "wide": _W_NBPS * BS + 80}[name]


@pytest.mark.parametrize("pad", [0, 1, BS - 1, BS + 3])
@pytest.mark.parametrize("pools", ["bf16", "int8"])
@pytest.mark.parametrize("heads", sorted(_W_HEADS))
@pytest.mark.parametrize("window", ["none", "short", "len", "wide"])
def test_windowed_walk_matches_the_windowed_oracle_bitwise(window, heads,
                                                           pools, pad):
    """Three slots of 250, 141 and 31 tokens behind ``pad`` left pads: under
    the short window the first starts its walk inside the second trip, the
    second inside the first, and the third sees all it has. The oracle is
    ``xla_attention`` over the gathered view under ``sliding_window``."""
    cursors = (pad + 249, pad + 140, pad + 30)
    got, want = _cursor_run(
        cursors, pads=(pad,) * 3, nbps=_W_NBPS, dtype=jnp.bfloat16,
        quant=pools == "int8", past="finite",
        window=_window_of(window, 250), **_W_HEADS[heads])
    np.testing.assert_array_equal(got, want)


_WINDOW_CASES = {
    # first admitted lane 229 of [128, 256)
    "starts_mid_trip": dict(cursors=(300, 60)),
    # first admitted lanes 128 and (under a window of 45) 256: a trip's first
    "starts_at_a_trips_first_lane": dict(cursors=(199, 300), also={1: 45}),
    # column 30 of a window over columns 28 .. 37 holds no block
    "hole_inside_the_window": dict(cursors=(300, 150),
                                   holes=((0, 30), (1, 10))),
    # pads mid-row put the first admitted lane 9 lanes before
    # cursor + 1 - window = 132, inside the trip before
    "pads_mid_row": dict(cursors=(203, 90), pads=(3, 0),
                         gaps=((150, 9), (40, BS))),
    # no column of slot 0's window, 28 .. 37, holds a block
    "window_holds_no_block": dict(cursors=(300, 40),
                                  holes=tuple((0, c) for c in range(28, 38))),
}


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_windowed_walk_at_the_edges(case, dtype):
    """A window of 72 lanes over tables of 320, NaN past every cursor."""
    kw = dict(_WINDOW_CASES[case])
    also = kw.pop("also", {})
    for window, rows in [(72, slice(None))] + [(w, b) for b, w in also.items()]:
        got, want = _cursor_run(
            nbps=_W_NBPS, window=window, past="nan",
            dtype=jnp.float32 if dtype == "f32" else jnp.bfloat16,
            quant=dtype == "int8", **kw)
        if case == "window_holds_no_block":
            # no lane is admitted: zeros, as for a slot with nothing to walk
            # (the oracle's row is uniform garbage)
            np.testing.assert_array_equal(got[0], 0.0)
            rows = 1
        if dtype == "f32":
            np.testing.assert_allclose(got[rows], want[rows], rtol=1e-5,
                                       atol=1e-6)
        else:
            np.testing.assert_array_equal(got[rows], want[rows])


@pytest.mark.parametrize("tables", ["all_unallocated", "held_and_scrubbed"])
def test_windowed_slot_with_nothing_written_yields_zeros(tables):
    """A released slot (a table of -1) and one admitted but not yet written
    (blocks held, every position the sentinel) beside a live one: no lane is
    admitted, no trip is walked, and zeros are stored."""
    B, KV, G, d, nbps, NB = 2, 2, 2, 16, _W_NBPS, 2 * _W_NBPS
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((LAYERS, NB, BS, KV * d)),
                       jnp.bfloat16)
    table = np.full((B, nbps), -1, np.int32)
    table[0] = np.arange(nbps)
    if tables == "held_and_scrubbed":
        table[1] = nbps + np.arange(nbps)
    pos = np.full((NB, BS), POS_SENTINEL, np.int32)
    pos[:nbps] = np.arange(nbps * BS).reshape(nbps, BS)
    got = _jitted_decode(
        jnp.asarray(rng.standard_normal((B, KV * G, d)), jnp.bfloat16),
        pool, pool, None, None, LAYER, jnp.asarray(table), jnp.asarray(pos),
        jnp.asarray([200, 0], jnp.int32), jnp.asarray([200, 0], jnp.int32),
        window=64)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all() and np.abs(got[0]).max() > 0
    np.testing.assert_array_equal(got[1], 0.0)


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("cursor", [2 * _W_LANES - 1, 2 * _W_LANES,
                                    _W_NBPS * BS - 1])
def test_columns_before_the_windows_first_trip_are_never_copied(cursor,
                                                                dtype):
    """NaN in every column of the trips before the one the window starts in
    (NaN scales for the int8 pools): a copy of one of them would put NaN in
    a buffer and ``0 * NaN`` in the sum. The columns of the first trip that
    lie before the window ARE copied and masked."""
    window, pad = 72, 3
    cursors = (cursor, cursor - _W_LANES)
    first_trip = [(c + 1 - window) // _W_LANES for c in cursors]
    assert first_trip[0] >= 1
    got, want = _cursor_run(
        cursors, pads=(pad,) * 2, nbps=_W_NBPS, window=window, past="nan",
        dtype=jnp.float32 if dtype == "f32" else jnp.bfloat16,
        quant=dtype == "int8",
        poison_before=[t * _W_LANES // BS for t in first_trip])
    assert np.isfinite(got).all()
    if dtype == "f32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_a_window_the_cache_cannot_exceed_lowers_to_the_windowless_call(quant):
    """``window=None`` and ``window >= table columns x block size`` are the
    program a call with no ``window`` argument lowers to (the parent's
    call); a narrower one takes one more scalar-prefetch operand."""
    B, KV, G, d, nbps, NB = 4, 2, 4, 16, _W_NBPS, 8
    pool = jnp.zeros((LAYERS, NB, BS, KV * d),
                     jnp.int8 if quant else jnp.bfloat16)
    scale = jnp.zeros((LAYERS, NB, BS, KV), jnp.float32) if quant else None
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    q = jnp.zeros((B, KV * G, d), jnp.bfloat16)

    def lowered(**kw):
        return jax.jit(lambda q: paged_decode_attention(
            q, pool, pool, scale, scale, LAYER, ints(B, nbps), ints(NB, BS),
            ints(B), ints(B), **kw)).lower(q).as_text()

    base = lowered()
    assert lowered(window=None) == base
    assert lowered(window=nbps * BS) == base
    assert lowered(window=nbps * BS + 4096) == base
    narrower = lowered(window=nbps * BS - 1)
    assert narrower != base

    def prefetched(**kw):
        jaxpr = jax.make_jaxpr(lambda q: paged_decode_attention(
            q, pool, pool, scale, scale, LAYER, ints(B, nbps), ints(NB, BS),
            ints(B), ints(B), **kw))(q)
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return call.params["grid_mapping"].num_index_operands

    assert prefetched() == 4 and prefetched(window=nbps * BS - 1) == 5


def test_grid_steps_follow_the_slots_not_the_table():
    """The walk over the table is a loop inside the kernel: one pallas_call
    whose grid has at most two steps a slot, whatever the table's width."""
    B, KV, G, d, nbps, NB = 4, 2, 2, 16, 64, 8
    pool = jnp.zeros((LAYERS, NB, BS, KV * d), jnp.bfloat16)
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(
        lambda q: paged_decode_attention(
            q, pool, pool, None, None, LAYER, ints(B, nbps), ints(NB, BS),
            ints(B), ints(B)))(jnp.zeros((B, KV * G, d), jnp.bfloat16))
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == "dtx_paged_decode"
    assert int(np.prod(calls[0].params["grid_mapping"].grid)) <= 2 * B
    assert calls[0].outvars[0].aval.shape == (B, KV * G, d)


def test_decode_step_wrapper_shape():
    from datatunerx_tpu.ops.pallas_paged_attention import (
        paged_attention_decode_step,
    )

    B, KV, G, d, nbps, NB = 2, 2, 2, 8, 2, 4
    H = KV * G
    key, kq, kq2 = jax.random.split(jax.random.PRNGKey(3), 3)
    tables = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
    kp, vp, ks, vs, pos, _, _ = _make_pool(key, B, NB, KV, d, (9, 4), tables)
    q = jax.random.normal(kq, (B, 1, H, d))
    cache = {"block_tables": tables, "len": jnp.asarray([8, 3], jnp.int32)}
    leaves = _leaves(kp, vp, None, None)
    out = paged_attention_decode_step(
        q, leaves, LAYER, cache, pos, jnp.asarray([[8], [3]], jnp.int32))
    assert out.shape == (B, 1, H, d)
    with pytest.raises(AssertionError):
        paged_attention_decode_step(
            jax.random.normal(kq2, (B, 2, H, d)), leaves, LAYER, cache,
            pos, jnp.asarray([[8, 9], [3, 4]], jnp.int32))


# --------------------------------------- multi-token (bucketed q_len) kernel

from datatunerx_tpu.ops.attention import attention_allow  # noqa: E402
from datatunerx_tpu.ops.pallas_paged_attention import (  # noqa: E402
    paged_multitoken_attention,
)


def _gathered_view(kp, vp, ks, vs, tables, pos, dtype):
    """The gather oracle's linear view: clamped-table gather, dequant,
    sentinel-masked positions — what the model biases over."""
    B = tables.shape[0]
    tbl = jnp.where(tables >= 0, tables, 0)
    k_all = kp[tbl].reshape(B, -1, kp.shape[-2], kp.shape[-1])
    v_all = vp[tbl].reshape(B, -1, vp.shape[-2], vp.shape[-1])
    if ks is not None:
        k_all = kv_dequantize(k_all, ks[tbl].reshape(B, -1, ks.shape[-1]),
                              dtype)
        v_all = kv_dequantize(v_all, vs[tbl].reshape(B, -1, vs.shape[-1]),
                              dtype)
    else:
        k_all, v_all = k_all.astype(dtype), v_all.astype(dtype)
    kv_pos = pos[tbl]
    kv_pos = jnp.where((tables >= 0)[:, :, None], kv_pos, POS_SENTINEL)
    return k_all, v_all, kv_pos.reshape(B, -1)


def _run_mt(B=2, NB=8, nbps=3, KV=2, G=2, d=16, lens=(17, 5), T=3,
            dtype=jnp.float32, quant=False, tables=None, seed=0,
            window=None, layer=LAYER):
    """Multi-token kernel vs the gather oracle. Queries sit on the last T
    written lanes per slot (the post-write verify/chunk shape), so every
    row has a DIFFERENT causal offset on a ragged batch. ``window=WN``
    additionally carves a random branch mask over the last WN lanes — the
    tree-verify operand (requires lens[b] > WN so no row is fully
    masked)."""
    H = KV * G
    key = jax.random.PRNGKey(seed)
    if tables is None:
        rows = []
        nxt = 0
        for b in range(B):
            need = max(1, -(-int(lens[b]) // BS))
            row = list(range(nxt, nxt + need)) + [-1] * (nbps - need)
            nxt += need
            rows.append(row)
        tables = jnp.asarray(rows, jnp.int32)
    kp, vp, ks, vs, pos, _, _ = _make_pool(key, B, NB, KV, d, lens, tables,
                                           dtype=dtype, quant=quant)
    q = jax.random.normal(jax.random.fold_in(key, 7),
                          (B, T, H, d)).astype(dtype)
    q_positions = jnp.asarray(
        [[max(int(lens[b]) - T + t, t) for t in range(T)]
         for b in range(B)], jnp.int32)
    k_all, v_all, kv_pos = _gathered_view(kp, vp, ks, vs, tables, pos, dtype)
    window_mask = window_start = None
    if window is not None:
        assert all(int(x) > window for x in lens)
        window_mask = jax.random.bernoulli(
            jax.random.fold_in(key, 13), 0.6, (B, T, window))
        window_start = jnp.asarray(
            [int(x) - window for x in lens], jnp.int32)
    allow = attention_allow(q_positions, kv_pos, window_mask=window_mask,
                            window_start=window_start)
    got = paged_multitoken_attention(
        q, *(_stacked(p, layer) for p in (kp, vp, ks, vs)), layer, tables,
        allow)
    bias = make_causal_bias(q_positions, kv_pos, window_mask=window_mask,
                            window_start=window_start)
    want = xla_attention(q.astype(dtype), k_all, v_all, bias)
    assert got.dtype == q.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def test_multitoken_matches_gather_f32():
    got, want = _run_mt()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
def test_multitoken_reads_the_layer_it_is_given(layer):
    got, want = _run_mt(layer=jnp.asarray(layer, jnp.int32),
                        quant=layer > 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_multitoken_q_len_one_degenerate():
    """T=1 through the multi-token path must equal the oracle too — the
    bucketed kernel's smallest bucket, not a special case."""
    got, want = _run_mt(T=1, lens=(17, 5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_multitoken_ragged_causal_offsets():
    """Ragged depths: each row's T queries carry row-specific absolute
    positions, so the per-row causal frontier differs across the batch —
    the chunked-prefill shape."""
    got, want = _run_mt(B=3, NB=10, nbps=4, lens=(25, 9, 4), T=4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_multitoken_gqa_int8_dequant_inside_kernel():
    """GQA head mapping and int8 dequant together: 3 query heads share
    each of 4 kv heads, and the kernel dequantizes the int8 pools by
    their scales before the same two-pass arithmetic."""
    got, want = _run_mt(KV=4, G=3, d=8, lens=(11, 20), T=3, quant=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_multitoken_bf16_matches_oracle_bitwise():
    """The serving dtype: same per-block normalize-then-cast rounding as
    the decode kernel, so bf16 outputs are BITWISE oracle-equal — the
    engine token-parity guarantee for chunked prefill + verify columns."""
    got, want = _run_mt(dtype=jnp.bfloat16, lens=(17, 6), T=3)
    np.testing.assert_array_equal(got, want)


def test_multitoken_bf16_int8_matches_oracle_bitwise():
    got, want = _run_mt(dtype=jnp.bfloat16, quant=True, lens=(12, 23), T=5)
    np.testing.assert_array_equal(got, want)


def test_multitoken_tree_branch_window_mask():
    """The tree-verify operand: a random per-(row, column) branch mask over
    the step's own window of lanes. Inside the window the mask AND causal
    both gate (siblings share rope positions); outside, plain causal — the
    kernel must agree with the oracle biased by the SAME allow tensor."""
    got, want = _run_mt(lens=(17, 9), T=3, window=4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    got, want = _run_mt(lens=(17, 9), T=3, window=4, quant=True,
                        dtype=jnp.bfloat16)
    np.testing.assert_array_equal(got, want)


def test_lower_triangular_window_mask_is_chain():
    """A lower-triangular window mask over the queries' own lanes adds
    nothing beyond causality — chain verify semantics reproduce exactly,
    which is why the chain path never builds a mask."""
    B, T, lens = 2, 3, (17, 9)
    key = jax.random.PRNGKey(5)
    tables = jnp.asarray([[0, 1, 2], [3, 4, -1]], jnp.int32)
    kp, vp, ks, vs, pos, _, _ = _make_pool(key, B, 8, 2, 16, lens, tables)
    q_positions = jnp.asarray(
        [[int(x) - T + t for t in range(T)] for x in lens], jnp.int32)
    _, _, kv_pos = _gathered_view(kp, vp, ks, vs, tables, pos, jnp.float32)
    tri = jnp.broadcast_to(
        jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T))
    start = jnp.asarray([int(x) - T for x in lens], jnp.int32)
    with_mask = attention_allow(q_positions, kv_pos, window_mask=tri,
                                window_start=start)
    without = attention_allow(q_positions, kv_pos)
    np.testing.assert_array_equal(np.asarray(with_mask),
                                  np.asarray(without))


def test_multitoken_empty_slot_yields_finite_output():
    tables = jnp.asarray([[0, 1, -1], [-1, -1, -1]], jnp.int32)
    got, _ = _run_mt(B=2, NB=4, nbps=3, lens=(10, 0), T=3, tables=tables)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[1], 0.0)


def test_multitoken_step_wrapper_shape_and_allow_contract():
    from datatunerx_tpu.ops.pallas_paged_attention import (
        paged_attention_multitoken_step,
    )

    B, KV, G, d, nbps, NB, T = 2, 2, 2, 8, 2, 4, 3
    H = KV * G
    key = jax.random.PRNGKey(3)
    tables = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
    kp, vp, ks, vs, pos, _, _ = _make_pool(key, B, NB, KV, d, (9, 4), tables)
    q = jax.random.normal(key, (B, T, H, d))
    allow = jnp.ones((B, T, nbps * BS), bool)
    cache = {"block_tables": tables}
    leaves = _leaves(kp, vp, None, None)
    out = paged_attention_multitoken_step(q, leaves, LAYER, cache, allow)
    assert out.shape == (B, T, H, d)
    with pytest.raises(AssertionError, match="allow"):
        paged_attention_multitoken_step(
            q, leaves, LAYER, cache, jnp.ones((B, T + 1, nbps * BS), bool))
