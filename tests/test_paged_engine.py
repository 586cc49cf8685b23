"""Paged KV cache + chunked prefill (vLLM PagedAttention / Sarathi-style
scheduling, PAPERS.md): the correctness bar is that paging is INVISIBLE in
the tokens — paged and dense engines must produce token-exact outputs for
greedy and fixed-seed sampled decode, across base and LoRA-adapter requests
and through every prefix-cache path — while the allocator's free list and
the scheduler's prefill-token budget deliver the HBM and latency wins."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.llama import forward, init_cache
from datatunerx_tpu.ops.paged_attention import (
    BlockAllocator,
    BlockAllocatorError,
    POS_SENTINEL,
    init_paged_cache,
    paged_clear_rows,
    paged_install_table,
)
from datatunerx_tpu.serving.batched_engine import BatchedEngine

MODEL = "preset:debug"


@pytest.fixture(scope="module")
def dense():
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def paged():
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def kernel_eng():
    """Pallas in-place decode kernel forced on (interpret mode under
    JAX_PLATFORMS=cpu) — every other knob identical to ``paged``, which is
    its gather-path oracle."""
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        paged_kernel="on")
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def budgeted():
    """Paged + chunked prefill with an interleave budget — shared by the
    parity and scheduler-bound tests (engine compiles are the expensive
    part of this suite; a single request's output is budget-invariant)."""
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        prefill_chunk=64, prefill_token_budget=64)
    yield eng
    eng.close()


# ------------------------------------------------------------- allocator

def test_block_allocator_exhaustion_free_reuse():
    a = BlockAllocator(4)
    b1 = a.alloc(3)
    assert b1 == [0, 1, 2] and a.free_count == 1
    # refusal is atomic: a failed alloc takes nothing
    assert a.alloc(2) is None and a.free_count == 1
    b2 = a.alloc(1)
    assert b2 == [3] and a.free_count == 0
    assert a.alloc(1) is None  # exhausted
    a.free(b1)
    assert a.free_count == 3
    assert a.alloc(2) == [0, 1]  # freed blocks are reused lowest-first
    assert a.alloc(0) == []
    with pytest.raises(ValueError):
        BlockAllocator(0)


def test_block_allocator_free_rejects_corruption():
    """free() hardening: out-of-range ids, double-frees, and in-call
    duplicates raise the typed error BEFORE mutating — the silent
    alternative re-issues a live block to a second slot."""
    a = BlockAllocator(4)
    held = a.alloc(2)  # [0, 1]
    with pytest.raises(BlockAllocatorError):
        a.free([4])  # out of range (pool has ids 0..3)
    with pytest.raises(BlockAllocatorError):
        a.free([-1])
    with pytest.raises(BlockAllocatorError):
        a.free([2])  # never allocated — already on the free list
    with pytest.raises(BlockAllocatorError):
        a.free([0, 0])  # duplicate ids in one call
    a.free(held)  # the legitimate free still works...
    assert a.free_count == 4
    with pytest.raises(BlockAllocatorError):
        a.free(held)  # ...and replaying it is a double-free
    assert a.free_count == 4  # rejected frees changed nothing
    assert isinstance(BlockAllocatorError("x"), ValueError)


@pytest.mark.parametrize("blocks", [[5], [7, 2, 9], [0, 1, 2, 3]])
def test_install_table_is_the_three_eager_updates_in_one_program(blocks):
    """Admission hands a slot its blocks through ONE jitted program whatever
    their number (an eager scatter compiled a handful of small programs per
    block count): the table row, the scrub of the blocks' recycled positions
    and the rewound cursor are what the three eager updates gave; a block the
    row does not name keeps its positions, as does the pool's last block,
    which an unused column's -1 must not reach."""
    from datatunerx_tpu.models import get_config

    cache = init_paged_cache(get_config("debug"), 3, 12, 4, 4)
    cache["pos"] = jnp.arange(48, dtype=jnp.int32).reshape(12, 4)
    cache["len"] = jnp.asarray([9, 8, 7], jnp.int32)
    row = np.full((4,), -1, np.int32)
    row[: len(blocks)] = blocks
    want_pos = cache["pos"].at[jnp.asarray(blocks)].set(POS_SENTINEL)
    want_tables = cache["block_tables"].at[1].set(jnp.asarray(row))
    out = jax.jit(paged_install_table)(cache, jnp.asarray(1, jnp.int32), jnp.asarray(row))
    np.testing.assert_array_equal(out["pos"], want_pos)
    np.testing.assert_array_equal(out["block_tables"], want_tables)
    np.testing.assert_array_equal(out["len"], [9, 0, 7])
    assert int(out["pos"][11, 0]) == 44 and set(out) == set(cache)


_CLEAR = jax.jit(paged_clear_rows)


@pytest.mark.parametrize("slots", [[1], [2, 0, 3], [0, 1, 2, 3]],
                         ids=["1", "3", "all"])
def test_clear_rows_is_the_eager_clears_in_one_program(slots):
    """A pass gives its slots' rows up through ONE jitted program whatever
    their number: what an eager ``.at[slot].set(-1)`` a slot gave (a handful
    of small programs each); a padded index past the table writes nothing,
    and the three list lengths are one compilation."""
    tables = jnp.arange(4 * 8, dtype=jnp.int32).reshape(4, 8)
    want = tables
    for slot in slots:
        want = want.at[slot].set(-1)
    rows = np.full((4,), 4, np.int32)
    rows[: len(slots)] = slots
    np.testing.assert_array_equal(_CLEAR(tables, rows), want)
    np.testing.assert_array_equal(_CLEAR(tables, np.full((4,), 4, np.int32)), tables)
    assert _CLEAR._cache_size() == 1


# ------------------------------------------------------- model primitive

def _debug_setup():
    from datatunerx_tpu.models import get_config, init_params

    cfg = get_config("debug")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size, jnp.int32)
    return cfg, params, toks


def test_paged_forward_matches_dense_exactly():
    """The gathered block view is element-identical to the dense row, so
    prefill AND a decode step must match bit-for-bit — including when a slot
    holds fewer blocks than full capacity (ragged table)."""
    cfg, params, toks = _debug_setup()
    B, P = toks.shape

    dense_c = init_cache(cfg, B, 16, dtype=jnp.float32, per_slot=True)
    ld, dense_c = forward(params, toks, cfg, cache=dense_c)

    paged_c = init_paged_cache(cfg, B, 8, 4, 4, dtype=jnp.float32)
    paged_c["block_tables"] = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]],
                                          jnp.int32)
    lp, paged_c = forward(params, toks, cfg, cache=paged_c)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))

    nxt = jnp.argmax(ld[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.full((B, 1), P, jnp.int32)
    l2d, _ = forward(params, nxt, cfg, positions=pos, cache=dense_c)
    l2p, _ = forward(params, nxt, cfg, positions=pos, cache=paged_c)
    np.testing.assert_array_equal(np.asarray(l2d), np.asarray(l2p))

    # ragged: slot 1 holds only the 2 blocks its short request needs
    ragged = init_paged_cache(cfg, B, 8, 4, 4, dtype=jnp.float32)
    ragged["block_tables"] = jnp.asarray([[0, 1, 2, 3], [4, 5, -1, -1]],
                                         jnp.int32)
    lr, _ = forward(params, toks, cfg, cache=ragged)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lr))


def test_paged_int8_cache_matches_dense_int8():
    cfg, params, toks = _debug_setup()
    qd = init_cache(cfg, 2, 16, dtype=jnp.float32, per_slot=True,
                    quantize="int8")
    ld, _ = forward(params, toks, cfg, cache=qd)
    qp = init_paged_cache(cfg, 2, 8, 4, 4, quantize="int8")
    qp["block_tables"] = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    lp, qp = forward(params, toks, cfg, cache=qp)
    assert qp["k"].dtype == jnp.int8
    assert qp["k_scale"].shape == qp["k"].shape[:-1] + (cfg.num_kv_heads,)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


# ------------------------------------------------------- engine parity

def test_paged_greedy_matches_dense(dense, paged):
    prompt = dense.tokenizer.encode("the quick brown fox jumps over")
    want = dense.generate(prompt, max_new_tokens=12)
    got = paged.generate(prompt, max_new_tokens=12)
    assert got == want, (got, want)
    # elastic accounting: every block returned after completion
    assert paged.free_kv_blocks == paged.total_kv_blocks


def test_paged_sampled_matches_dense(dense, paged):
    """Fixed-PRNG sampling: same seed → same rng stream per slot → identical
    tokens, because the paged logits are bit-identical to dense."""
    prompt = dense.tokenizer.encode("sampling determinism probe")
    for seed in (0, 7):
        want = dense.generate(prompt, max_new_tokens=10, temperature=0.8,
                              top_p=0.9, seed=seed)
        got = paged.generate(prompt, max_new_tokens=10, temperature=0.8,
                             top_p=0.9, seed=seed)
        assert got == want, (seed, got, want)


def test_paged_long_prompt_chunked_prefill_matches_dense(dense, budgeted):
    """A prompt long enough to take several prefill chunks must still decode
    token-exactly — chunked prefill is algebraically the same computation."""
    prompt = dense.tokenizer.encode("long context " * 70)
    want = dense.generate(prompt, max_new_tokens=8)
    got = budgeted.generate(prompt, max_new_tokens=8)
    assert got == want, (got, want)
    chunks = [e for e in budgeted.sched_trace if e[0] == "prefill"]
    assert len(chunks) >= 2, "prompt did not prefill in chunks"


# ------------------------------------------- pallas kernel decode parity
#
# The gather engine (``paged``) is the ORACLE: same pool, same tables, same
# scheduler — only the attention read differs. The bar is token-exactness,
# greedy AND fixed-seed sampled, across bf16/int8 pools, pooled adapters,
# ragged in-flight lens, and the chunked-prefill → kernel-decode handoff.

def test_kernel_decode_matches_gather_and_dense(dense, paged, kernel_eng):
    assert kernel_eng.decode_path == "pallas"
    assert kernel_eng.decode_paths == {"global": "pallas"}
    assert paged.decode_path == "gather" and dense.decode_path == "dense"
    prompt = dense.tokenizer.encode("the quick brown fox jumps over")
    want = dense.generate(prompt, max_new_tokens=12)
    assert paged.generate(prompt, max_new_tokens=12) == want
    assert kernel_eng.generate(prompt, max_new_tokens=12) == want
    # elastic accounting unchanged by the kernel: every block returned
    assert kernel_eng.free_kv_blocks == kernel_eng.total_kv_blocks


def test_kernel_sampled_matches_gather(paged, kernel_eng):
    prompt = paged.tokenizer.encode("sampling determinism probe")
    for seed in (0, 7):
        want = paged.generate(prompt, max_new_tokens=10, temperature=0.8,
                              top_p=0.9, seed=seed)
        got = kernel_eng.generate(prompt, max_new_tokens=10, temperature=0.8,
                                  top_p=0.9, seed=seed)
        assert got == want, (seed, got, want)


def test_kernel_ragged_inflight_matches_gather(paged, kernel_eng):
    """Slots at DIFFERENT depths decoding concurrently (slots=2 forces
    overlap): the kernel walks each slot's own table/len, so ragged batches
    must match the gather engine token for token."""
    tok = paged.tokenizer
    prompts = [tok.encode("short one"),
               tok.encode("a much longer prompt with plenty of context " * 3)]
    want = [paged.generate(p, max_new_tokens=8 + 4 * i)
            for i, p in enumerate(prompts)]
    reqs = [kernel_eng.submit(p, max_new_tokens=8 + 4 * i)
            for i, p in enumerate(prompts)]
    for r, w in zip(reqs, want):
        assert r.done.wait(300) and r.error is None, r.error
        assert r.tokens == w, (r.tokens, w)


def test_kernel_chunked_prefill_handoff(dense, kernel_eng):
    """Chunked prefill stays on the gather path (T > 1) and hands its slot
    to KERNEL decode — the seam between the two paths must be invisible."""
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        prefill_chunk=64, prefill_token_budget=64,
                        paged_kernel="on")
    try:
        prompt = dense.tokenizer.encode("long context " * 70)
        want = dense.generate(prompt, max_new_tokens=8)
        got = eng.generate(prompt, max_new_tokens=8)
        assert got == want, (got, want)
        chunks = [e for e in eng.sched_trace if e[0] == "prefill"]
        assert len(chunks) >= 2, "prompt did not prefill in chunks"
    finally:
        eng.close()


def test_kernel_int8_kv_parity():
    """int8 kv_quant pools: the kernel dequantizes by the paged scale pools
    in place and must match the gather path's dequantized read exactly."""
    gather = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                           slots=2, decode_chunk=4, kv_block_size=16,
                           kv_quant="int8", paged_kernel="off")
    kern = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                         slots=2, decode_chunk=4, kv_block_size=16,
                         kv_quant="int8", paged_kernel="on")
    try:
        prompt = gather.tokenizer.encode("quantized cache kernel probe")
        for kw in ({}, {"temperature": 0.7, "top_p": 0.9, "seed": 11}):
            want = gather.generate(prompt, max_new_tokens=8, **kw)
            got = kern.generate(prompt, max_new_tokens=8, **kw)
            assert got == want, (kw, got, want)
    finally:
        gather.close()
        kern.close()


def test_kernel_pooled_adapter_parity(tmp_path):
    """Mixed-rank pooled adapters through kernel decode: LoRA deltas ride
    the projections (not attention), but the adapter-indexed q/k/v feeding
    the kernel must still produce gather-identical tokens — greedy and
    fixed-seed sampled, base + both tenants."""
    cks = _mixed_rank_checkpoints(tmp_path)
    gather = BatchedEngine(MODEL, adapters=cks, adapter_pool=2,
                           adapter_rank_max=8, template="vanilla",
                           max_seq_len=256, slots=2, decode_chunk=4,
                           kv_block_size=16, paged_kernel="off")
    kern = BatchedEngine(MODEL, adapters=cks, adapter_pool=2,
                         adapter_rank_max=8, template="vanilla",
                         max_seq_len=256, slots=2, decode_chunk=4,
                         kv_block_size=16, paged_kernel="on")
    try:
        prompt = gather.tokenizer.encode("tenant isolation kernel probe")
        want = {}
        for adapter in ("", "a", "b"):
            want[adapter] = gather.generate(prompt, max_new_tokens=8,
                                            adapter=adapter)
            got = kern.generate(prompt, max_new_tokens=8, adapter=adapter)
            assert got == want[adapter], (adapter, got, want[adapter])
        assert want["a"] != want[""] and want["b"] != want[""]  # non-vacuous
        for adapter in ("a", "b"):
            w = gather.generate(prompt, max_new_tokens=8, adapter=adapter,
                                temperature=0.8, top_p=0.9, seed=7)
            g = kern.generate(prompt, max_new_tokens=8, adapter=adapter,
                              temperature=0.8, top_p=0.9, seed=7)
            assert g == w, (adapter, g, w)
    finally:
        gather.close()
        kern.close()


# the paths that move a slot's cursor or its table without a plain decode
# step, kernel on against gather off, everything else equal: (engine keywords,
# scenario). A scenario drives one engine and returns its token streams,
# greedy and fixed-seed sampled; it asserts that the path it is named for ran.
_SAMPLED = {"temperature": 0.8, "top_p": 0.9, "seed": 7}


def _off_bucket_prompts(eng):
    """Prompt lengths off the 64-token bucket (left padding inside the
    chunk: the query's lane runs ahead of its rope position), on it, and
    past it by less than a block, alone and in flight together."""
    prompts = [list(range(3, 3 + n)) for n in (5, 64, 70)]
    out = [eng.generate(p, max_new_tokens=10, **kw)
           for p in prompts for kw in ({}, _SAMPLED)]
    reqs = [eng.submit(p, max_new_tokens=6 + 5 * i)
            for i, p in enumerate(prompts[:2])]
    for r in reqs:
        assert r.done.wait(300) and r.error is None, r.error
    return out + [r.tokens for r in reqs]


def _cow_prefix_hit(eng):
    """Slots whose tables map the SAME blocks for a shared prefix: an exact
    hit, then a strict-prefix hit that extends it."""
    tok = eng.tokenizer
    p1 = tok.encode("shared system prompt for every request here")
    p2 = tok.encode("shared system prompt for every request here plus")
    out = [eng.generate(p1, max_new_tokens=10) for _ in range(2)]
    out.append(eng.generate(p2, max_new_tokens=10))
    out.append(eng.generate(p1, max_new_tokens=10, **_SAMPLED))
    modes = {e[3] for e in eng.sched_trace if e[0] == "admit"}
    assert "cow" in modes and "cow_extend" in modes, modes
    return out


def _preempt_and_resume(eng):
    """Four sessions growing toward 9 blocks each on a 20-block pool: tables
    grow a block at a time, and a preempted session's blocks and cursor are
    exported and written back into other blocks."""
    prompts = [eng.tokenizer.encode(f"request number {i} probing growth")
               for i in range(4)]
    reqs = [eng.submit(p, max_new_tokens=80, **kw)
            for p, kw in zip(prompts, ({}, _SAMPLED, {}, _SAMPLED))]
    for i, r in enumerate(reqs):
        assert r.done.wait(300), f"request {i} stalled"
        assert r.error is None, (i, r.error)
    assert eng.preempt_stats.get("exported", 0) >= 1, eng.preempt_stats
    assert eng.free_kv_blocks == eng.total_kv_blocks
    return [r.tokens for r in reqs]


def _spec_rejections(eng):
    """A weak draft (one layer of two): most proposals are rejected and the
    cursor rolls back over lanes that stay written."""
    tok = eng.tokenizer
    out = [eng.generate(tok.encode(text), max_new_tokens=16, **kw)
           for text in ("hello world this is serving", "short")
           for kw in ({}, _SAMPLED)]
    info = eng.spec_info()
    assert info["accepted"] < info["proposed"], info
    return out


def _migrated_resume(eng):
    """A session exported mid-decode and imported again: its rows land in
    fresh blocks and decode goes on from the cursor it carried."""
    from test_session_handoff import _export_mid_decode, _import_and_wait

    prompt = eng.tokenizer.encode("the quick brown fox jumps over")
    out = []
    for kw in ({}, _SAMPLED):
        payload = _export_mid_decode(eng, prompt, max_new_tokens=24, **kw)
        handle, _ = _import_and_wait(eng, payload)
        out.append(handle.tokens)
    assert eng.session_stats["import"].get("ok", 0) >= 2
    return out


# a model with a sliding window: its token step takes the decode kernel too,
# which drops a window the cache (256 lanes here, two trips of 128) cannot
# exceed and starts its walk at the first trip of a narrower one
def _windowed_traffic(eng):
    """Prompts shorter than a window of 32, longer than it, and longer than a
    trip of the kernel's walk, off the 64-token bucket, alone and in flight
    together."""
    prompts = [list(range(3, 3 + n)) for n in (5, 70, 150)]
    out = [eng.generate(p, max_new_tokens=12, **kw)
           for p in prompts for kw in ({}, _SAMPLED)]
    reqs = [eng.submit(p, max_new_tokens=8 + 6 * i)
            for i, p in enumerate(prompts[1:])]
    for r in reqs:
        assert r.done.wait(300) and r.error is None, r.error
    return out + [r.tokens for r in reqs]


def _windowed_prefix_extension(eng):
    """A strict-prefix hit extends a row of 128 lanes by a suffix of 4 tokens
    behind 60 pads: a window of 32 then reaches over the pads into the
    prefix, a trip before the one ``cursor + 1 - window`` lies in."""
    p1 = list(range(3, 123))
    p2 = p1 + [7, 8, 9, 10]
    out = [eng.generate(p1, max_new_tokens=6),
           eng.generate(p2, max_new_tokens=24),
           eng.generate(p2, max_new_tokens=24, **_SAMPLED)]
    modes = {e[3] for e in eng.sched_trace if e[0] == "admit"}
    assert "cow_extend" in modes, modes
    return out


_CURSOR_PATHS = {
    "off_bucket_prompt": ({}, _off_bucket_prompts),
    "cow_prefix_hit": (dict(kv_overcommit="on", prefix_cache=4),
                       _cow_prefix_hit),
    "preempt_and_resume": (dict(slots=4, kv_blocks=20, kv_overcommit="on"),
                           _preempt_and_resume),
    "spec_rejections": (dict(slots=3, spec_draft="take:1", spec_k=3,
                             spec_mode="on"), _spec_rejections),
    "migrated_resume": ({}, _migrated_resume),
    "window_32": (dict(window=32), _windowed_traffic),
    "window_32_prefix_extension": (
        dict(window=32, kv_overcommit="on", prefix_cache=4),
        _windowed_prefix_extension),
    "window_512_dropped": (dict(window=512), _windowed_traffic),
}


@pytest.mark.parametrize("path", sorted(_CURSOR_PATHS))
def test_kernel_matches_gather_where_the_cursor_or_table_moves(
        path, monkeypatch):
    """The decode kernel walks a slot's table as far as its cursor, so every
    path that sets a cursor or a table has to leave them telling the truth.
    (int8 pools: test_kernel_int8_kv_parity; pooled adapters:
    test_kernel_pooled_adapter_parity; chunked prefill's handoff:
    test_kernel_chunked_prefill_handoff.) ``window`` gives the model a
    sliding window."""
    extra, scenario = _CURSOR_PATHS[path]
    kw = dict(dict(template="vanilla", max_seq_len=256, slots=2,
                   decode_chunk=4, kv_block_size=16), **extra)
    model, window = MODEL, kw.pop("window", None)
    if window:
        import dataclasses

        from datatunerx_tpu.models.config import PRESETS

        model = "preset:debug-window"
        monkeypatch.setitem(PRESETS, "debug-window", dataclasses.replace(
            PRESETS["debug"], name="debug-window", sliding_window=window))
    streams = {}
    for mode in ("off", "on"):
        eng = BatchedEngine(model, paged_kernel=mode, **kw)
        try:
            assert eng.decode_path == ("pallas" if mode == "on" else "gather")
            assert eng.decode_window == (32 if mode == "on" and window == 32
                                         else None)
            streams[mode] = scenario(eng)
        finally:
            eng.close()
    assert all(streams["off"]), streams["off"]
    assert streams["on"] == streams["off"]


def test_kernel_flag_validation():
    with pytest.raises(ValueError, match="kv_block_size"):
        BatchedEngine(MODEL, template="vanilla", max_seq_len=256, slots=2,
                      paged_kernel="on")  # dense cache: nothing to kernel
    with pytest.raises(ValueError, match="auto|on|off"):
        BatchedEngine(MODEL, template="vanilla", max_seq_len=256, slots=2,
                      kv_block_size=16, paged_kernel="sometimes")
    # auto on a CPU backend resolves to the gather oracle
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256, slots=2,
                        decode_chunk=4, kv_block_size=16,
                        paged_kernel="auto")
    try:
        assert eng.decode_path == "gather" and not eng.paged_kernel
    finally:
        eng.close()


def test_paged_lora_adapter_parity(tmp_path):
    """Adapter-indexed decode through the paged cache matches dense — the
    multi-tenant path must be as invisible as the base path."""
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint

    ck = make_adapter_checkpoint(str(tmp_path / "ck"), MODEL, seed=3)
    d = BatchedEngine(MODEL, adapters={"a": ck}, template="vanilla",
                      max_seq_len=256, slots=2, decode_chunk=4)
    p = BatchedEngine(MODEL, adapters={"a": ck}, template="vanilla",
                      max_seq_len=256, slots=2, decode_chunk=4,
                      kv_block_size=16)
    try:
        prompt = d.tokenizer.encode("adapter routing check")
        for adapter in ("", "a"):
            want = d.generate(prompt, max_new_tokens=8, adapter=adapter)
            got = p.generate(prompt, max_new_tokens=8, adapter=adapter)
            assert got == want, (adapter, got, want)
        # adapters must actually differ from base, or parity proves nothing
        assert (d.generate(prompt, max_new_tokens=8, adapter="a")
                != d.generate(prompt, max_new_tokens=8))
    finally:
        d.close()
        p.close()


# ------------------------------------------------ dynamic pooled adapters

def _mixed_rank_checkpoints(tmp_path, names=("a", "b")):
    """Adapters at DIFFERENT ranks (2 and 4) so pooled parity also proves
    rank-padding to r_max is numerically invisible."""
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint

    return {n: make_adapter_checkpoint(str(tmp_path / n), MODEL,
                                       seed=3 + i, rank=2 * (i + 1))
            for i, n in enumerate(names)}


def test_pooled_adapter_decode_matches_stacked(tmp_path):
    """The tentpole's correctness bar: the dynamic pool (rank-padded slots,
    load-on-miss at admission) is TOKEN-EXACT vs the static stacked-adapter
    engine — greedy AND fixed-seed sampled — and one heterogeneous-adapter
    batch decodes concurrently through one compiled program."""
    cks = _mixed_rank_checkpoints(tmp_path)
    static = BatchedEngine(MODEL, adapters=cks, template="vanilla",
                           max_seq_len=256, slots=2, decode_chunk=4)
    pooled = BatchedEngine(MODEL, adapters=cks, adapter_pool=2,
                           adapter_rank_max=8, template="vanilla",
                           max_seq_len=256, slots=2, decode_chunk=4,
                           kv_block_size=16)
    try:
        prompt = static.tokenizer.encode("tenant isolation probe")
        want = {}
        for adapter in ("", "a", "b"):
            want[adapter] = static.generate(prompt, max_new_tokens=8,
                                            adapter=adapter)
            got = pooled.generate(prompt, max_new_tokens=8, adapter=adapter)
            assert got == want[adapter], (adapter, got, want[adapter])
        # adapters must differ from base (and each other), or parity is vacuous
        assert want["a"] != want[""] and want["b"] != want[""]
        assert want["a"] != want["b"]
        # fixed-seed sampled decode: same rng stream, bit-identical logits
        for adapter in ("a", "b"):
            w = static.generate(prompt, max_new_tokens=8, adapter=adapter,
                                temperature=0.8, top_p=0.9, seed=7)
            g = pooled.generate(prompt, max_new_tokens=8, adapter=adapter,
                                temperature=0.8, top_p=0.9, seed=7)
            assert g == w, (adapter, g, w)
        # heterogeneous batch: base + both tenants IN FLIGHT TOGETHER
        # (slots=2 forces overlap) through the one decode program
        reqs = {a: pooled.submit(prompt, max_new_tokens=8, adapter=a)
                for a in ("a", "b", "")}
        for a, r in reqs.items():
            assert r.done.wait(300) and r.error is None, (a, r.error)
            assert r.tokens == want[a], (a, r.tokens, want[a])
        occ = pooled.adapter_occupancy()
        assert occ["resident"] == 2 and occ["pinned"] == 0
    finally:
        static.close()
        pooled.close()


def test_pooled_adapter_int8_kv_parity(tmp_path):
    """Pooled adapters over the int8-quantized paged KV cache match the
    static stack over the same quantized cache."""
    cks = _mixed_rank_checkpoints(tmp_path, names=("q",))
    static = BatchedEngine(MODEL, adapters=cks, template="vanilla",
                           max_seq_len=256, slots=2, decode_chunk=4,
                           kv_quant="int8", kv_block_size=16)
    pooled = BatchedEngine(MODEL, adapters=cks, adapter_pool=1,
                           adapter_rank_max=8, template="vanilla",
                           max_seq_len=256, slots=2, decode_chunk=4,
                           kv_quant="int8", kv_block_size=16)
    try:
        prompt = static.tokenizer.encode("quantized tenant probe")
        for adapter in ("", "q"):
            for kw in ({}, {"temperature": 0.7, "top_p": 0.9, "seed": 11}):
                want = static.generate(prompt, max_new_tokens=8,
                                       adapter=adapter, **kw)
                got = pooled.generate(prompt, max_new_tokens=8,
                                      adapter=adapter, **kw)
                assert got == want, (adapter, kw, got, want)
    finally:
        static.close()
        pooled.close()


def test_adapter_load_unload_zero_recompiles(tmp_path):
    """The acceptance criterion: loading/unloading adapters at runtime
    triggers ZERO recompiles — the pool is a program ARGUMENT with fixed
    geometry, so jax's executable cache never sees a new shape. Asserted
    via the jit caches of the engine's memoized programs."""
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint

    cks = _mixed_rank_checkpoints(tmp_path)
    eng = BatchedEngine(MODEL, adapters=cks, adapter_pool=2,
                        adapter_rank_max=8, template="vanilla",
                        max_seq_len=256, slots=2, decode_chunk=4,
                        kv_block_size=16)
    try:
        prompt = eng.tokenizer.encode("compile once, serve any tenant")
        base_out = {a: eng.generate(prompt, max_new_tokens=6, adapter=a)
                    for a in ("a", "b")}
        sizes = lambda: (eng._decode._cache_size(),  # noqa: E731
                         eng._prefill._cache_size(),
                         eng._prefill_chunk_fn._cache_size())
        before = sizes()
        # runtime load of a NEW adapter (evicts an unpinned resident:
        # pool=2 is full) and traffic on it — no new programs. The
        # compile_budget(0) window turns "no recompiles" from a jit-cache
        # size comparison into a hard sanitizer error naming any compile
        # site (checkpoint construction compiles, so it stays outside).
        from datatunerx_tpu.analysis.sanitizers import compile_budget

        ck_c = make_adapter_checkpoint(str(tmp_path / "c"), MODEL, seed=9,
                                       rank=8)
        with compile_budget(0, label="adapter load/unload"):
            eng.load_adapter("c", ck_c)
            assert eng.generate(prompt, max_new_tokens=6, adapter="c")
            eng.unload_adapter("c")
            # the evicted adapter reloads on miss — still no new programs,
            # and its output is unchanged (slot recycling is invisible)
            for a in ("a", "b"):
                assert eng.generate(prompt, max_new_tokens=6,
                                    adapter=a) == base_out[a]
        assert sizes() == before, (before, sizes())
        assert eng.adapter_occupancy()["evictions"] >= 1
    finally:
        eng.close()


# ------------------------------------------------------- prefix cache

def test_paged_prefix_cache_reuse_and_extend_parity(dense):
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        prefix_cache=4)
    try:
        tok = eng.tokenizer
        p1 = tok.encode("shared system prompt for every request here")
        want1 = dense.generate(p1, max_new_tokens=10)
        assert eng.generate(p1, max_new_tokens=10) == want1  # miss → store
        assert eng.generate(p1, max_new_tokens=10) == want1  # exact reuse
        p2 = tok.encode("shared system prompt for every request here plus")
        want2 = dense.generate(p2, max_new_tokens=10)
        assert eng.generate(p2, max_new_tokens=10) == want2  # prefix extend
        assert eng.prefill_stats["reuse"] >= 1
        assert eng.prefill_stats["extend"] >= 1
        # reuse/extend insert rows into blocks; all come back on finish
        assert eng.free_kv_blocks == eng.total_kv_blocks
    finally:
        eng.close()


# ------------------------------------------- elastic admission / exhaustion

def test_block_exhaustion_queues_drains_and_short_requests_reserve_few():
    """A pool of exactly one full-length slot's blocks serves 2 slots: the
    allocator (not the slot count) gates admission, requests queue while
    blocks are out, every completion returns its blocks — and the HBM win
    itself: a short chat reserves ceil((plen+max_new)/bs) blocks, not a
    dense row's max_seq_len/bs."""
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        kv_blocks=16)
    try:
        reqs = [eng.submit(eng.tokenizer.encode(f"request number {i}"),
                           max_new_tokens=6) for i in range(4)]
        for r in reqs:
            assert r.done.wait(300), "request stalled under block exhaustion"
            assert r.error is None, r.error
        assert eng.free_kv_blocks == eng.total_kv_blocks == 16

        req = eng.submit(eng.tokenizer.encode("hi"), max_new_tokens=16)
        peak_reserved = 0
        deadline = time.time() + 300
        while not req.done.is_set() and time.time() < deadline:
            peak_reserved = max(
                peak_reserved, eng.total_kv_blocks - eng.free_kv_blocks)
            time.sleep(0.002)
        assert req.done.wait(300) and req.error is None
        # plen=64 + buf=64 → ≤ 8 blocks of 16; a dense row would strand 16
        assert 0 < peak_reserved <= 8, peak_reserved
    finally:
        eng.close()


def test_slots_given_up_together_and_re_issued_blocks_keep_every_token(paged):
    """Twelve clients over four slots and a pool that holds four requests and
    no more: requests of one budget end in one decode chunk and are given up
    by ONE program, their blocks go straight to the next admissions while
    other slots still decode, and every request's tokens are those of the
    same request served alone."""
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=4, decode_chunk=4, kv_block_size=16,
                        kv_blocks=20)
    try:
        tok = eng.tokenizer
        asks = [(tok.encode(f"client {i} asks about block number {i * 7}"),
                 8 if i % 4 < 2 else 12) for i in range(12)]
        # the first pass sees all twelve: four are admitted together
        all_in, tick = threading.Event(), eng._tick
        eng._tick = lambda: all_in.wait(600) and tick()
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in asks]
        all_in.set()
        for r in reqs:
            assert r.done.wait(300) and r.error is None, r.error
    finally:
        eng.close()
    for r, (p, n) in zip(reqs, asks):
        assert r.tokens == paged.generate(p, max_new_tokens=n), (p, n)
    assert eng.free_kv_blocks == eng.total_kv_blocks == 20
    # 12 requests of 5 blocks (64 + 8 or 12 tokens) through 20: blocks were
    # re-issued; and fewer programs than finishes: slots went together
    passes = eng.sched_stats["dtx_engine_release"][1]
    assert list(eng._pool.session_blocks) == [5] * 12
    assert passes < eng.sched_stats["dtx_engine_complete"][1] == 12


# ------------------------------------------------------- scheduler bound

def test_prefill_budget_bounds_decode_delay():
    """With prefill_token_budget set, a long-prompt admission may hold up
    in-flight decode by at most one budget's worth of prefill between decode
    chunks (the accepted stall = one prefill burst + one decode chunk)."""
    # chunk > budget on purpose: the budget is a HARD bound, so the tick
    # must clamp the chunk to the remaining budget rather than let one
    # chunk-sized burst overshoot it
    budget, chunk = 64, 128
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        prefill_chunk=chunk, prefill_token_budget=budget)
    try:
        tok = eng.tokenizer
        short = eng.submit(tok.encode("short request"), max_new_tokens=48)
        # wait until the short request is actively decoding
        deadline = time.time() + 300
        while not short.tokens and time.time() < deadline:
            time.sleep(0.002)
        assert short.tokens, "short request never started decoding"
        long_req = eng.submit(tok.encode("ctx " * 180), max_new_tokens=8)
        assert short.done.wait(300) and long_req.done.wait(300)
        assert short.error is None and long_req.error is None

        trace = list(eng.sched_trace)
        admit_i = next(i for i, e in enumerate(trace)
                       if e[0] == "admit" and e[3] == "chunked"
                       and e[2] > budget)
        activate_i = next(i for i, e in enumerate(trace)
                          if i > admit_i and e[0] == "activate")
        window = trace[admit_i:activate_i]
        # the long prompt really was interleaved: its prefill spans several
        # bursts with decode chunks in between
        assert sum(e[2] for e in window if e[0] == "prefill") > budget
        assert any(e[0] == "decode" for e in window)
        # bound: between consecutive decode chunks (and before the first
        # one), never more than `budget` prefill tokens
        burst = 0
        for e in window:
            if e[0] == "prefill":
                burst += e[2]
                assert burst <= budget, trace
            elif e[0] == "decode":
                burst = 0
    finally:
        eng.close()


# ------------------------------------------------------- gateway signal

def test_replica_stats_surface_free_blocks(paged, dense):
    from datatunerx_tpu.gateway.replica_pool import InProcessReplica

    rp = InProcessReplica("p0", paged)
    st = rp.stats()
    assert st["kv_blocks_total"] == paged.total_kv_blocks > 0
    assert st["kv_blocks_free"] == paged.free_kv_blocks
    assert 0.0 <= rp.busy_fraction() <= 1.0

    rd = InProcessReplica("d0", dense)
    st = rd.stats()
    assert st["kv_blocks_total"] == 0  # dense replicas keep the slot signal
    assert rd.busy_fraction() == 0.0


def test_serving_metrics_expose_block_gauges(paged):
    """The /metrics text the HTTPReplica scrape parses carries the free-block
    gauge for paged engines."""
    from datatunerx_tpu.serving import server as serving_server

    class _Sink:
        def __init__(self):
            self.code, self.body, self.headers = None, b"", {}

        def send_response(self, code):
            self.code = code

        def send_header(self, k, v):
            self.headers[k] = v

        def end_headers(self):
            pass

    sink = _Sink()
    handler = serving_server.Handler.__new__(serving_server.Handler)
    handler.send_response = sink.send_response
    handler.send_header = sink.send_header
    handler.end_headers = sink.end_headers
    handler.wfile = type("W", (), {"write": lambda self, b: sink.__setattr__(
        "body", sink.body + b)})()
    old = serving_server.STATE.engine
    serving_server.STATE.engine = paged
    try:
        handler._metrics()
    finally:
        serving_server.STATE.engine = old
    text = sink.body.decode()
    assert f"dtx_serving_kv_blocks_capacity {paged.total_kv_blocks}" in text
    assert "dtx_serving_kv_blocks_free " in text
