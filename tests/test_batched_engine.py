"""Continuous-batching engine: greedy parity with the single-request engine,
slot reuse/admission under load, measurable request overlap, streaming deltas,
and unmerged multi-adapter LoRA correctness (VERDICT round-1 item 5)."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.llama import forward, init_cache
from datatunerx_tpu.models.lora import init_lora_params, lora_scaling, merge_lora
from datatunerx_tpu.serving.batched_engine import BatchedEngine
from datatunerx_tpu.serving.engine import InferenceEngine


@pytest.fixture(scope="module")
def single():
    return InferenceEngine("preset:debug", template="vanilla", max_seq_len=256)


@pytest.fixture(scope="module")
def batched():
    eng = BatchedEngine("preset:debug", template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4)
    yield eng
    eng.close()


# ----------------------------------------------------- model primitive

def test_per_slot_cache_matches_scalar_cache():
    """Vector-cursor decode must equal scalar-cursor decode when all rows are
    at the same depth (the aligned case is exactly the old semantics)."""
    from datatunerx_tpu.models import get_config, init_params

    cfg = get_config("debug")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, P = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                              cfg.vocab_size, jnp.int32)

    cache_s = init_cache(cfg, B, P + 4, dtype=jnp.float32)
    logits_s, cache_s = forward(params, toks, cfg, cache=cache_s)
    cache_v = init_cache(cfg, B, P + 4, dtype=jnp.float32, per_slot=True)
    logits_v, cache_v = forward(params, toks, cfg, cache=cache_v)
    np.testing.assert_allclose(np.asarray(logits_s), np.asarray(logits_v),
                               rtol=2e-4, atol=2e-4)

    nxt = jnp.argmax(logits_s[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.full((B, 1), P, jnp.int32)
    l2s, _ = forward(params, nxt, cfg, positions=pos, cache=cache_s)
    l2v, _ = forward(params, nxt, cfg, positions=pos, cache=cache_v)
    np.testing.assert_allclose(np.asarray(l2s), np.asarray(l2v),
                               rtol=2e-4, atol=2e-4)


def test_multi_adapter_matches_per_row_merge():
    """forward(lora_adapter_idx=…) with stacked adapters must equal running
    each row through its own merged model."""
    from datatunerx_tpu.models import get_config, init_params

    cfg = get_config("debug")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rank = 4
    l1 = init_lora_params(cfg, jax.random.PRNGKey(1), rank=rank)
    l2 = init_lora_params(cfg, jax.random.PRNGKey(2), rank=rank)
    # non-zero B so adapters actually change the output
    for lo in (l1, l2):
        for t, ab in lo["layers"].items():
            ab["b"] = jax.random.normal(jax.random.PRNGKey(7), ab["b"].shape) * 0.05
    s1, s2 = lora_scaling(32, rank), lora_scaling(16, rank)

    # stacked tree: [L, E, …] with E=3 (0 = zero adapter)
    stack = {}
    for t in l1["layers"]:
        a = jnp.stack([jnp.zeros_like(l1["layers"][t]["a"]),
                       l1["layers"][t]["a"], l2["layers"][t]["a"]], axis=1)
        b = jnp.stack([jnp.zeros_like(l1["layers"][t]["b"]),
                       l1["layers"][t]["b"], l2["layers"][t]["b"]], axis=1)
        stack[t] = {"a": a, "b": b}
    scales = jnp.asarray([0.0, s1, s2], jnp.float32)

    toks = jax.random.randint(jax.random.PRNGKey(3), (3, 6), 0,
                              cfg.vocab_size, jnp.int32)
    got, _ = forward(params, toks, cfg, lora=({"layers": stack}, scales),
                     lora_adapter_idx=jnp.asarray([0, 1, 2], jnp.int32))

    base, _ = forward(params, toks[:1], cfg)
    m1, _ = forward(merge_lora(params, l1, s1), toks[1:2], cfg)
    m2, _ = forward(merge_lora(params, l2, s2), toks[2:3], cfg)
    want = jnp.concatenate([base, m1, m2], axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


# ------------------------------------------------------------- engine

def test_batched_greedy_matches_single_engine(single, batched):
    prompt = single.tokenizer.encode("the quick brown fox")
    want = single.generate(prompt, max_new_tokens=12)
    got = batched.generate(prompt, max_new_tokens=12)
    assert got == want, (got, want)


def test_more_requests_than_slots_all_complete(batched):
    prompts = [batched.tokenizer.encode(f"prompt number {i}") for i in range(5)]
    reqs = [batched.submit(p, max_new_tokens=6) for p in prompts]
    for r in reqs:
        assert r.done.wait(300), "request did not finish"
        assert r.error is None
        assert len(r.tokens) <= 6


def test_concurrent_requests_overlap(batched):
    """Two in-flight requests must occupy two slots of the same decode
    program at the same time — continuous batching, not serial turn-taking."""
    prompt = batched.tokenizer.encode("overlap test prompt")
    r1 = batched.submit(prompt, max_new_tokens=48)
    r2 = batched.submit(prompt, max_new_tokens=48)
    overlapped = False
    deadline = time.time() + 300
    while time.time() < deadline and not (r1.done.is_set() and r2.done.is_set()):
        if sum(r is not None for r in batched._slot_req) >= 2:
            overlapped = True
            break
        time.sleep(0.005)
    r1.done.wait(300), r2.done.wait(300)
    assert overlapped, "requests never shared the decode program"
    assert r1.error is None and r2.error is None


def test_streaming_deltas_concatenate_to_full_output(batched):
    msgs = [{"role": "user", "content": "hello there"}]
    full = batched.chat(msgs, max_new_tokens=10)
    pieces = []
    n_events = 0
    for delta in batched.chat_stream(msgs, max_new_tokens=10):
        pieces.append(delta)
        n_events += 1
    assert "".join(pieces) == full
    if len(full) > 1:
        assert n_events >= 1


def test_stream_sends_a_reply_that_ends_mid_utf8_sequence(batched):
    """Text ending in U+FFFD is held back while the next token may complete
    the byte sequence; when the reply ENDS there, chat returns it, so the
    stream must too (it used to drop the tail: '' against chat's '��')."""
    import queue
    import types

    tok = batched.tokenizer
    ids = tok.encode("aé")[:-1]  # 'a' + the first byte of a two-byte é
    req = types.SimpleNamespace(stream=queue.Queue(), error=None)
    for t in ids + [None]:
        req.stream.put(t)
    deltas = list(batched._stream_text(req, []))
    assert deltas == ["a", "�"]
    assert "".join(deltas) == tok.decode(ids, skip_special_tokens=True)
    # a migrated stream's held tail is its continuation's to send
    req = types.SimpleNamespace(stream=queue.Queue(), error="session migrated")
    for t in ids + [None]:
        req.stream.put(t)
    with pytest.raises(RuntimeError, match="migrated"):
        list(batched._stream_text(req, []))
    # end to end, on a reply the debug weights end in an incomplete sequence
    msgs = [{"role": "user", "content": "tell me a long story about foxes"}]
    full = batched.chat(msgs, max_new_tokens=40)
    assert "".join(batched.chat_stream(msgs, max_new_tokens=40)) == full


def test_unknown_adapter_rejected(batched):
    with pytest.raises(KeyError, match="unknown adapter"):
        batched.submit([1, 2, 3], adapter="nope")


def test_interleaved_admission_prefix_consistency(batched):
    """A request admitted mid-decode of another must not perturb the other's
    output (slot isolation): run A alone, then A with B injected midway."""
    tok = batched.tokenizer
    pa = tok.encode("isolation check alpha")
    pb = tok.encode("a different prompt entirely for the second slot")
    want_a = batched.generate(pa, max_new_tokens=24)

    ra = batched.submit(pa, max_new_tokens=24)
    time.sleep(0.01)  # land B mid-flight (chunked decode ⇒ admission gap)
    rb = batched.submit(pb, max_new_tokens=8)
    assert ra.done.wait(300) and rb.done.wait(300)
    assert ra.tokens == want_a, (ra.tokens, want_a)


# ----------------------------------------------------- int8 KV cache

def test_int8_kv_cache_close_to_bf16_cache():
    """Quantized-cache decode logits track the full-precision cache within
    int8 tolerance (per-vector scales over head_dim)."""
    from datatunerx_tpu.models import get_config, init_params

    cfg = get_config("debug")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, P = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                              cfg.vocab_size, jnp.int32)

    ref_cache = init_cache(cfg, B, P + 4, dtype=jnp.float32)
    ref_logits, ref_cache = forward(params, toks, cfg, cache=ref_cache)
    q_cache = init_cache(cfg, B, P + 4, dtype=jnp.float32, quantize="int8")
    q_logits, q_cache = forward(params, toks, cfg, cache=q_cache)
    assert q_cache["k"].dtype == jnp.int8
    assert q_cache["k_scale"].shape == q_cache["k"].shape[:-1] + (
        cfg.num_kv_heads,)
    np.testing.assert_allclose(np.asarray(q_logits), np.asarray(ref_logits),
                               rtol=0.1, atol=0.15)

    nxt = jnp.argmax(ref_logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.full((B, 1), P, jnp.int32)
    l_ref, _ = forward(params, nxt, cfg, positions=pos, cache=ref_cache)
    l_q, _ = forward(params, nxt, cfg, positions=pos, cache=q_cache)
    np.testing.assert_allclose(np.asarray(l_q), np.asarray(l_ref),
                               rtol=0.1, atol=0.15)
    # and greedy argmax agrees on this step
    np.testing.assert_array_equal(
        np.argmax(np.asarray(l_q)[:, -1], -1),
        np.argmax(np.asarray(l_ref)[:, -1], -1))


def test_int8_kv_engine_end_to_end(single):
    """Batched engine with int8 cache completes requests; greedy output
    matches the full-precision engine on the debug model."""
    eng = BatchedEngine("preset:debug", template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_quant="int8")
    try:
        prompt = single.tokenizer.encode("the quick brown fox")
        want = single.generate(prompt, max_new_tokens=8)
        got = eng.generate(prompt, max_new_tokens=8)
        assert got == want, (got, want)
    finally:
        eng.close()
