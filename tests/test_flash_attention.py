"""Flash attention kernel vs the XLA reference path (interpret mode on CPU)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import ModelConfig
from datatunerx_tpu.models.llama import forward, init_params
from datatunerx_tpu.ops.attention import make_causal_bias, xla_attention
from datatunerx_tpu.ops.flash_attention import flash_attention


def _qkv(rng, B=2, T=128, H=4, KV=2, d=32):
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("T,block", [(128, 64), (256, 128), (96, 32)])
def test_flash_matches_xla_causal(T, block):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, T=T)
    B = q.shape[0]
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    bias = make_causal_bias(pos, pos)
    ref = xla_attention(q, k, v, bias)
    out = flash_attention(q, k, v, block_q=block, block_k=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_gqa_grouping():
    """Each query head must read its own KV group, not a mixed one."""
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, B=1, T=64, H=4, KV=2)
    pos = jnp.arange(64)[None]
    bias = make_causal_bias(pos, pos)
    ref = xla_attention(q, k, v, bias)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_model_forward_flash_matches_xla():
    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=256, remat="none",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 128, (2, 128), np.int32))
    ref, _ = forward(params, toks, cfg)
    fcfg = dataclasses.replace(cfg, attention_impl="flash")
    out, _ = forward(params, toks, fcfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_flash_falls_back_for_packed_and_cache():
    """Packed segments ride the kernel (T 32 is one tile); a forward with a
    cache takes the exact biased path. Both give finite logits."""
    cfg = ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=2, num_kv_heads=2, max_seq_len=64, remat="none",
        attention_impl="flash",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 128, (1, 32), np.int32))
    segs = jnp.asarray(np.repeat([[1, 2]], 16, axis=1).reshape(1, 32))
    logits, _ = forward(params, toks, cfg, segment_ids=segs)
    assert np.isfinite(np.asarray(logits)).all()

    from datatunerx_tpu.models.llama import init_cache

    cache = init_cache(cfg, 1, 32, dtype=jnp.float32)
    logits2, cache = forward(params, toks[:, :8], cfg,
                             positions=jnp.arange(8)[None], cache=cache)
    assert np.isfinite(np.asarray(logits2)).all()


def test_flash_training_grad_matches_xla():
    """Backward pass through the kernel (interpret-mode autodiff) vs XLA."""
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, B=1, T=64, H=2, KV=2, d=16)
    pos = jnp.arange(64)[None]
    bias = make_causal_bias(pos, pos)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, bias) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4)


def test_flash_segment_masking_matches_xla():
    """Packed-segment flash vs the biased XLA path, forward + gradients."""
    from datatunerx_tpu.ops.flash_attention import flash_attention as fa

    rng = np.random.default_rng(7)
    B, T, H, KV, d = 2, 128, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    # three segments + trailing padding (id 0)
    segs = np.zeros((B, T), np.int32)
    segs[:, :40] = 1
    segs[:, 40:90] = 2
    segs[:, 90:120] = 3
    segs = jnp.asarray(segs)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))  # row-global positions

    bias = make_causal_bias(pos, pos, q_segment_ids=segs, kv_segment_ids=segs)
    ref = xla_attention(q, k, v, bias)
    out = fa(q, k, v, segment_ids=segs, block_q=32, block_k=32)
    valid = np.asarray(segs > 0)
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=2e-5, rtol=2e-5)

    def loss_ref(q, k, v):
        m = jnp.asarray(valid)[:, :, None, None]
        return jnp.sum(jnp.where(m, xla_attention(q, k, v, bias), 0.0) ** 2)

    def loss_fa(q, k, v):
        m = jnp.asarray(valid)[:, :, None, None]
        return jnp.sum(jnp.where(
            m, fa(q, k, v, segment_ids=segs, block_q=32, block_k=32), 0.0) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4)


def test_packed_training_flash_matches_xla():
    """End-to-end: packed batch trained with attention_impl=flash equals xla."""
    from datatunerx_tpu.models.config import ModelConfig
    from datatunerx_tpu.models.llama import init_params
    from datatunerx_tpu.training import TrainConfig, Trainer
    from datatunerx_tpu.training.loss import IGNORE_INDEX

    base = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
                num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
                remat="none")
    rng = np.random.default_rng(9)
    toks = rng.integers(4, 256, (2, 128)).astype(np.int32)
    segs = np.zeros((2, 128), np.int32)
    segs[:, :50] = 1
    segs[:, 50:110] = 2
    positions = np.concatenate([np.arange(50), np.arange(60), np.zeros(18)]
                               ).astype(np.int32)[None].repeat(2, 0)
    labels = np.where(segs > 0, toks, IGNORE_INDEX)
    batch = {"input_ids": jnp.asarray(toks), "labels": jnp.asarray(labels),
             "segment_ids": jnp.asarray(segs),
             "positions": jnp.asarray(positions),
             "attention_mask": jnp.asarray((segs > 0).astype(np.int32))}

    losses = {}
    for impl in ("xla", "flash"):
        cfg = ModelConfig(**base, attention_impl=impl)
        tr = Trainer(cfg, TrainConfig(finetuning_type="lora", lora_rank=4,
                                      lora_dropout=0.0, learning_rate=1e-2,
                                      scheduler="constant", total_steps=5,
                                      compute_dtype=None))
        state = tr.init_state(init_params(cfg, jax.random.PRNGKey(0)),
                              jax.random.PRNGKey(1))
        state, m = tr.train_step(state, batch)
        losses[impl] = float(m["loss"])
    np.testing.assert_allclose(losses["flash"], losses["xla"], rtol=1e-5)


def _window_case(T, packed, dtype, seed=11):
    """GQA 4:1 operands, the packed layout (three documents and a padded
    tail) or none, and the oracle's bias for ``window``."""
    rng = np.random.default_rng(seed)
    B, H, KV, d = 2, 4, 1, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, n, d)), dtype)
               for n in (H, KV, KV))
    segs = None
    if packed:
        ids = np.zeros((B, T), np.int32)
        ids[:, :70] = 1
        ids[:, 70:T - 50] = 2  # longer than every window tried
        ids[:, T - 50:T - 16] = 3
        segs = jnp.asarray(ids)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))  # row index

    def bias(window):
        return make_causal_bias(pos, pos, sliding_window=window,
                                q_segment_ids=segs, kv_segment_ids=segs)

    return q, k, v, segs, bias


def _out_and_grads(f, q, k, v):
    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    return (f(q, k, v),) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("window", [24, 100, 130])
@pytest.mark.parametrize("block", [64, 128])
def test_flash_window_matches_xla(block, window, packed, dtype):
    """The window in the three kernels (mask and tile skip) against the
    biased einsum path: windows that bind and fall on no tile edge, forward
    and dq / dk / dv. Row index stands for position, as inside a packed
    segment it does."""
    T = 256
    q, k, v, segs, bias = _window_case(T, packed, dtype)
    got = _out_and_grads(
        lambda q, k, v: flash_attention(
            q, k, v, segment_ids=segs, sliding_window=window,
            block_q=block, block_k=block), q, k, v)
    want = _out_and_grads(
        lambda q, k, v: xla_attention(q, k, v, bias(window)), q, k, v)
    # f32: the kernels' own rounding; bf16: both sides round the
    # probabilities and ds to 8 bits before a product, in another order
    tol = 5e-4 if dtype == jnp.float32 else 4e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(
            a, b, atol=tol * max(1.0, float(np.abs(b).max())), rtol=tol,
            err_msg=name)


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
def test_flash_window_that_cannot_bind_is_the_windowless_kernel(packed):
    """window >= T removes no key: the call emits the kernels it emits with
    no window, bit for bit, forward and gradients."""
    T = 256
    q, k, v, segs, _ = _window_case(T, packed, jnp.float32)

    def run(window):
        return _out_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, segment_ids=segs, sliding_window=window,
                block_q=64, block_k=64), q, k, v)

    for window in (T, 4096):
        for a, b in zip(run(window), run(None)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jaxprs = {w: str(jax.make_jaxpr(lambda q, k, v, w=w: flash_attention(
        q, k, v, segment_ids=segs, sliding_window=w))(q, k, v))
        for w in (None, T, T - 1)}
    assert jaxprs[T] == jaxprs[None] != jaxprs[T - 1]


def test_windowed_model_trains_through_flash(capsys):
    """A windowed single-kind model with attention_impl=flash: the cache-less
    forward traces the kernels and says so, and logits and LoRA gradients
    equal the einsum path's. (``debug`` + window 24: no preset is both
    windowed and single-kind.)"""
    from datatunerx_tpu.models import get_config
    from datatunerx_tpu.models.llama import _log_attention_once
    from datatunerx_tpu.models.lora import init_lora_params

    T = 128
    base = get_config("debug", sliding_window=24, remat="none")
    params = init_params(base, jax.random.PRNGKey(0))
    lora = init_lora_params(base, jax.random.PRNGKey(1), rank=4,
                            targets=("q_proj", "v_proj"))
    # b is zero at init: give it a value so that a's gradient is not zero
    lora = jax.tree_util.tree_map(
        lambda x: x + 0.02 if not x.any() else x, lora)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, base.vocab_size, (2, T), np.int32))
    segs = jnp.asarray(
        np.repeat(np.int32([1, 2, 2, 3]), T // 4)[None].repeat(2, 0))
    positions = jnp.asarray(np.concatenate(
        [np.arange(T // 4), np.arange(T // 2), np.arange(T // 4)]
    ).astype(np.int32)[None].repeat(2, 0))  # a 64-token document: 24 binds

    def run(impl):
        cfg = dataclasses.replace(base, attention_impl=impl)

        def loss(lora):
            logits, _ = forward(params, toks, cfg, positions=positions,
                                segment_ids=segs, lora=(lora, 2.0))
            return jnp.mean(logits ** 2), logits

        (_, logits), grads = jax.value_and_grad(loss, has_aux=True)(lora)
        return logits, grads

    _log_attention_once.cache_clear()
    capsys.readouterr()
    logits, grads = run("flash")
    assert ("[attention] requested=flash traced=flash T=128 "
            "sliding_window=24 packed=True\n") in capsys.readouterr().err
    ref_logits, ref_grads = run("xla")
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               atol=1e-4, rtol=1e-4)
    flat, ref_flat = (jax.tree_util.tree_leaves(g) for g in (grads, ref_grads))
    assert len(flat) == 4 and all(np.asarray(g).any() for g in ref_flat)
    for a, b in zip(flat, ref_flat):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3,
            atol=1e-4 * float(np.abs(np.asarray(b)).max()))
