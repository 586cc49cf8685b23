"""Quantization correctness: pack/dequant math, XLA matmuls, Pallas kernels
(interpret mode on CPU) vs the XLA reference, and the QLoRA training path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import ModelConfig
from datatunerx_tpu.models.llama import forward, init_params
from datatunerx_tpu.ops.quant import (
    NF4_CODE,
    dequant_int8,
    dequant_nf4,
    matmul_int8,
    matmul_nf4,
    nf4_scales,
    quantize_int8,
    quantize_nf4,
    quantize_model_params,
)

CFG = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, max_seq_len=64, remat="none",
)


def _w(rng, shape, scale=0.05):
    return jnp.asarray(rng.normal(size=shape, scale=scale), jnp.float32)


def test_int8_roundtrip_error():
    rng = np.random.default_rng(0)
    w = _w(rng, (128, 64))
    qw = quantize_int8(w)
    assert qw["q"].dtype == jnp.int8
    deq = dequant_int8(qw["q"], qw["scale"])
    err = np.abs(np.asarray(deq - w))
    per_chan_max = np.max(np.abs(np.asarray(w)), axis=0)
    assert (err.max(axis=0) <= per_chan_max / 127 * 1.01).all()


def test_int8_matmul_matches_dequant():
    rng = np.random.default_rng(1)
    w = _w(rng, (64, 96))
    x = _w(rng, (8, 64), scale=1.0)
    qw = quantize_int8(w)
    ref = x @ dequant_int8(qw["q"], qw["scale"])
    out = matmul_int8(x, qw["q"], qw["scale"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_nf4_roundtrip_error():
    rng = np.random.default_rng(2)
    w = _w(rng, (128, 64))
    qw = quantize_nf4(w)
    assert qw["packed"].dtype == jnp.uint8
    assert qw["packed"].shape == (128 * 64 // 64, 32)
    deq = dequant_nf4(qw, (128, 64))
    # nf4 max error per block <= scale * max code gap (~0.14) + double-quant slack
    scales = np.asarray(nf4_scales(qw))
    blocks_err = np.abs(np.asarray(deq - w)).T.reshape(-1, 64)
    gap = np.max(np.diff(NF4_CODE)) / 2
    assert (blocks_err.max(axis=1) <= scales * gap * 1.2 + 1e-3).all()


def test_nf4_codebook_values_exact():
    # weights already equal to code values * scale must roundtrip exactly
    scale = 0.07
    w = jnp.asarray(np.tile(NF4_CODE * scale, 8).reshape(2, 64).T, jnp.float32)
    qw = quantize_nf4(w)
    deq = dequant_nf4(qw, (64, 2))
    np.testing.assert_allclose(np.asarray(deq), np.asarray(w), atol=scale / 120)


def test_nf4_matmul_matches_dequant():
    rng = np.random.default_rng(3)
    w = _w(rng, (64, 96))
    x = _w(rng, (8, 64), scale=1.0)
    qw = quantize_nf4(w)
    ref = x @ dequant_nf4(qw, (64, 96))
    out = matmul_nf4(x, qw, (64, 96))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_pallas_kernels_match_xla(mode):
    from datatunerx_tpu.ops.pallas_quant import pallas_matmul_int8, pallas_matmul_nf4

    rng = np.random.default_rng(4)
    K, N = 128, 256
    w = _w(rng, (K, N))
    x = _w(rng, (4, 40, K), scale=1.0)  # M=160: exercises row padding
    if mode == "int8":
        qw = quantize_int8(w)
        ref = matmul_int8(x, qw["q"], qw["scale"])
        out = pallas_matmul_int8(x, qw["q"], qw["scale"], block_m=64, block_n=128)
    else:
        qw = quantize_nf4(w)
        ref = matmul_nf4(x, qw, (K, N))
        out = pallas_matmul_nf4(x, qw, (K, N), block_m=64, block_n=128)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_pallas_int8_tiles_k_beyond_one_block():
    """K above the kernel's K tile (llama2-7b's down_proj K=11008 overflowed
    scoped VMEM as one whole-K block on the chip) runs as a multi-step K grid
    with an f32 accumulator, its K block the largest 128-multiple divisor
    of K within the tile."""
    from datatunerx_tpu.ops._pallas import pick_block_n
    from datatunerx_tpu.ops.pallas_quant import (
        _INT8_BLOCK_K,
        pallas_matmul_int8,
    )

    K, N = 2 * _INT8_BLOCK_K + 256, 128  # 4352 = 128 · 34
    assert pick_block_n(K, _INT8_BLOCK_K) == 256  # 17 steps of 256
    rng = np.random.default_rng(12)
    w = _w(rng, (K, N))
    x = _w(rng, (24, K), scale=1.0)
    qw = quantize_int8(w)
    ref = matmul_int8(x, qw["q"], qw["scale"])
    out = pallas_matmul_int8(x, qw["q"], qw["scale"], block_m=64, block_n=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_pallas_nf4_odd_chunk_k():
    """Real-model K values (5632, 11008) are not 128·64-multiples: with
    K=1408 the kernel runs 2 chunks of 11 blocks — odd blocks-per-chunk and
    a multi-step K grid, the shape class the chunk-major layout exists for."""
    from datatunerx_tpu.ops.pallas_quant import _pick_chunk, pallas_matmul_nf4

    K, N = 1408, 128
    assert _pick_chunk(K // 64, 64) == 11 * 64
    rng = np.random.default_rng(11)
    w = _w(rng, (K, N))
    x = _w(rng, (24, K), scale=1.0)
    qw = quantize_nf4(w)
    ref = matmul_nf4(x, qw, (K, N))
    out = pallas_matmul_nf4(x, qw, (K, N), block_m=64, block_n=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantized_forward_close_to_full(mode):
    import dataclasses

    params = init_params(CFG, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(5).integers(0, 256, (2, 16), np.int32))
    full, _ = forward(params, toks, CFG)

    qcfg = dataclasses.replace(CFG, quantization=mode)
    qparams = quantize_model_params(params, mode)
    quant, _ = forward(qparams, toks, qcfg)
    # quantized logits track full-precision within loose tolerance
    corr = np.corrcoef(np.asarray(full).ravel(), np.asarray(quant).ravel())[0, 1]
    assert corr > 0.99, corr


def test_qlora_training_decreases_loss():
    """QLoRA: frozen quantized base + trainable adapters (reference
    bnb int4 + peft path, cmd/tuning/train.py:224-280)."""
    import dataclasses

    from datatunerx_tpu.training import TrainConfig, Trainer
    from datatunerx_tpu.training.loss import IGNORE_INDEX

    qcfg = dataclasses.replace(CFG, quantization="int4")
    params = quantize_model_params(init_params(CFG, jax.random.PRNGKey(0)), "int4")
    tr = Trainer(qcfg, TrainConfig(
        finetuning_type="lora", lora_rank=4, lora_dropout=0.0,
        learning_rate=3e-2, scheduler="constant", total_steps=30,
        compute_dtype=None,
    ))
    state = tr.init_state(params, jax.random.PRNGKey(1))
    rng = np.random.default_rng(6)
    toks = rng.integers(4, 256, (4, 16)).astype(np.int32)
    labels = toks.copy()
    labels[:, :4] = IGNORE_INDEX
    batch = {"input_ids": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    losses = []
    for _ in range(20):
        state, m = tr.train_step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses
    # base stayed quantized (no kernel materialized in state)
    assert "quant" in state.params["layers"]["q_proj"]


def test_stacked_quantize_matches_per_layer():
    """quantize_model_params' one-dispatch stacked path must be bit-identical
    to the per-matrix reference functions (searchsorted-on-midpoints ==
    16-way argmin, including tie behavior)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from datatunerx_tpu.ops.quant import (
        _quantize_int8_stacked,
        _quantize_nf4_stacked,
        quantize_int8,
        quantize_nf4,
    )

    kern = jax.random.normal(jax.random.PRNGKey(3), (3, 128, 64), jnp.float32)
    st = _quantize_nf4_stacked(kern)
    for i in range(3):
        ref = quantize_nf4(kern[i])
        # stacked layout stores flat bytes per layer (tile-padding-free);
        # same bytes, same order as the per-matrix [nb, b/2] format
        np.testing.assert_array_equal(np.asarray(st["packed"][i]),
                                      np.asarray(ref["packed"]).reshape(-1))
        np.testing.assert_array_equal(np.asarray(st["scale_q"][i]),
                                      np.asarray(ref["scale_q"]))
        np.testing.assert_allclose(np.asarray(st["meta"][i]),
                                   np.asarray(ref["meta"]), rtol=1e-7)
    st8 = _quantize_int8_stacked(kern)
    for i in range(3):
        ref8 = quantize_int8(kern[i])
        np.testing.assert_array_equal(np.asarray(st8["q"][i]),
                                      np.asarray(ref8["q"]))
        # jit fusion may reorder the absmax reduction: 1-ulp scale drift ok
        np.testing.assert_allclose(np.asarray(st8["scale"][i]),
                                   np.asarray(ref8["scale"]), rtol=1e-6)


def test_pallas_quant_kernels_differentiate():
    """QLoRA training through the fused kernels: grads w.r.t. x must match
    the XLA reference path (the custom_vjp backward is dx = g @ Wᵀ on
    dequantized weights; frozen base gets no grads)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from datatunerx_tpu.ops.pallas_quant import (
        pallas_matmul_int8,
        pallas_matmul_nf4,
    )

    rng = np.random.default_rng(6)
    K, N = 128, 256
    w = _w(rng, (K, N))
    x = jnp.asarray(rng.standard_normal((8, K)), jnp.float32)

    q8 = quantize_int8(w)
    g_pallas = jax.grad(lambda x: jnp.sum(
        pallas_matmul_int8(x, q8["q"], q8["scale"], block_m=64, block_n=128) ** 2
    ))(x)
    g_ref = jax.grad(lambda x: jnp.sum(
        matmul_int8(x, q8["q"], q8["scale"]) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_ref),
                               atol=1e-2, rtol=1e-2)

    q4 = quantize_nf4(w)
    g_pallas = jax.grad(lambda x: jnp.sum(
        pallas_matmul_nf4(x, q4, (K, N), block_m=64, block_n=128) ** 2))(x)
    g_ref = jax.grad(lambda x: jnp.sum(matmul_nf4(x, q4, (K, N)) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_ref),
                               atol=1e-2, rtol=1e-2)


def test_pallas_nf4_transposed_kernel_matches_reference():
    """The fused dx kernel (g @ Wᵀ with per-tile dequant, round-3): exact
    against the XLA dequant product across N-tile accumulation (nn > 1),
    non-128·64-multiple K, and row padding."""
    import numpy as np

    import jax.numpy as jnp

    from datatunerx_tpu.ops.pallas_quant import (
        _pallas_matmul_nf4_t_impl,
        _pick_chunk,
    )

    # the [M, K] output is tiled on its LANE dim by the chunk, so the chunk
    # must be a 128-multiple: 22 blocks → 2 (128 lanes), not the forward
    # kernel's 11 (704 — refused by Mosaic at tinyllama's K=5632 on the chip)
    assert _pick_chunk(22, 64) == 11 * 64
    assert _pick_chunk(22, 64, lane_aligned=True) == 2 * 64
    rng = np.random.default_rng(11)
    for K, N, M in ((320, 256, 8), (384, 512, 33), (128, 384, 64),
                    (1408, 128, 8)):
        w = _w(rng, (K, N))
        q4 = quantize_nf4(w)
        wd = np.asarray(dequant_nf4(q4, (K, N)))
        g = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
        dx = _pallas_matmul_nf4_t_impl(g, q4, (K, N),
                                       block_m=32, block_n=128)
        ref = np.asarray(g) @ wd.T
        np.testing.assert_allclose(np.asarray(dx), ref, atol=1e-3, rtol=1e-3)
