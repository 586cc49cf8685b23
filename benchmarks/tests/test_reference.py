"""The plain reference against the program's own forward pass at a small size
on the CPU, in float32, for what the two configurations differ in: q/k/v bias,
grouped-query heads, the sliding window, LoRA, packed segments."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spec
import weights
from reference import decoder


@pytest.mark.parametrize("cellname", ["rehearsal-serve", "rehearsal-batch"])
def test_reference_matches_the_program_forward(cellname):
    from datatunerx_tpu.models.llama import forward

    cell = spec.load_cell(cellname)
    cfg = spec.register_preset(cell)
    mc = cell.model_fields
    params = weights.draw_params(mc, 7, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 7, count=2, rank=4, targets=["q_proj", "v_proj"], b_std=0.05)
    one = {t: {"a": lora[t]["a"][1], "b": lora[t]["b"][1]} for t in lora}
    T = 150  # past debug-mistral's window of 96, so the window binds
    toks = np.random.default_rng(0).integers(10, 3000, size=T)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, jnp.asarray(toks)[None], cfg, lora=({"layers": one}, 4.0))
    ref = decoder.sequence_logits(params, mc, toks.tolist(), list(range(T)), one, 4.0)
    assert float(jnp.max(jnp.abs(got[0] - ref))) < 2e-5 * float(jnp.std(ref)) * 100
    if mc.get("attention_bias"):
        assert float(jnp.max(jnp.abs(params["layers"]["q_proj"]["bias"]))) > 0  # bias is exercised
    # a padded tail (to a compiled length) changes nothing before it
    padded = decoder.sequence_logits(params, mc, toks.tolist() + [0] * 106, list(range(T)), one, 4.0,
                                     valid_len=T)
    assert float(jnp.max(jnp.abs(padded - ref))) < 1e-5


def test_reference_loss_matches_the_program_on_packed_rows():
    from datatunerx_tpu.models.llama import forward
    from datatunerx_tpu.training.loss import causal_lm_loss

    cell = spec.load_cell("rehearsal-train")
    cfg = spec.register_preset(cell)
    mc = cell.model_fields
    params = weights.draw_params(mc, 3, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 3, count=1, rank=4, targets=["q_proj", "v_proj"], b_std=0.05)
    one = {t: {"a": lora[t]["a"][0], "b": lora[t]["b"][0]} for t in lora}
    rng = np.random.default_rng(1)
    T = 128
    seg = np.array([[1] * 50 + [2] * 60 + [0] * 18, [1] * 128])
    pos = np.array([list(range(50)) + list(range(60)) + [0] * 18, list(range(128))])
    ids = rng.integers(10, 3000, size=(2, T))
    labels = np.where(seg > 0, ids, -100)
    labels[0, 0] = labels[0, 50] = labels[1, 0] = -100
    batch = {"input_ids": ids.astype(np.int32), "labels": labels.astype(np.int32),
             "attention_mask": (seg > 0).astype(np.int32), "segment_ids": seg.astype(np.int32),
             "positions": pos.astype(np.int32)}
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, jnp.asarray(batch["input_ids"]), cfg,
                            attention_mask=jnp.asarray(batch["attention_mask"]),
                            segment_ids=jnp.asarray(batch["segment_ids"]),
                            positions=jnp.asarray(batch["positions"]), lora=({"layers": one}, 4.0))
        s, n = causal_lm_loss(logits, jnp.asarray(batch["labels"]))
    loss, grads = decoder.loss_and_grads(params, mc, one, batch, 4.0)
    assert float(loss) == pytest.approx(float(s / n), rel=1e-5)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree_util.tree_leaves(grads))


def test_adamw_and_clip_follow_optax():
    import optax

    p = {"w": jnp.asarray([0.5, -1.0, 2.0])}
    g = {"w": jnp.asarray([3.0, -4.0, 12.0])}
    opt = optax.chain(optax.clip_by_global_norm(1.0),
                      optax.adamw(optax.cosine_decay_schedule(2e-4, 1000), weight_decay=0.01))
    st = opt.init(p)
    pr, m, v = p, {"w": jnp.zeros(3)}, {"w": jnp.zeros(3)}
    for step in range(3):
        up, st = opt.update(g, st, p)
        p = optax.apply_updates(p, up)
        gc = decoder.clip_by_global_norm(g, 1.0)
        pr, m, v = decoder.adamw_step(pr, gc, m, v, step, lr=decoder.cosine_lr(step, 2e-4, 1000),
                                      weight_decay=0.01)
    np.testing.assert_allclose(np.asarray(pr["w"]), np.asarray(p["w"]), rtol=1e-6)
