"""Plain reference of the llama-family decoder: forward pass, next-token loss,
LoRA gradients and the AdamW step, in straightforward ``jax.numpy`` and float32
at ``jax.default_matmul_precision("highest")``. No kernel, no cache, no
batching tricks. Written from the published descriptions (Llama / Mistral /
Qwen2 model cards and the Hugging Face modelling code's equations), and it
imports nothing of the program under test.

  h      = x + Attn(RMSNorm(x) ; Wq, Wk, Wv, Wo [+ bq, bk, bv])      (pre-norm)
  x'     = h + Wdown( silu(Wgate n) * (Wup n) ),  n = RMSNorm(h)
  Attn:  rotary embedding in the "rotate half" layout on q and k, grouped-query
         heads (each KV head serves H/KV query heads), softmax(q k^T / sqrt(d))
         over keys that are causal, inside the sliding window (q_pos - k_pos <
         window), in the same packed segment, and not padding.
  LoRA:  W x  ->  W x + (alpha / r) * B (A x)   on the targeted projections.

Departures, each on purpose: weights are kept in the type they are served in
(bf16) and upcast one layer at a time, because a float32 copy of 9 GB does not
fit beside the model; masked scores get -1e30, not -inf, so a padding row
softmaxes to a finite value instead of NaN.

``precision="int8"`` is the CONTROL of the benchmark's correctness check, not a
reference: the same mathematics with every matmul operand rounded to 8-bit
integers (weights per output channel, activations per token, symmetric), the
nearest precision below the bf16 the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30


def _q8(x, axis):
    """Symmetric 8-bit rounding along ``axis`` (fake quantisation in float32)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _ste_q8(x, axis):
    """8-bit rounding whose gradient is the identity (for the training control)."""
    return x + jax.lax.stop_gradient(_q8(x, axis) - x)


def matmul(x, w, precision):
    x, w = x.astype(F32), w.astype(F32)
    if precision == "int8":
        x, w = _ste_q8(x, -1), _ste_q8(w, 0)
    return x @ w


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x [T, H, d]; positions [T]. Rotate-half layout."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d // 2, dtype=F32) / (d // 2)))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def allow_mask(positions, segments, valid, window):
    """[T, T] bool: may query t attend key s. One sequence (row)."""
    T = positions.shape[0]
    idx = jnp.arange(T)
    ok = idx[:, None] >= idx[None, :]
    ok &= segments[:, None] == segments[None, :]
    ok &= valid[None, :].astype(bool)
    if window is not None:
        ok &= (positions[:, None] - positions[None, :]) < window
    return ok


def layer(x, lw, positions, allow, mc, lora=None, lora_scale=0.0, precision="f32"):
    """One decoder layer on one sequence: x [T, D] float32."""
    H, KV = mc["num_heads"], mc["num_kv_heads"]
    d = mc.get("head_dim") or mc["hidden_size"] // H
    T = x.shape[0]

    def proj(h, name):
        out = matmul(h, lw[name]["kernel"], precision)
        if "bias" in lw[name]:
            out = out + lw[name]["bias"].astype(F32)
        if lora is not None and name in lora:
            a, b = lora[name]["a"].astype(F32), lora[name]["b"].astype(F32)
            out = out + lora_scale * ((h.astype(F32) @ a) @ b)
        return out

    n = rms_norm(x, lw["input_layernorm"]["scale"], mc["rms_norm_eps"])
    q = rope(proj(n, "q_proj").reshape(T, H, d), positions, mc["rope_theta"])
    k = rope(proj(n, "k_proj").reshape(T, KV, d), positions, mc["rope_theta"])
    v = proj(n, "v_proj").reshape(T, KV, d)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
    scores = jnp.where(allow[None], scores, NEG)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v).reshape(T, H * d)
    h = x + proj(attn, "o_proj")
    n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
    mlp = proj(jax.nn.silu(proj(n, "gate_proj")) * proj(n, "up_proj"), "down_proj")
    return h + mlp


def _static(mc):
    return tuple(sorted((k, v) for k, v in mc.items() if not isinstance(v, (dict, list))))


@functools.partial(jax.jit, static_argnames=("mc_items", "lora_scale", "precision"))
def _hidden(params, tokens, positions, segments, valid, lora, *, mc_items, lora_scale, precision):
    """Final-norm hidden states [T, D] of one sequence; layers under a scan so
    that one layer's float32 weights and scores are alive at a time."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        allow = allow_mask(positions, segments, valid, mc.get("sliding_window"))
        x = params["embed_tokens"]["embedding"][tokens].astype(F32)

        def body(x, xs):
            lw, ll = xs
            return layer(x, lw, positions, allow, mc, ll, lora_scale, precision), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, (params["layers"], lora))
        return rms_norm(x, params["norm"]["scale"], mc["rms_norm_eps"])


def head(params):
    if "lm_head" in params:
        return params["lm_head"]["kernel"]
    return params["embed_tokens"]["embedding"].T


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(w_head, hidden, *, precision):
    with jax.default_matmul_precision("highest"):
        return matmul(hidden, w_head, precision)


def sequence_logits(params, mc, tokens, rows, lora=None, lora_scale=0.0, valid_len=None,
                    precision="f32"):
    """Logits [len(rows), V] at the given positions of ONE sequence ``tokens``
    (a prompt followed by the tokens served for it): the full forward pass,
    positions 0..T-1, causal, windowed where the configuration has a window.
    Tokens from ``valid_len`` on are padding (to a length already compiled)."""
    T = len(tokens)
    tok = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    valid = (pos < (T if valid_len is None else valid_len)).astype(jnp.int32)
    hidden = _hidden(params, tok, pos, jnp.zeros((T,), jnp.int32), valid,
                     lora, mc_items=_static(mc), lora_scale=float(lora_scale), precision=precision)
    return _logits(head(params), hidden[jnp.asarray(rows, jnp.int32)], precision=precision)


# ------------------------------------------------------------------ training


@functools.partial(jax.jit, static_argnames=("mc_items", "lora_scale", "precision", "ignore"))
def _row_nll_and_grad(lora, params, row, *, mc_items, lora_scale, precision, ignore):
    """Sum of next-token NLL over one packed row's trained positions, its count,
    and the gradient of the SUM with respect to the LoRA leaves."""
    mc = dict(mc_items)

    def nll(lora):
        hidden = _hidden.__wrapped__(
            params, row["input_ids"], row["positions"], row["segment_ids"],
            row["attention_mask"], lora, mc_items=mc_items, lora_scale=lora_scale,
            precision=precision)
        with jax.default_matmul_precision("highest"):
            logits = matmul(hidden[:-1], head(params), precision)
        labels = row["labels"][1:]
        trained = labels != ignore
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.where(trained, labels, 0)[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(trained, picked, 0.0)), jnp.sum(trained)

    (s, n), g = jax.value_and_grad(nll, has_aux=True)(lora)
    return s, n, g


def loss_and_grads(params, mc, lora, batch, lora_scale, precision="f32", ignore=-100):
    """Mean NLL over the batch's trained tokens and the gradient of that mean,
    one packed row at a time (sums are additive over rows)."""
    B = batch["input_ids"].shape[0]
    tot_s, tot_n, tot_g = 0.0, 0, None
    for r in range(B):
        row = {k: jnp.asarray(v[r]) for k, v in batch.items()}
        s, n, g = _row_nll_and_grad(lora, params, row, mc_items=_static(mc),
                                    lora_scale=float(lora_scale), precision=precision,
                                    ignore=ignore)
        tot_s, tot_n = tot_s + s, tot_n + n
        tot_g = g if tot_g is None else jax.tree_util.tree_map(jnp.add, tot_g, g)
    denom = jnp.maximum(tot_n, 1).astype(F32)
    return tot_s / denom, jax.tree_util.tree_map(lambda x: x / denom, tot_g)


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    factor = jnp.where(norm > max_norm, max_norm / norm, 1.0) if max_norm else 1.0
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


def cosine_lr(step, base, total):
    return base * 0.5 * (1.0 + math.cos(math.pi * min(step, total) / total))


def adamw_step(p, g, m, v, step, *, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0):
    """One AdamW update, ``step`` counted from 0 (Loshchilov & Hutter)."""
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)
    p = tm(lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p), p, m, v)
    return p, m, v
