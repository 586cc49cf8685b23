"""Plain reference of the MiMo-V2 language model's forward pass (model_type
``mimo_v2``: MiMo-V2-Flash, MiMo-V2.5), in straightforward ``jax.numpy`` and
float32 at ``jax.default_matmul_precision("highest")``. No cache, no kernel, no
batching, one sequence at a time; it imports nothing of the program under test.
Written from the published ``config.json`` and the equations of the published
modelling code:

  every layer   h  = x + Attn(RMSNorm(x));   x' = h + FFN(RMSNorm(h))   (no bias)
  Attn          64 query heads; q and k heads of width 192, v heads of width
                128, o_proj 64*128 -> D. RoPE ("rotate half") on the first
                int(192 * 0.334) = 64 dims of each q and k head, the other 128
                pass through. v <- 0.707 v (attention_value_scale). Scores
                scaled by 192 ** -0.5, softmax in float32, grouped-query heads.
    global      4 KV heads, theta 1e7, causal over the whole context, no sink.
    window      8 KV heads, theta 1e4, key j visible to query i iff
                0 <= i - j < 128, and a learned sink logit per head:
                p = softmax([s_i,: , sink_h]), the sink's column dropped before
                p V (the rows of p sum to less than one).
  FFN dense     (layer 0) SwiGLU D -> 16384 -> D.
  FFN experts   g = x W_r in float32 (W_r: D x 256); s = sigmoid(g); the 8
                experts with the largest s + b are chosen (b =
                e_score_correction_bias, ``noaux_tc``, one group); their
                weights are s WITHOUT b, divided by their sum (norm_topk_prob),
                times routed_scaling_factor (null = 1); each expert is SwiGLU
                D -> 2048 -> D; no shared expert; no token is dropped.
  final RMSNorm, untied output head.

The chip's share: ``experts_held`` of the ``experts_total`` experts are held,
from ``first_held`` on. The router scores all of them; pairs whose expert is
absent add nothing, here as in the program, and the partial sum goes on to the
next layer. With every expert held this is the published layer.

Departures from the published code, each on purpose:
- text only: the vision and audio encoders have no keys in the language
  model's config and are left out;
- no multi-token-prediction layers: requests are served without self-drafting;
- ``attention_chunk_size`` 128 is taken as a tiling hint of the published
  runtime: it has no effect on the equations above;
- weights are kept in the type they are served in (bf16) and upcast a layer
  (and within an expert layer, an expert) at a time, because a float32 copy of
  9 GB does not fit beside the model;
- masked scores get -1e30, not -inf, so a padding row softmaxes to a finite
  value instead of NaN.

``precision="int8"`` is the CONTROL of the benchmark's correctness check, not a
reference: the same mathematics with every matmul operand rounded to 8-bit
integers (weights per output channel, activations per token, symmetric), the
nearest precision below the bf16 the configuration states.

Two switches exist for the tests that show a mechanism matters, and nothing
else reads them: ``mc["correction_in_weights"]`` (adds b to the weights too)
and ``mc["no_correction"]`` (selects without b).
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


def matmul(x, w, precision):
    x, w = x.astype(F32), w.astype(F32)
    if precision == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, positions, theta, rotary_dim):
    """x [T, H, d]; rotate-half on the first ``rotary_dim`` dims, the rest pass."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = rot[..., :half], rot[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def runs_of(mc: dict) -> list:
    """[(attention type, ffn type, count)] of consecutive like layers, in order."""
    out = []
    for t, f in zip(mc["layer_types"], mc["ffn_types"]):
        if out and out[-1][0] == t and out[-1][1] == f:
            out[-1][2] += 1
        else:
            out.append([t, f, 1])
    return [tuple(r) for r in out]


def attention(x, lw, positions, valid, mc, kind, lora, lora_scale, precision):
    """One attention layer on one sequence: x [T, D] float32."""
    H, d, dv = mc["num_heads"], mc["head_dim"], mc["v_head_dim"]
    window = mc["sliding_window"] if kind == "window" else None
    KV = mc["window_num_kv_heads"] if kind == "window" else mc["num_kv_heads"]
    theta = mc["window_rope_theta"] if kind == "window" else mc["rope_theta"]
    rotary = int(d * mc["partial_rotary_factor"])
    rotary -= rotary % 2
    T = x.shape[0]

    def proj(h, name):
        out = matmul(h, lw[name]["kernel"], precision)
        if lora is not None and name in lora:
            a, b = lora[name]["a"].astype(F32), lora[name]["b"].astype(F32)
            out = out + lora_scale * ((h.astype(F32) @ a) @ b)
        return out

    n = rms_norm(x, lw["input_layernorm"]["scale"], mc["rms_norm_eps"])
    q = rope(proj(n, "q_proj").reshape(T, H, d), positions, theta, rotary)
    k = rope(proj(n, "k_proj").reshape(T, KV, d), positions, theta, rotary)
    v = proj(n, "v_proj").reshape(T, KV, dv) * mc["attention_value_scale"]
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    idx = jnp.arange(T)
    allow = (idx[:, None] >= idx[None, :]) & valid[None, :].astype(bool)
    if window is not None:
        allow &= (positions[:, None] - positions[None, :]) < window
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
    scores = jnp.where(allow[None], scores, NEG)
    if kind == "window" and mc.get("window_sink"):
        sink = jnp.broadcast_to(lw["attention_sink_bias"].astype(F32)[:, None, None], (H, T, 1))
        p = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hts,shd->thd", p, v).reshape(T, H * dv)
    return x + proj(attn, "o_proj")


def dense_ffn(h, lw, mc, precision):
    n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
    gate = matmul(n, lw["gate_proj"]["kernel"], precision)
    up = matmul(n, lw["up_proj"]["kernel"], precision)
    return h + matmul(jax.nn.silu(gate) * up, lw["down_proj"]["kernel"], precision)


def expert_ffn(h, lw, mc, precision):
    """The held experts' part of one expert layer: every token goes through
    every held expert, weighted by what the router gave that expert for it
    (0 where it was not chosen)."""
    n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
    g = matmul(n, lw["router"]["kernel"], precision)
    s = jax.nn.sigmoid(g)
    b = lw["e_score_correction_bias"].astype(F32)
    pick = s if mc.get("no_correction") else s + b
    _, chosen = jax.lax.top_k(pick, mc["experts_per_token"])  # [T, k]
    w = jnp.take_along_axis(s + b if mc.get("correction_in_weights") else s, chosen, axis=-1)
    if mc.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * float(mc.get("routed_scaling_factor") or 1.0)
    ids = mc["first_held"] + jnp.arange(mc["experts_held"])

    def one(acc, xs):
        e, gate, up, down = xs
        we = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [T]
        y = matmul(jax.nn.silu(matmul(n, gate, precision)) * matmul(n, up, precision),
                   down, precision)
        return acc + we[:, None] * y, None

    ex = lw["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(n),
                        (ids, ex["gate_proj"], ex["up_proj"], ex["down_proj"]))
    return h + y


def _static(mc):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in mc.items() if not isinstance(v, dict)))


@functools.partial(jax.jit, static_argnames=("mc_items", "lora_scale", "precision"))
def _hidden(params, tokens, positions, valid, lora, *, mc_items, lora_scale, precision):
    """Final-norm hidden states [T, D] of one sequence; each run of like layers
    under a scan, so that one layer's float32 weights and scores are alive at a time."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(F32)
        for i, (kind, ffn, _) in enumerate(runs_of(mc)):
            key = f"run{i}"

            def body(x, xs, kind=kind, ffn=ffn):
                lw, ll = xs
                h = attention(x, lw, positions, valid, mc, kind, ll, lora_scale, precision)
                out = (dense_ffn if ffn == "dense" else expert_ffn)(h, lw, mc, precision)
                return out, None

            x, _ = jax.lax.scan(jax.checkpoint(body), x,
                                (params["layers"][key], (lora or {}).get(key)))
        return rms_norm(x, params["norm"]["scale"], mc["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(w_head, hidden, *, precision):
    with jax.default_matmul_precision("highest"):
        return matmul(hidden, w_head, precision)


def sequence_logits(params, mc, tokens, rows, lora=None, lora_scale=0.0, valid_len=None,
                    precision="f32"):
    """Logits [len(rows), V] at the given positions of ONE sequence ``tokens``
    (a prompt followed by the tokens served for it): the full forward pass,
    positions 0..T-1. Tokens from ``valid_len`` on are padding (to a length
    already compiled). ``lora`` is ``{run<i>: {target: {a, b}}}`` of one adapter."""
    T = len(tokens)
    tok = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    valid = (pos < (T if valid_len is None else valid_len)).astype(jnp.int32)
    hidden = _hidden(params, tok, pos, valid, lora, mc_items=_static(mc),
                     lora_scale=float(lora_scale), precision=precision)
    return _logits(params["lm_head"]["kernel"], hidden[jnp.asarray(rows, jnp.int32)],
                   precision=precision)
