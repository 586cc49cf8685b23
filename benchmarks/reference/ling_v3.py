"""Plain reference of the Ling-3.0 language model's forward pass (model_type
``bailing_hybrid``: Ling-3.0-flash), in straightforward ``jax.numpy`` and
float32 at ``jax.default_matmul_precision("highest")``. No cache, no recurrent
state carried between calls, no chunk form, no absorption, no kernel, no
batching, one sequence at a time; it imports nothing of the program under
test. Written from the published ``config.json`` and the equations of the
families it names (``D`` hidden, ``H`` heads, ``d`` head width):

  every layer   h = x + Mixer(RMSNorm(x));   x' = h + FFN(RMSNorm(h))   (no bias)
  KDA mixer     (Kimi Linear, arXiv:2510.26692 section 3) q~, k~, v~ = n Wq, n Wk,
                n Wv, each H*d wide. Depthwise causal convolution, kernel 4, a
                weight [4] per channel, then SiLU:
                q'_t = silu(sum_i w[:, i] q~_{t-3+i}), rows before the first
                are zero. Per head q = q' / max(|q'|, 1e-6) * d**-0.5,
                k = k' / max(|k'|, 1e-6), v = v'. No RoPE. Decay per head and
                key channel g_t = lower_bound * sigmoid(exp(A_log[h]) * (n Wf +
                dt_bias)) (lower_bound -5), write strength beta_t = sigmoid(n Wb)
                per head. State S [H, d, d] float32 from zero, TOKEN BY TOKEN:
                    S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
                    o_t = S^T q_t
                then o_t <- RMSNorm_head(o_t) * sigmoid(n Wg) (channel-wise),
                y = concat_heads(o) Wo.
  MLA mixer     (DeepSeek-V2, arXiv:2405.04434 section 2.1, q uncompressed)
                q = n Wq -> [H, nope 128 | rope 64]; [c 512 | kR 64] = n Wkva;
                c <- RMSNorm(c); RoPE on kR and on q's rope lanes, INTERLEAVED
                pairs (2i, 2i+1), theta 6e6; [k_nope_h | v_h] = c Wkvb[h];
                score = (q_nope_h . k_nope_h + q_rope_h . kR) / sqrt(192),
                causal, softmax in float32; o_h = sum p v_h;
                o_h <- o_h * sigmoid((n Wg)_h), one scalar a head; y = concat(o) Wo.
  FFN dense     SwiGLU D -> intermediate_size -> D.
  FFN experts   s = sigmoid(n Wr) in float32; c = s + b; the experts lie in
                n_group groups of consecutive experts, a group's score is the
                sum of its 2 largest c, the topk_group best groups are kept;
                among their experts the experts_per_token with the largest c
                are chosen; weights are s (without b) of the chosen, divided by
                their sum, times routed_scaling_factor; plus ONE shared expert
                (SwiGLU, every token, weight 1).
  final RMSNorm, untied output head.

The chip's share: ``experts_held`` of the ``experts_total`` experts are held,
from ``first_held`` on. The router scores all of them in all their groups;
pairs whose expert is absent add nothing, here as in the program; the shared
expert is whole. With every expert held this is the published layer.

Departures from the published model, each on purpose (the configuration file
lists them under ``assumed``): no multi-token-prediction module; the SwiGLU
clamp (``expert_swiglu_limit_list``) is 0 in every layer held and is not
computed; weights stay in the type they are served in (bf16) and are upcast a
layer (an expert) at a time; masked scores get -1e30, not -inf; an adapter is
folded into the projections it sits on (``W + s A B``, float32) before the pass
and every sequence is padded to a multiple of ``BUCKET`` tokens, so that base
and adapters, short and long, share one compiled program (``_fold`` says why).

``precision="int8"`` is the CONTROL of the benchmark's correctness check, not a
reference: the same mathematics with every matmul operand rounded to 8-bit
integers (weights per output channel, activations per token, symmetric), the
nearest precision below the bf16 the configuration states. The recurrence on
``S`` stays float32 in the control too: it is no matmul operand.
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


def rope_interleaved(x, positions, theta):
    """x [T, H, r]: rotates each pair of lanes (2i, 2i + 1) by position * theta ** (-2i / r)."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r // 2, dtype=F32) / (r // 2)))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def runs_of(mc: dict) -> list:
    """[(mixer type, ffn type, count)] of consecutive like layers, in order."""
    out = []
    for t, f in zip(mc["layer_types"], mc["ffn_types"]):
        if out and out[-1][0] == t and out[-1][1] == f:
            out[-1][2] += 1
        else:
            out.append([t, f, 1])
    return [tuple(r) for r in out]


ADAPTABLE = ("q_proj", "k_proj", "v_proj", "o_proj")


@functools.partial(jax.jit, static_argnames=("lora_scale",))
def _fold(kernels, lora, *, lora_scale):
    """``kernels`` ``{run: {projection: [n, in, out]}}``, the mixer projections
    an adapter may sit on, in float32 with the adapter folded in: ``W +
    lora_scale * A B`` (``h W + s (h A) B`` is ``h (W + s A B)``). A request to
    the base gets the same leaves upcast and nothing added, so the forward pass
    below is ONE compiled program for base and adapters alike: a float32 matmul
    at HIGHEST precision costs the TPU's compiler seconds, a forward pass half
    a minute."""
    with jax.default_matmul_precision("highest"):
        out = {}
        for run, ws in kernels.items():
            out[run] = {}
            for name, w in ws.items():
                w = w.astype(F32)
                ll = (lora or {}).get(run, {}).get(name)
                if ll is not None:
                    w = w + lora_scale * jnp.einsum("nir,nro->nio", ll["a"].astype(F32),
                                                    ll["b"].astype(F32))
                out[run][name] = {"kernel": w}
        return out


def with_adapter(params, lora, lora_scale):
    """The parameter tree with ``_fold``'s leaves in place of the bf16 ones."""
    layers = params["layers"]
    folded = _fold({run: {name: lw[name]["kernel"] for name in ADAPTABLE if name in lw}
                    for run, lw in layers.items()}, lora, lora_scale=float(lora_scale))
    return dict(params, layers={run: {**lw, **folded[run]} for run, lw in layers.items()})


def _projector(lw, precision):
    return lambda h, name: matmul(h, lw[name]["kernel"], precision)


def short_conv(x, w):
    """x [T, C] pre-convolution rows, w [C, K]: y_t = silu(sum_i w[:, i] x_{t-(K-1)+i})."""
    T, K = x.shape[0], w.shape[-1]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x.astype(F32)], axis=0)
    y = sum(ext[i:i + T] * w.astype(F32)[None, :, i] for i in range(K))
    return jax.nn.silu(y)


def _unit(x, eps=1e-6):
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)), eps)


def kda(x, lw, positions, valid, mc, precision):
    """One KDA layer on one sequence: x [T, D] float32. The recurrence runs
    token by token from a zero state; a padding row (``valid`` 0, at the tail)
    is causally behind every real one."""
    H, d = mc["num_heads"], mc["head_dim"]
    dv = mc.get("v_head_dim") or d
    T = x.shape[0]
    proj = _projector(lw, precision)
    n = rms_norm(x, lw["input_layernorm"]["scale"], mc["rms_norm_eps"])
    w = lw["conv"]["kernel"]  # [H*d | H*d | H*dv channels, K]
    q = short_conv(proj(n, "q_proj"), w[:H * d]).reshape(T, H, d)
    k = short_conv(proj(n, "k_proj"), w[H * d:2 * H * d]).reshape(T, H, d)
    v = short_conv(proj(n, "v_proj"), w[2 * H * d:]).reshape(T, H, dv)
    q, k = _unit(q) * d ** -0.5, _unit(k)
    f = matmul(n, lw["f_proj"]["kernel"], precision).reshape(T, H, d)
    a = jnp.exp(lw["A_log"].astype(F32))[None, :, None]
    g = float(mc.get("kda_lower_bound", -5.0)) * jax.nn.sigmoid(
        a * (f + lw["dt_bias"].astype(F32).reshape(1, H, d)))
    beta = jax.nn.sigmoid(matmul(n, lw["b_proj"]["kernel"], precision))  # [T, H]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S  # [H, d, dv]
        err = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * err[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, dv), F32), (q, k, v, g, beta))
    o = rms_norm(o, lw["o_norm"]["scale"], mc["rms_norm_eps"])
    o = o * jax.nn.sigmoid(matmul(n, lw["g_proj"]["kernel"], precision)).reshape(T, H, dv)
    return x + proj(o.reshape(T, H * dv), "o_proj")


def mla(x, lw, positions, valid, mc, precision):
    """One MLA layer on one sequence, over expanded heads: x [T, D] float32."""
    H, rank = mc["num_heads"], mc["kv_lora_rank"]
    nope, rot = mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    dv = mc.get("v_head_dim") or mc["head_dim"]
    T = x.shape[0]
    proj = _projector(lw, precision)
    n = rms_norm(x, lw["input_layernorm"]["scale"], mc["rms_norm_eps"])
    q = proj(n, "q_proj").reshape(T, H, nope + rot)
    q_nope, q_rope = q[..., :nope], rope_interleaved(q[..., nope:], positions, mc["rope_theta"])
    row = matmul(n, lw["kv_a_proj"]["kernel"], precision)
    c = rms_norm(row[:, :rank], lw["kv_a_layernorm"]["scale"], mc["rms_norm_eps"])
    k_rope = rope_interleaved(row[:, None, rank:], positions, mc["rope_theta"])  # [T, 1, rot]
    kv = matmul(c, lw["kv_b_proj"]["kernel"], precision).reshape(T, H, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    idx = jnp.arange(T)
    allow = (idx[:, None] >= idx[None, :]) & valid[None, :].astype(bool)
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_rope, k_rope[:, 0])) / math.sqrt(nope + rot)
    p = jax.nn.softmax(jnp.where(allow[None], scores, NEG), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v)
    o = o * jax.nn.sigmoid(matmul(n, lw["g_proj"]["kernel"], precision))[:, :, None]
    return x + proj(o.reshape(T, H * dv), "o_proj")


def swiglu(n, w, precision, leaf=lambda x: x["kernel"]):
    return matmul(jax.nn.silu(matmul(n, leaf(w["gate_proj"]), precision))
                  * matmul(n, leaf(w["up_proj"]), precision), leaf(w["down_proj"]), precision)


def dense_ffn(h, lw, mc, precision):
    n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
    return h + swiglu(n, lw, precision)


def choose(s, b, mc):
    """The chosen experts [T, k] under group-limited selection, a plain loop
    over the groups: s [T, E] sigmoid scores, b [E] the selection-only bias."""
    c = s + b
    G, keep = int(mc.get("n_group") or 1), int(mc.get("topk_group") or 1)
    if G > 1:
        size = c.shape[-1] // G
        score = jnp.stack([jnp.sum(jnp.sort(c[:, i * size:(i + 1) * size], axis=-1)[:, -2:], axis=-1)
                           for i in range(G)], axis=-1)  # [T, G]
        # a group is kept iff fewer than ``keep`` groups beat it (ties to the lower index)
        beats = (score[:, None, :] > score[:, :, None]) | (
            (score[:, None, :] == score[:, :, None])
            & (jnp.arange(G)[None, None, :] < jnp.arange(G)[None, :, None]))
        kept = jnp.sum(beats, axis=-1) < keep  # [T, G]
        c = jnp.where(jnp.repeat(kept, size, axis=-1), c, -jnp.inf)
    return jax.lax.top_k(c, mc["experts_per_token"])[1]


def expert_ffn(h, lw, mc, precision):
    """The held experts' part of one expert layer (every token through every
    held expert, weighted by what the router gave that expert for it, 0 where
    it was not chosen) plus the shared expert, whole."""
    n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
    s = jax.nn.sigmoid(matmul(n, lw["router"]["kernel"], precision))
    chosen = choose(s, lw["e_score_correction_bias"].astype(F32), mc)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if mc.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * float(mc.get("routed_scaling_factor") or 1.0)
    ids = mc["first_held"] + jnp.arange(mc["experts_held"])

    def one(acc, xs):
        e, gate, up, down = xs
        we = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [T]
        y = swiglu(n, {"gate_proj": gate, "up_proj": up, "down_proj": down}, precision,
                   leaf=lambda x: x)
        return acc + we[:, None] * y, None

    ex = lw["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(n),
                        (ids, ex["gate_proj"], ex["up_proj"], ex["down_proj"]))
    if mc.get("shared_expert_intermediate_size") and not mc.get("no_shared_expert"):
        y = y + swiglu(n, lw["shared_expert"], precision)
    return h + y


MIXERS = {"kda": kda, "mla": mla}


def _static(mc):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in mc.items() if not isinstance(v, dict)))


@functools.partial(jax.jit, static_argnames=("mc_items", "precision"))
def _hidden(params, tokens, positions, valid, *, mc_items, precision):
    """Final-norm hidden states [T, D] of one sequence; each run of like layers
    under a scan, so that one layer's float32 weights and scores are alive at a time."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][tokens].astype(F32)
        for i, (kind, ffn, _) in enumerate(runs_of(mc)):
            def body(x, lw, kind=kind, ffn=ffn):
                h = MIXERS[kind](x, lw, positions, valid, mc, precision)
                out = (dense_ffn if ffn == "dense" else expert_ffn)(h, lw, mc, precision)
                return out, None

            x, _ = jax.lax.scan(body, x, params["layers"][f"run{i}"])
        return rms_norm(x, params["norm"]["scale"], mc["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(w_head, hidden, *, precision):
    with jax.default_matmul_precision("highest"):
        return matmul(hidden, w_head, precision)


BUCKET = 1536  # tokens: one compiled length for every context up to it


def sequence_logits(params, mc, tokens, rows, lora=None, lora_scale=0.0, valid_len=None,
                    precision="f32"):
    """Logits [len(rows), V] at the given positions of ONE sequence ``tokens``
    (a prompt followed by the tokens served for it): the full forward pass,
    positions 0..T-1. Tokens from ``valid_len`` on are padding, and the
    sequence is padded further to a multiple of ``BUCKET`` here (causal, so a
    tail of padding is inert): few compiled lengths. ``lora`` is ``{run<i>:
    {target: {a, b}}}`` of one adapter."""
    n_valid = len(tokens) if valid_len is None else int(valid_len)
    T = -(-len(tokens) // BUCKET) * BUCKET
    tok = jnp.asarray(list(tokens) + [0] * (T - len(tokens)), jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    valid = (pos < n_valid).astype(jnp.int32)
    hidden = _hidden(with_adapter(params, lora, lora_scale), tok, pos, valid,
                     mc_items=_static(mc), precision=precision)
    return _logits(params["lm_head"]["kernel"], hidden[jnp.asarray(rows, jnp.int32)],
                   precision=precision)
