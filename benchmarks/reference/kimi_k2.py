"""Plain reference of the Kimi-K2.5 language model's forward pass (model_type
``kimi_k2``, which reuses DeepSeek-V3's modelling code), in straightforward
``jax.numpy`` and float32 at ``jax.default_matmul_precision("highest")``. Heads
EXPANDED through ``kv_b_proj`` (no absorption), no cache, no gather through a
table, no kernel, no batching, one sequence at a time; it imports nothing of the
program under test, and its YaRN is written from the published formulas, not
from ``ops/rope.py``. Written from the published ``config.json`` and the
equations of DeepSeek-V3 (arXiv:2412.19437: latent attention with a low-rank
query, sigmoid routing with a selection-only bias) and of YaRN
(arXiv:2309.00071, as DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` has it).
``D`` hidden, ``H`` heads, ``n = RMSNorm(x)``:

  every layer   h = x + Mixer(n);   x' = h + FFN(RMSNorm(h))            (no bias)
  query         c_q = RMSNorm(n W_qa) (q_lora_rank);  q = c_q W_qb -> [H, nope | rope];
                q's rope lanes rotated, INTERLEAVED pairs (2i, 2i+1).
  latent        [c_kv | k_r] = n W_kva;  c = RMSNorm(c_kv);  k_r rotated alike,
                one for all heads;  [k_nope_h | v_h] = c W_kvb[h].
  YaRN          (``rope_scaling_type`` "yarn", the program's field names: ``yarn_of``)
                d rope lanes, base theta, trained length L0, factor s:
                corr(n) = d ln(L0 / (2 pi n)) / (2 ln theta);
                low = max(floor(corr(beta_fast)), 0), high = min(ceil(corr(beta_slow)), d - 1);
                ramp_i = clip((i - low) / (high - low), 0, 1), i = 0 .. d/2 - 1;
                f_i = theta^(-2i/d) ((1 - ramp_i) + ramp_i / s);   angle = position f_i;
                m(s, a) = 0.1 a ln s + 1;  cos and sin times m(s, mscale) / m(s, mscale_all_dim);
                the scores times m(s, mscale_all_dim)^2 where mscale_all_dim is not 0.
  attention     EVERY causally visible key: p = softmax_s((q_nope_h . k_nope_{h,s} +
                q_rope_h . k_r_s) m^2 / sqrt(nope + rope)), float32;  o_h = sum p v_{h,s}
                (v 128 wide beside q/k 192);  y = concat(o) W_o.  No gate, no indexer.
  FFN dense     SwiGLU D -> intermediate_size -> D.
  FFN experts   s = sigmoid(n W_r) in float32; the experts_per_token largest of
                s + b chosen (b selects only; n_group 1); weights s of the chosen
                over their sum, times routed_scaling_factor; plus ONE shared
                expert (SwiGLU, every token, weight 1).
  final RMSNorm, untied output head.

The chip's share: ``experts_held`` of the ``experts_total`` experts are held,
from ``first_held`` on. The router scores all of them; pairs whose expert is
absent add nothing, here as in the program; the shared expert is whole
(``no_shared_expert`` leaves it out: the test that adds the shares up counts it
once). With every expert held this is the published layer.

Departures from the published model, each on purpose (the configuration file
lists them under ``assumed`` / ``left_out``): no vision tower (token ids only).
Weights stay in the type they are served in (bf16) and are upcast a layer (an
expert) at a time; masked scores get -1e30, not -inf; an adapter is folded into
the projections it sits on (``W + s A B``, float32) as its layer is taken; every
sequence is padded to ONE length, ``BUCKET`` (12,288, or the configuration's
``max_seq_len`` where that is shorter; longer sequences to multiples of it), and
attention runs over blocks of ``Q_BLOCK`` queries against every key, so that base
and adapters, every layer and every length share one compiled mixer, two
feed-forwards, an embedding and a head (``_take`` says why).

``precision="int8"`` is the CONTROL of the benchmark's correctness check, not a
reference: the same mathematics with every matmul operand rounded to 8-bit
integers (weights per output channel, activations per token, symmetric), the
nearest precision below the bf16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
BUCKET = 12288  # tokens: one compiled length for every context up to it
Q_BLOCK = 256  # queries a block of attention


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


def yarn_of(mc: dict):
    """(s, L0, beta_fast, beta_slow, mscale, mscale_all_dim) of a configuration
    that states YaRN (the program's field names), else None: plain RoPE."""
    if mc.get("rope_scaling_type") != "yarn":
        return None
    return (float(mc["rope_scaling_factor"]), float(mc["rope_original_max_len"]),
            float(mc.get("rope_beta_fast", 32.0)), float(mc.get("rope_beta_slow", 1.0)),
            float(mc.get("rope_mscale", 1.0)), float(mc.get("rope_mscale_all_dim", 0.0)))


def yarn_frequencies(d: int, theta: float, yarn):
    """([d/2] angles a position, the factor on cos and sin): the formulas of
    the module's docstring; ``yarn`` None is plain RoPE."""
    i = jnp.arange(0, d // 2, dtype=F32)
    f = theta ** (-2.0 * i / d)
    if yarn is None:
        return f, 1.0
    s, L0, beta_fast, beta_slow, mscale, mscale_all_dim = yarn

    def corr(turns):
        return d * math.log(L0 / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * ((1.0 - ramp) + ramp / s), temperature(s, mscale) / temperature(s, mscale_all_dim)


def temperature(s: float, a: float) -> float:
    return 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0


def rope_interleaved(x, positions, theta, yarn=None):
    """x [T, H, r]: rotates each pair of lanes (2i, 2i + 1) by position * f_i."""
    f, m = yarn_frequencies(x.shape[-1], theta, yarn)
    ang = positions.astype(F32)[:, None] * f[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def score_scale(mc: dict) -> float:
    yarn = yarn_of(mc)
    scale = 1.0 / math.sqrt(mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"])
    if yarn is not None and yarn[5]:
        scale *= temperature(yarn[0], yarn[5]) ** 2
    return scale


def runs_of(mc: dict) -> list:
    """[(mixer type, ffn type, count)] of consecutive like layers, in order."""
    out = []
    for t, f in zip(mc["layer_types"], mc["ffn_types"]):
        if out and out[-1][0] == t and out[-1][1] == f:
            out[-1][2] += 1
        else:
            out.append([t, f, 1])
    return [tuple(r) for r in out]


ADAPTABLE = ("q_b_proj", "o_proj")


@functools.partial(jax.jit, static_argnames=("lora_scale",))
def _take(run, lora, i, *, lora_scale):
    """Layer ``i`` of one run's stacked parameters (its experts too), with the
    projections an adapter may sit on in float32 and the adapter folded in: ``W
    + lora_scale * A B`` (``h W + s (h A) B`` is ``h (W + s A B)``). A request to
    the base gets the same leaves upcast and nothing added, so ``_mix`` below is
    ONE compiled program and each feed-forward one, for base and adapters, for
    every layer: a float32 matmul at HIGHEST precision costs the TPU's compiler
    seconds, and the benchmark's whole run has minutes."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), run)
        for name in ADAPTABLE:
            w = lw[name]["kernel"].astype(F32)
            ll = (lora or {}).get(name)
            if ll is not None:
                w = w + lora_scale * (ll["a"][i].astype(F32) @ ll["b"][i].astype(F32))
            lw[name] = {"kernel": w}
        return lw


def mla(n, lw, positions, valid, mc, precision):
    """One mixer on one sequence, over expanded heads: n [T, D] the normed
    input. Returns y [T, D] before the residual."""
    H, rank = mc["num_heads"], mc["kv_lora_rank"]
    nope, rot = mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    dv = mc.get("v_head_dim") or mc["head_dim"]
    theta, eps, yarn = mc["rope_theta"], mc["rms_norm_eps"], yarn_of(mc)
    T = n.shape[0]
    c_q = rms_norm(matmul(n, lw["q_a_proj"]["kernel"], precision), lw["q_a_layernorm"]["scale"], eps)
    q = matmul(c_q, lw["q_b_proj"]["kernel"], precision).reshape(T, H, nope + rot)
    q_nope, q_rope = q[..., :nope], rope_interleaved(q[..., nope:], positions, theta, yarn)
    row = matmul(n, lw["kv_a_proj"]["kernel"], precision)
    c = rms_norm(row[:, :rank], lw["kv_a_layernorm"]["scale"], eps)
    k_rope = rope_interleaved(row[:, None, rank:], positions, theta, yarn)[:, 0]  # [T, rot]
    kv = matmul(c, lw["kv_b_proj"]["kernel"], precision).reshape(T, H, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    keys = jnp.arange(T)
    scale = score_scale(mc)

    def block(start):
        """Q_BLOCK queries from ``start`` on, against every key."""
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, Q_BLOCK, 0)  # noqa: E731
        rows = start + jnp.arange(Q_BLOCK)
        allow = (rows[:, None] >= keys[None, :]) & valid[None, :].astype(bool)
        scores = (jnp.einsum("thd,shd->hts", cut(q_nope), k_nope)
                  + jnp.einsum("thd,sd->hts", cut(q_rope), k_rope)) * scale
        p = jax.nn.softmax(jnp.where(allow[None], scores, NEG), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))
    return matmul(o.reshape(T, H * dv), lw["o_proj"]["kernel"], precision)


def swiglu(n, w, precision, leaf=lambda x: x["kernel"]):
    return matmul(jax.nn.silu(matmul(n, leaf(w["gate_proj"]), precision))
                  * matmul(n, leaf(w["up_proj"]), precision), leaf(w["down_proj"]), precision)


def choose(s, b, mc):
    """The chosen experts [T, k]: the largest ``s + b`` (no groups: n_group 1)."""
    return jax.lax.top_k(s + b, mc["experts_per_token"])[1]


def dense_ffn(h, lw, mc, precision):
    n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
    return h + swiglu(n, lw, precision)


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


MIXER_LEAVES = ("input_layernorm", "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj",
                "kv_a_layernorm", "kv_b_proj", "o_proj")


def _static(mc):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in mc.items() if not isinstance(v, dict)))


@functools.partial(jax.jit, static_argnames=("mc_items", "precision"))
def _mix(x, lw, positions, valid, *, mc_items, precision):
    """A layer's first half on one sequence: x [T, D] float32 -> h."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        n = rms_norm(x, lw["input_layernorm"]["scale"], mc["rms_norm_eps"])
        return x + mla(n, lw, positions, valid, mc, precision)


@functools.partial(jax.jit, static_argnames=("ffn", "mc_items", "precision"))
def _feed_forward(h, lw, *, ffn, mc_items, precision):
    """A layer's second half: the dense SwiGLU or this chip's part of the experts."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        return (dense_ffn if ffn == "dense" else expert_ffn)(h, lw, mc, precision)


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("precision", "eps"))
def _logits(w_head, scale, x, rows, *, precision, eps):
    with jax.default_matmul_precision("highest"):
        return matmul(rms_norm(x, scale, eps)[rows], w_head, precision)


def _padded(mc, tokens, valid_len):
    bucket = min(BUCKET, int(mc["max_seq_len"]))
    bucket = -(-bucket // Q_BLOCK) * Q_BLOCK
    T = -(-len(tokens) // bucket) * bucket
    tok = jnp.asarray(list(tokens) + [0] * (T - len(tokens)), jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    n_valid = len(tokens) if valid_len is None else int(valid_len)
    return tok, pos, (pos < n_valid).astype(jnp.int32)


def _layers(params, mc, tok, pos, valid, lora, lora_scale, precision):
    """Yields x after each layer: one layer's float32 weights are alive at a time."""
    items = _static(mc)
    x = _embed(params["embed_tokens"]["embedding"], tok)
    for r, (_, ffn, count) in enumerate(runs_of(mc)):
        run = params["layers"][f"run{r}"]
        for i in range(count):
            lw = _take(run, (lora or {}).get(f"run{r}"), jnp.int32(i), lora_scale=float(lora_scale))
            mixer = {name: lw.pop(name) for name in MIXER_LEAVES}
            x = _mix(x, mixer, pos, valid, mc_items=items, precision=precision)
            x = _feed_forward(x, lw, ffn=ffn, mc_items=items, precision=precision)
            yield x


def sequence_logits(params, mc, tokens, rows, lora=None, lora_scale=0.0, valid_len=None,
                    precision="f32"):
    """Logits [len(rows), V] at the given positions of ONE sequence ``tokens``
    (a prompt followed by the tokens served for it): the full forward pass,
    positions 0..T-1. Tokens from ``valid_len`` on are padding, and the
    sequence is padded further to the one length here (causal, so a tail of
    padding is inert). ``lora`` is ``{run<i>: {target: {a, b}}}`` of one adapter."""
    tok, pos, valid = _padded(mc, tokens, valid_len)
    for x in _layers(params, mc, tok, pos, valid, lora, lora_scale, precision):
        pass
    return _logits(params["lm_head"]["kernel"], params["norm"]["scale"], x,
                   jnp.asarray(rows, jnp.int32), precision=precision, eps=mc["rms_norm_eps"])
