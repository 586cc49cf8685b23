"""Plain reference of the Granite 4.0-H language model's forward pass
(model_type ``granitemoehybrid``: granite-4.0-h-micro), in straightforward
``jax.numpy`` and float32 at ``jax.default_matmul_precision("highest")``. No
cache, no recurrent state carried between calls, no chunk (SSD) form, no
kernel, no batching, one sequence at a time; it imports nothing of the program
under test. Written from the published ``config.json`` and the equations of the
families it names (``D`` hidden, ``H`` Mamba heads of ``P`` channels, ``N`` the
state size, ``G`` groups; ``m_e``, ``m_a``, ``m_r``, ``m_l`` the four multipliers):

  embedding     x = m_e * E[token]                                   (m_e 12)
  every layer   h = x + m_r Mixer(RMSNorm(x));  x' = h + m_r FFN(RMSNorm(h))
                                                          (m_r 0.22, no bias)
  Mamba-2 mixer (Dao and Gu, arXiv:2405.21060; ``mamba_*`` keys) [z | xBC | dt] =
                n W_in, widths H*P | H*P + 2 G N | H. Depthwise causal
                convolution over xBC, kernel 4, a weight [4] AND a bias per
                channel (mamba_conv_bias), then SiLU: u_t = silu(b + sum_i
                w[:, i] xBC_{t-3+i}), rows before the first are zero; split
                u = [x (H, P) | B (G, N) | C (G, N)], B and C shared by the H/G
                heads of a group. Per head dt_t = softplus(dt_t + dt_bias), no
                clamp (time_step_limit (0, inf)); A = -exp(A_log); state S [H,
                P, N] float32 from zero, TOKEN BY TOKEN:
                    S = exp(dt_t A) S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t
                then y <- RMSNorm_group(y * silu(z)) * w (the gate BEFORE the
                norm, the norm over each group's H*P/G channels), out = y W_out.
  attention     GQA, q heads of head_dim over num_kv_heads KV heads, no bias, NO
                rotation (position_embedding_type nope: order comes from the
                Mamba layers); score = m_a q.k (m_a 0.015625, not
                head_dim ** -0.5), causal, softmax in float32; y = concat(o) Wo.
  FFN           SwiGLU D -> intermediate_size -> D (HF's fused input_linear is
                [gate | up]; num_local_experts 0: no routed experts at all).
  logits        RMSNorm(x) E^T / m_l, E the tied embedding        (m_l 8)

Departures from the published model, each on purpose (the configuration file
lists them under ``assumed``): the recurrent state is float32 (HF keeps it in
the model's dtype); weights stay in the type they are served in (bf16) and are
upcast a layer at a time; masked scores get -1e30, not -inf; an adapter is
folded into the projections it sits on (``W + s A B``, float32) as its layer is
taken, and every sequence is padded to a multiple of ``BUCKET`` tokens, so that
base and adapters, short and long, every run of like layers whatever its
length, share one compiled program a mixer kind (``_take`` says why).

``precision="int8"`` is the CONTROL of the benchmark's correctness check, not a
reference: the same mathematics with every matmul operand rounded to 8-bit
integers (weights per output channel, activations per token, symmetric), the
nearest precision below the bf16 the configuration states. The recurrence on
``S`` stays float32 in the control too: it is no matmul operand.
"""

from __future__ import annotations

import functools

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


def runs_of(mc: dict) -> list:
    """[(mixer type, ffn type, count)] of consecutive like layers, in order."""
    out = []
    for t in mc["layer_types"]:
        if out and out[-1][0] == t:
            out[-1][2] += 1
        else:
            out.append([t, "dense", 1])
    return [tuple(r) for r in out]


def ssm_dims(mc: dict):
    """(H heads, P channels a head, N state, G groups, K convolution taps)."""
    return (mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_state"],
            int(mc.get("ssm_groups") or 1), int(mc.get("ssm_conv_kernel") or 4))


ADAPTABLE = ("q_proj", "k_proj", "v_proj", "o_proj", "in_proj")


@functools.partial(jax.jit, static_argnames=("lora_scale",))
def _take(run, lora, i, *, lora_scale):
    """Layer ``i`` of one run's stacked parameters, with the projections an
    adapter may sit on in float32 and the adapter folded in: ``W + lora_scale *
    A B`` (``h W + s (h A) B`` is ``h (W + s A B)``). A request to the base gets
    the same leaves upcast and nothing added, so ``_mix`` below is ONE compiled
    program a mixer kind and ``_feed_forward`` one for all, for base and
    adapters, for every run whatever its length: a float32 matmul at HIGHEST precision costs the TPU's compiler
    seconds, and the model has nine runs of like layers."""
    with jax.default_matmul_precision("highest"):
        lw = jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), run)
        for name in ADAPTABLE:
            if name not in lw:
                continue
            w = lw[name]["kernel"].astype(F32)
            ll = (lora or {}).get(name)
            if ll is not None:
                w = w + lora_scale * (ll["a"][i].astype(F32) @ ll["b"][i].astype(F32))
            lw[name] = {"kernel": w}
        return lw


def short_conv(x, w, b):
    """x [T, C] pre-convolution rows, w [C, K], b [C]: y_t = silu(b + sum_i w[:, i] x_{t-(K-1)+i})."""
    T, K = x.shape[0], w.shape[-1]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x.astype(F32)], axis=0)
    y = sum(ext[i:i + T] * w.astype(F32)[None, :, i] for i in range(K))
    return jax.nn.silu(y + b.astype(F32))


def ssm(n, lw, valid, mc, precision):
    """One Mamba-2 mixer on one sequence: n [T, D] the normed input. The
    recurrence runs token by token from a zero state; a padding row (at the
    tail) is causally behind every real one."""
    H, P, N, G, _ = ssm_dims(mc)
    T, inner = n.shape[0], H * P
    zxbcdt = matmul(n, lw["in_proj"]["kernel"], precision)
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:-H], zxbcdt[:, -H:]
    u = short_conv(xbc, lw["conv"]["kernel"], lw["conv"]["bias"])
    x = u[:, :inner].reshape(T, H, P)
    B = jnp.repeat(u[:, inner:inner + G * N].reshape(T, G, N), H // G, axis=1)  # [T, H, N]
    C = jnp.repeat(u[:, inner + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))  # [T, H]
    A = -jnp.exp(lw["A_log"].astype(F32))
    D = lw["D"].astype(F32)

    def step(S, xs):
        x_t, B_t, C_t, dt_t = xs
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, B, C, dt))
    y = y.reshape(T, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(T, G, inner // G), lw["ssm_norm"]["scale"].reshape(G, inner // G),
                 mc["rms_norm_eps"])
    return matmul(y.reshape(T, inner), lw["o_proj"]["kernel"], precision)


def attention(n, lw, valid, mc, precision):
    """One attention mixer on one sequence, no positions: n [T, D]."""
    H, KV, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    T = n.shape[0]
    # q, k and v in ONE product (each output column is its own dot product, in
    # the int8 control its own scale): a product fewer for the chip's compiler
    qkv = matmul(n, jnp.concatenate([lw[name]["kernel"].astype(F32) for name in
                                     ("q_proj", "k_proj", "v_proj")], axis=1), precision)
    q = qkv[:, :H * d].reshape(T, KV, H // KV, d)
    k = qkv[:, H * d:(H + KV) * d].reshape(T, KV, d)
    v = qkv[:, (H + KV) * d:].reshape(T, KV, d)
    scale = mc.get("attention_multiplier")
    scale = d ** -0.5 if scale is None else float(scale)
    idx = jnp.arange(T)
    allow = (idx[:, None] >= idx[None, :]) & valid[None, :].astype(bool)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * scale
    p = jax.nn.softmax(jnp.where(allow[None, None], scores, NEG), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v)
    return matmul(o.reshape(T, H * d), lw["o_proj"]["kernel"], precision)


def swiglu(n, lw, precision):
    """[gate | up] in one product, as HF's fused ``input_linear`` holds them."""
    F = lw["gate_proj"]["kernel"].shape[-1]
    gu = matmul(n, jnp.concatenate([lw["gate_proj"]["kernel"], lw["up_proj"]["kernel"]], axis=1),
                precision)
    return matmul(jax.nn.silu(gu[:, :F]) * gu[:, F:], lw["down_proj"]["kernel"], precision)


MIXERS = {"ssm": ssm, "global": attention}
FFN_LEAVES = ("post_attention_layernorm", "gate_proj", "up_proj", "down_proj")


def _static(mc):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in mc.items() if not isinstance(v, dict)))


@functools.partial(jax.jit, static_argnames=("kind", "mc_items", "precision"))
def _mix(x, lw, valid, *, kind, mc_items, precision):
    """A layer's first half on one sequence: x [T, D] float32."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        n = rms_norm(x, lw["input_layernorm"]["scale"], mc["rms_norm_eps"])
        return x + float(mc.get("residual_multiplier", 1.0)) * MIXERS[kind](n, lw, valid, mc, precision)


@functools.partial(jax.jit, static_argnames=("mc_items", "precision"))
def _feed_forward(h, lw, *, mc_items, precision):
    """A layer's second half, the same program after either mixer."""
    mc = dict(mc_items)
    with jax.default_matmul_precision("highest"):
        n = rms_norm(h, lw["post_attention_layernorm"]["scale"], mc["rms_norm_eps"])
        return h + float(mc.get("residual_multiplier", 1.0)) * swiglu(n, lw, precision)


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(embedding, tokens, *, multiplier):
    return embedding[tokens].astype(F32) * multiplier


@functools.partial(jax.jit, static_argnames=("precision", "divisor", "eps"))
def _logits(embedding, scale, x, rows, *, precision, divisor, eps):
    with jax.default_matmul_precision("highest"):
        return matmul(rms_norm(x, scale, eps)[rows], embedding.T, precision) / divisor


BUCKET = 1024  # tokens: one compiled length for every context up to it


def sequence_logits(params, mc, tokens, rows, lora=None, lora_scale=0.0, valid_len=None,
                    precision="f32"):
    """Logits [len(rows), V] at the given positions of ONE sequence ``tokens``
    (a prompt followed by the tokens served for it): the full forward pass.
    Tokens from ``valid_len`` on are padding, and the sequence is padded
    further to a multiple of ``BUCKET`` here (causal, so a tail of padding is
    inert): few compiled lengths. ``lora`` is ``{run<i>: {target: {a, b}}}`` of
    one adapter. The head is the tied embedding."""
    n_valid = len(tokens) if valid_len is None else int(valid_len)
    T = -(-len(tokens) // BUCKET) * BUCKET
    tok = jnp.asarray(list(tokens) + [0] * (T - len(tokens)), jnp.int32)
    valid = (jnp.arange(T, dtype=jnp.int32) < n_valid).astype(jnp.int32)
    embedding, items = params["embed_tokens"]["embedding"], _static(mc)
    x = _embed(embedding, tok, multiplier=float(mc.get("embedding_multiplier", 1.0)))
    for r, (kind, _, n) in enumerate(runs_of(mc)):
        run = params["layers"][f"run{r}"]
        for i in range(n):  # a layer at a time: one layer's float32 weights are alive
            lw = _take(run, (lora or {}).get(f"run{r}"), jnp.int32(i), lora_scale=float(lora_scale))
            ffn = {name: lw.pop(name) for name in FFN_LEAVES}
            x = _mix(x, lw, valid, kind=kind, mc_items=items, precision=precision)
            x = _feed_forward(x, ffn, mc_items=items, precision=precision)
    return _logits(embedding, params["norm"]["scale"], x, jnp.asarray(rows, jnp.int32),
                   precision=precision, divisor=float(mc.get("logits_scaling", 1.0)),
                   eps=mc["rms_norm_eps"])
