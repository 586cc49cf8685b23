"""Weights and adapters of a ``bailing_hybrid`` (Ling-3.0) configuration, drawn
on the device from the seed in the type they are used in: one jitted call per
run of like layers (a layer at a time inside it, an expert at a time inside an
expert layer, so that no float32 copy of a stacked leaf is ever alive). They
are the benchmark's, not the program's: the program and the plain reference
(``reference/ling_v3.py``) are handed the same arrays.

Layout is the program's parameter tree for a model of several layer kinds
(``datatunerx_tpu/models/hybrid.py`` docstring): ``layers.run<i>`` per run of
like layers, stacked ``[n, ...]``; adapters mirror it. What is drawn, and how,
is the configuration file's ``assumed``:

- projections, embeddings, the head, experts: normal 0.02; norm scales
  (``*_layernorm``, ``kv_a_layernorm``, ``o_norm``) 1 + normal 0.02;
- ``e_score_correction_bias`` normal 0.003 (``weights_mimo_v2.BIAS_STD`` says
  why: a larger one alone decides the choice of experts);
- the short convolution ``conv.kernel [C, 4]`` normal ``4 ** -0.5`` (a
  convolution that keeps the scale of what passes: at 0.02 the values a KDA
  layer writes into its state would be a few hundredths of its keys);
- the decay gate ``g = -5 sigmoid(exp(A_log) (n Wf + dt_bias))``: ``A_log [H]
  = log(uniform(1, 16))``; per head, with ``a = exp(A_log[h])``, ``Wf[:, h, :]``
  normal ``1 / (a sqrt(D))`` and ``dt_bias[h, :] = (-2.5 + normal 0.5) / a``, so
  that the sigmoid's argument is about normal(-2.5, 1.1) in every head
  whatever its ``a``: ``exp(g)`` then has its median near 0.68 and its middle
  four fifths between 0.3 and 0.9 over channels and tokens (a memory of one to
  seven tokens' half-life; ``tests`` hold the median between 0.5 and 0.95).
  Normal 0.02 for ``Wf`` with ``a`` up to 16 saturates the sigmoid both ways:
  half the channels die in a token and the rest never decay.
  ``A_log`` and ``dt_bias`` are float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.ling_v3 import ADAPTABLE, runs_of  # how the reference groups like layers

STD = 0.02
BIAS_STD = 0.003
GATE_MEAN, GATE_STD = -2.5, 0.5


def _key(seed: int, tag: int):
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 31), tag)


def mixer_shapes(mc: dict, kind: str) -> dict:
    """{projection: (in, out)} of one mixer kind; the adaptable ones are q/k/v/o."""
    D, H, d = mc["hidden_size"], mc["num_heads"], mc["head_dim"]
    dv = mc.get("v_head_dim") or d
    if kind == "mla":
        rank, nope, rot = mc["kv_lora_rank"], mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
        return {"q_proj": (D, H * (nope + rot)), "kv_a_proj": (D, rank + rot),
                "kv_b_proj": (rank, H * (nope + dv)), "g_proj": (D, H), "o_proj": (H * dv, D)}
    assert kind == "kda", kind
    return {"q_proj": (D, H * d), "k_proj": (D, H * d), "v_proj": (D, H * dv),
            "b_proj": (D, H), "g_proj": (D, H * dv), "o_proj": (H * dv, D)}



def _sliced_normal(k, shape, std, mean, dtype, most=1 << 19):
    """``mean + std * normal`` of ``shape``, drawn in slices of whole rows of at
    most ``most`` elements under a loop where it is larger: the TPU's compiler
    takes a second per ten million elements of ONE draw, half a second for a
    loop of small ones."""
    rows, last = math.prod(shape[:-1]), shape[-1]
    r = max(1, min(rows, most // last))
    while rows % r:
        r -= 1

    def one(kk):
        return (mean + jax.random.normal(kk, (r, last), jnp.float32) * std).astype(dtype)

    if r == rows:
        return one(k).reshape(shape)
    return jax.lax.map(one, jax.random.split(k, rows // r)).reshape(shape)


@functools.partial(jax.jit, static_argnames=("mc_items", "kind", "ffn", "n", "dtype"))
def _draw_run(key, *, mc_items, kind, ffn, n, dtype):
    mc = dict(mc_items)
    D, H, d = mc["hidden_size"], mc["num_heads"], mc["head_dim"]
    dv = mc.get("v_head_dim") or d

    def normal(k, shape, std=STD, mean=0.0, dtype=dtype):
        return _sliced_normal(k, shape, std, mean, dtype)

    def one_layer(k):
        ks = iter(jax.random.split(k, 24))
        out = {name: {"kernel": normal(next(ks), shape)}
               for name, shape in sorted(mixer_shapes(mc, kind).items())}
        for name in ("input_layernorm", "post_attention_layernorm"):
            out[name] = {"scale": normal(next(ks), (D,), mean=1.0)}
        if kind == "mla":
            out["kv_a_layernorm"] = {"scale": normal(next(ks), (mc["kv_lora_rank"],), mean=1.0)}
        else:
            K = int(mc.get("kda_conv_kernel", 4))
            out["conv"] = {"kernel": normal(next(ks), (H * (2 * d + dv), K), std=K ** -0.5)}
            out["o_norm"] = {"scale": normal(next(ks), (dv,), mean=1.0)}
            a = jax.random.uniform(next(ks), (H,), jnp.float32, 1.0, 16.0)
            out["A_log"] = jnp.log(a)
            wf = normal(next(ks), (D, H, d), std=1.0, dtype=jnp.float32) / (a[None, :, None] * D ** 0.5)
            out["f_proj"] = {"kernel": wf.reshape(D, H * d).astype(dtype)}
            out["dt_bias"] = (normal(next(ks), (H, d), std=GATE_STD, mean=GATE_MEAN,
                                     dtype=jnp.float32) / a[:, None]).reshape(H * d)
        if ffn == "dense":
            F = mc["intermediate_size"]
            for name, shape in (("down_proj", (F, D)), ("gate_proj", (D, F)), ("up_proj", (D, F))):
                out[name] = {"kernel": normal(next(ks), shape)}
        else:
            E, Eh, F = mc["experts_total"], mc["experts_held"], mc["expert_intermediate_size"]
            shapes = (("down_proj", (F, D)), ("gate_proj", (D, F)), ("up_proj", (D, F)))
            out["router"] = {"kernel": normal(next(ks), (D, E))}
            out["e_score_correction_bias"] = normal(next(ks), (E,), std=BIAS_STD)
            out["experts"] = {
                name: jax.lax.map(lambda kk, shape=shape: normal(kk, shape),
                                  jax.random.split(next(ks), Eh))
                for name, shape in shapes}
            Fs = int(mc.get("shared_expert_intermediate_size") or 0)
            if Fs:
                out["shared_expert"] = {
                    name: {"kernel": normal(next(ks), (Fs if i == 0 else D, D if i == 0 else Fs))}
                    for i, (name, _) in enumerate(shapes)}
        return out

    return jax.lax.map(one_layer, jax.random.split(key, n))


def _items(mc: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in mc.items() if not isinstance(v, dict)))


def draw_params(mc: dict, seed: int, dtype=jnp.bfloat16):
    D, V = mc["hidden_size"], mc["vocab_size"]
    layers = {}
    for i, (kind, ffn, n) in enumerate(runs_of(mc)):
        layers[f"run{i}"] = _draw_run(_key(seed, 0x100 + i), mc_items=_items(mc), kind=kind,
                                      ffn=ffn, n=n, dtype=dtype)
    draw = jax.jit(lambda k, shape, mean: _sliced_normal(k, shape, STD, mean, dtype),
                   static_argnums=(1, 2))
    return {"embed_tokens": {"embedding": draw(_key(seed, 0x11), (V, D), 0.0)},
            "layers": layers,
            "norm": {"scale": draw(_key(seed, 0x12), (D,), 1.0)},
            "lm_head": {"kernel": draw(_key(seed, 0x13), (D, V), 0.0)}}


@functools.partial(jax.jit, static_argnames=("runs", "n", "rank", "b_std"))
def _draw_lora(key, *, runs, n, rank, b_std):
    """One program for every run: ``runs`` is ((layers, ((target, d_in, d_out), ...)), ...)."""
    out = {}
    for i, (L, dims) in enumerate(runs):
        out[f"run{i}"] = {}
        for j, (name, d_in, d_out) in enumerate(dims):
            ka, kb = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, i), j))
            bound = 1.0 / (d_in ** 0.5)
            out[f"run{i}"][name] = {
                "a": jax.random.uniform(ka, (n, L, d_in, rank), jnp.float32, -bound, bound),
                "b": jax.random.normal(kb, (n, L, rank, d_out), jnp.float32) * b_std}
    return out


def draw_lora(mc: dict, seed: int, *, count: int, rank: int, targets, b_std: float):
    """``count`` adapters on the mixer projections named in ``targets``:
    ``{run<i>: {target: {a [count, n, d_in, r], b [count, n, r, d_out]}}}``
    float32, each run with its own kind's geometry (``q_proj`` is ``H * 192``
    wide in an MLA run and ``H * 128`` in a KDA run; an MLA run has no ``k_proj``
    or ``v_proj`` and takes none). A as PEFT draws it (uniform +-1/sqrt(d_in)),
    B normal(b_std)."""
    runs = []
    for kind, _, n in runs_of(mc):
        shapes = mixer_shapes(mc, kind)
        runs.append((n, tuple((t, shapes[t][0], shapes[t][1]) for t in sorted(set(targets))
                              if t in ADAPTABLE and t in shapes)))
    return _draw_lora(_key(seed, 0x200), runs=tuple(runs), n=count, rank=rank, b_std=b_std)
