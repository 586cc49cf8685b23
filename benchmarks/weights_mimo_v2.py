"""Weights and adapters of a ``mimo_v2`` configuration, drawn on the device from
the seed in the type they are used in: one jitted call per run of like layers
(a layer at a time inside it, an expert at a time inside an expert layer, so
that no float32 copy of a stacked leaf is ever alive). They are the
benchmark's, not the program's: the program and the plain reference
(``reference/mimo_v2.py``) are handed the same arrays.

Layout is the program's parameter tree for a model of several layer kinds
(``datatunerx_tpu/models/hybrid.py`` docstring): ``layers.run<i>`` per run of
like layers, stacked ``[n, ...]``, HF leaf names; adapters mirror it. What is
drawn, and how, is the configuration file's ``assumed``: weights normal 0.02,
norm scales 1 + normal 0.02, ``e_score_correction_bias`` normal 0.003
(``BIAS_STD`` below says why), ``attention_sink_bias`` normal 1.0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.mimo_v2 import runs_of  # how the reference groups like layers

STD = 0.02
# The correction bias is what the published model learned in order to BALANCE
# its experts' load. At these weights the router's logits have a spread of 1.28
# and the chosen experts' sigmoid scores lie within a few hundredths of 1: a
# drawn bias of spread 0.1 (tried first, PR 26) then decides the choice alone,
# the same 8 experts win for nearly every token, and a chip's share of the load
# is anything from 0.5 to 1.4 of fair by the seed (`serve_tok_s` spread 5.2 %
# over six seeds). At 0.003 the bias still breaks near-ties in the choice, and
# every chip's share is within 2 % of fair, as in the deployment.
BIAS_STD = 0.003


def _key(seed: int, tag: int):
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 31), tag)


def attn_shapes(mc: dict, kind: str) -> dict:
    D, H = mc["hidden_size"], mc["num_heads"]
    KV = mc["window_num_kv_heads"] if kind == "window" else mc["num_kv_heads"]
    return {"q_proj": (D, H * mc["head_dim"]), "k_proj": (D, KV * mc["head_dim"]),
            "v_proj": (D, KV * mc["v_head_dim"]), "o_proj": (H * mc["v_head_dim"], D)}


@functools.partial(jax.jit, static_argnames=("mc_items", "kind", "ffn", "n", "dtype"))
def _draw_run(key, *, mc_items, kind, ffn, n, dtype):
    mc = dict(mc_items)
    D = mc["hidden_size"]

    def normal(k, shape, std=STD, mean=0.0):
        return (mean + jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def one_layer(k):
        ks = iter(jax.random.split(k, 16))
        out = {name: {"kernel": normal(next(ks), shape)}
               for name, shape in sorted(attn_shapes(mc, kind).items())}
        for name in ("input_layernorm", "post_attention_layernorm"):
            out[name] = {"scale": normal(next(ks), (D,), mean=1.0)}
        if kind == "window" and mc.get("window_sink"):
            out["attention_sink_bias"] = normal(next(ks), (mc["num_heads"],), std=1.0)
        if ffn == "dense":
            F = mc["intermediate_size"]
            for name, shape in (("down_proj", (F, D)), ("gate_proj", (D, F)), ("up_proj", (D, F))):
                out[name] = {"kernel": normal(next(ks), shape)}
        else:
            E, Eh, F = mc["experts_total"], mc["experts_held"], mc["expert_intermediate_size"]
            out["router"] = {"kernel": normal(next(ks), (D, E))}
            out["e_score_correction_bias"] = normal(next(ks), (E,), std=BIAS_STD)
            out["experts"] = {
                name: jax.lax.map(lambda kk, shape=shape: normal(kk, shape),
                                  jax.random.split(next(ks), Eh))
                for name, shape in (("down_proj", (F, D)), ("gate_proj", (D, F)), ("up_proj", (D, F)))}
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
    draw = jax.jit(lambda k, shape, mean: (mean + jax.random.normal(k, shape, jnp.float32) * STD
                                           ).astype(dtype), static_argnums=(1, 2))
    return {"embed_tokens": {"embedding": draw(_key(seed, 0x11), (V, D), 0.0)},
            "layers": layers,
            "norm": {"scale": draw(_key(seed, 0x12), (D,), 1.0)},
            "lm_head": {"kernel": draw(_key(seed, 0x13), (D, V), 0.0)}}


@functools.partial(jax.jit, static_argnames=("dims", "L", "n", "rank", "b_std"))
def _draw_lora(key, *, dims, L, n, rank, b_std):
    out = {}
    for i, (name, d_in, d_out) in enumerate(dims):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        bound = 1.0 / (d_in ** 0.5)
        out[name] = {"a": jax.random.uniform(ka, (n, L, d_in, rank), jnp.float32, -bound, bound),
                     "b": jax.random.normal(kb, (n, L, rank, d_out), jnp.float32) * b_std}
    return out


def draw_lora(mc: dict, seed: int, *, count: int, rank: int, targets, b_std: float):
    """``count`` adapters on the attention projections named in ``targets``:
    ``{run<i>: {target: {a [count, n, d_in, r], b [count, n, r, d_out]}}}``
    float32, each run with its own kind's geometry (``v_proj`` is as wide as the
    kind's KV heads). A as PEFT draws it (uniform +-1/sqrt(d_in)), B normal(b_std)."""
    out = {}
    for i, (kind, _, n) in enumerate(runs_of(mc)):
        shapes = attn_shapes(mc, kind)
        dims = tuple((t, shapes[t][0], shapes[t][1]) for t in sorted(set(targets)))
        out[f"run{i}"] = _draw_lora(_key(seed, 0x200 + i), dims=dims, L=n, n=count,
                                    rank=rank, b_std=b_std)
    return out
