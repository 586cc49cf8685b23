"""Weights, adapters and trainable LoRA drawn on the device from the seed, each
in ONE jitted call and in the type it is used in. They are the benchmark's, not
the program's: the program and the plain reference are handed the same arrays.

Layout is the program's parameter tree (``models/llama.py`` docstring): stacked
``[L, ...]`` leaves, HF leaf names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def _key(seed: int, tag: int):
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 31), tag)


def param_shapes(mc: dict) -> dict:
    D, F, V = mc["hidden_size"], mc["intermediate_size"], mc["vocab_size"]
    hd = mc.get("head_dim") or D // mc["num_heads"]
    q, kv = mc["num_heads"] * hd, mc["num_kv_heads"] * hd
    layer = {
        "q_proj": (D, q), "k_proj": (D, kv), "v_proj": (D, kv), "o_proj": (q, D),
        "gate_proj": (D, F), "up_proj": (D, F), "down_proj": (F, D),
    }
    return {"D": D, "V": V, "layer": layer, "bias": {"q_proj": q, "k_proj": kv, "v_proj": kv}}


@functools.partial(jax.jit, static_argnames=("mc_items", "dtype"))
def _draw_params(key, *, mc_items, dtype):
    mc = dict(mc_items)
    sh = param_shapes(mc)
    L = mc["num_layers"]

    def one_layer(k):
        ks = jax.random.split(k, 12)
        out = {}
        for i, (name, shape) in enumerate(sorted(sh["layer"].items())):
            out[name] = {"kernel": (jax.random.normal(ks[i], shape, jnp.float32) * STD).astype(dtype)}
        if mc.get("attention_bias"):
            for i, (name, n) in enumerate(sorted(sh["bias"].items())):
                out[name]["bias"] = (jax.random.normal(ks[7 + i], (n,), jnp.float32) * STD).astype(dtype)
        for i, name in enumerate(("input_layernorm", "post_attention_layernorm")):
            out[name] = {"scale": (1.0 + jax.random.normal(ks[10 + i], (sh["D"],), jnp.float32) * STD).astype(dtype)}
        return out

    k_layers, k_emb, k_head, k_norm = jax.random.split(key, 4)
    # a layer at a time: the f32 draw of one stacked leaf would be gigabytes
    layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
    params = {
        "embed_tokens": {"embedding": (jax.random.normal(k_emb, (sh["V"], sh["D"]), jnp.float32) * STD).astype(dtype)},
        "layers": layers,
        "norm": {"scale": (1.0 + jax.random.normal(k_norm, (sh["D"],), jnp.float32) * STD).astype(dtype)},
    }
    if not mc.get("tie_word_embeddings"):
        params["lm_head"] = {"kernel": (jax.random.normal(k_head, (sh["D"], sh["V"]), jnp.float32) * STD).astype(dtype)}
    return params


def draw_params(mc: dict, seed: int, dtype=jnp.bfloat16):
    items = tuple(sorted((k, v) for k, v in mc.items() if not isinstance(v, (dict, list))))
    return _draw_params(_key(seed, 0x11), mc_items=items, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("dims", "L", "n", "rank", "b_std"))
def _draw_lora(key, *, dims, L, n, rank, b_std):
    out = {}
    for i, (name, d_in, d_out) in enumerate(dims):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        bound = 1.0 / (d_in ** 0.5)
        a = jax.random.uniform(ka, (n, L, d_in, rank), jnp.float32, -bound, bound)
        b = (jax.random.normal(kb, (n, L, rank, d_out), jnp.float32) * b_std
             if b_std else jnp.zeros((n, L, rank, d_out), jnp.float32))
        out[name] = {"a": a, "b": b}
    return out


def draw_lora(mc: dict, seed: int, *, count: int, rank: int, targets, b_std: float):
    """``count`` LoRA trees, leaves ``[count, L, d_in, r]`` / ``[count, L, r, d_out]``
    float32. A as PEFT draws it (uniform +-1/sqrt(d_in)); B normal(b_std) for a
    served adapter (a zero B would make every adapter the base), zero for a
    training start."""
    sh = param_shapes(mc)["layer"]
    dims = tuple((t, sh[t][0], sh[t][1]) for t in sorted(set(targets)))
    return _draw_lora(_key(seed, 0x22), dims=dims, L=mc["num_layers"], n=count,
                      rank=rank, b_std=b_std)
