"""Weights and adapters of a ``kimi_k2`` (Kimi-K2.5) configuration, drawn on the
device from the seed in the type they are used in: one jitted call per run of
like layers (a layer at a time inside it, an expert at a time inside an expert
layer, so that no float32 copy of a stacked leaf is ever alive). They are the
benchmark's, not the program's: the program and the plain reference
(``reference/kimi_k2.py``) are handed the same arrays.

Layout is the program's parameter tree for a model of several layer kinds
(``datatunerx_tpu/models/hybrid.py`` docstring): ``layers.run<i>`` per run of
like layers, stacked ``[n, ...]``; adapters mirror the tree. What is drawn, and
how, is the configuration file's ``assumed``:

- projections, embeddings, the head, experts: normal 0.02; norm scales
  (``*_layernorm``, the final ``norm``) 1 + normal 0.02;
- ``e_score_correction_bias`` normal 0.003 (``weights_mimo_v2.BIAS_STD`` says
  why: a larger one alone decides the choice of experts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.kimi_k2 import ADAPTABLE, runs_of  # how the reference groups like layers
from weights_ling_v3 import _draw_lora, _items, _key, _sliced_normal  # the seed's key, sliced draws, one adapter program

STD = 0.02
BIAS_STD = 0.003


def mixer_shapes(mc: dict) -> dict:
    """{projection: (in, out)} of the mixer; ``q_b_proj`` and ``o_proj`` take an adapter."""
    D, H = mc["hidden_size"], mc["num_heads"]
    dv = mc.get("v_head_dim") or mc["head_dim"]
    rank, nope, rot = mc["kv_lora_rank"], mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    q_rank = mc["q_lora_rank"]
    return {"q_a_proj": (D, q_rank), "q_b_proj": (q_rank, H * (nope + rot)),
            "kv_a_proj": (D, rank + rot), "kv_b_proj": (rank, H * (nope + dv)),
            "o_proj": (H * dv, D)}


@functools.partial(jax.jit, static_argnames=("mc_items", "ffn", "n", "dtype"))
def _draw_run(key, *, mc_items, ffn, n, dtype):
    mc = dict(mc_items)
    D = mc["hidden_size"]

    def normal(k, shape, std=STD, mean=0.0, dtype=dtype):
        return _sliced_normal(k, shape, std, mean, dtype)

    def one_layer(k):
        ks = iter(jax.random.split(k, 32))
        out = {name: {"kernel": normal(next(ks), shape)}
               for name, shape in sorted(mixer_shapes(mc).items())}
        for name, width in (("input_layernorm", D), ("post_attention_layernorm", D),
                            ("q_a_layernorm", mc["q_lora_rank"]),
                            ("kv_a_layernorm", mc["kv_lora_rank"])):
            out[name] = {"scale": normal(next(ks), (width,), mean=1.0)}
        shapes = lambda F: (("down_proj", (F, D)), ("gate_proj", (D, F)), ("up_proj", (D, F)))  # noqa: E731
        if ffn == "dense":
            for name, shape in shapes(mc["intermediate_size"]):
                out[name] = {"kernel": normal(next(ks), shape)}
        else:
            E, Eh = mc["experts_total"], mc["experts_held"]
            out["router"] = {"kernel": normal(next(ks), (D, E))}
            out["e_score_correction_bias"] = normal(next(ks), (E,), std=BIAS_STD)
            out["experts"] = {
                name: jax.lax.map(lambda kk, shape=shape: normal(kk, shape),
                                  jax.random.split(next(ks), Eh))
                for name, shape in shapes(mc["expert_intermediate_size"])}
            Fs = int(mc.get("shared_expert_intermediate_size") or 0)
            if Fs:
                out["shared_expert"] = {name: {"kernel": normal(next(ks), shape)}
                                        for name, shape in shapes(Fs)}
        return out

    return jax.lax.map(one_layer, jax.random.split(key, n))


def draw_params(mc: dict, seed: int, dtype=jnp.bfloat16):
    D, V = mc["hidden_size"], mc["vocab_size"]
    layers = {}
    for i, (_, ffn, n) in enumerate(runs_of(mc)):
        layers[f"run{i}"] = _draw_run(_key(seed, 0x100 + i), mc_items=_items(mc), ffn=ffn,
                                      n=n, dtype=dtype)
    draw = jax.jit(lambda k, shape, mean: _sliced_normal(k, shape, STD, mean, dtype),
                   static_argnums=(1, 2))
    return {"embed_tokens": {"embedding": draw(_key(seed, 0x11), (V, D), 0.0)},
            "layers": layers,
            "norm": {"scale": draw(_key(seed, 0x12), (D,), 1.0)},
            "lm_head": {"kernel": draw(_key(seed, 0x13), (D, V), 0.0)}}


def draw_lora(mc: dict, seed: int, *, count: int, rank: int, targets, b_std: float):
    """``count`` adapters on the mixer projections named in ``targets``:
    ``{run<i>: {target: {a [count, n, d_in, r], b [count, n, r, d_out]}}}``
    float32; the mixer has ``q_b_proj`` (from the query's 1,536-wide bottleneck
    up to 64 heads of 192) and ``o_proj``, and no ``q_proj`` to take one. A as
    PEFT draws it (uniform +-1/sqrt(d_in)), B normal(b_std)."""
    shapes = mixer_shapes(mc)
    dims = tuple((t, shapes[t][0], shapes[t][1]) for t in sorted(set(targets)) if t in ADAPTABLE)
    runs = tuple((n, dims) for _, _, n in runs_of(mc))
    return _draw_lora(_key(seed, 0x200), runs=runs, n=count, rank=rank, b_std=b_std)
