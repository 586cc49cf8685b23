"""Weights and adapters of a ``granitemoehybrid`` (Granite 4.0-H) configuration,
drawn on the device from the seed in the type they are used in: one jitted call
per run of like layers (a layer at a time inside it, so that no float32 copy of
a stacked leaf is ever alive; runs of equal kind and length share a program).
They are the benchmark's, not the program's: the program and the plain
reference (``reference/granite_v4.py``) are handed the same arrays.

Layout is the program's parameter tree for a model of several layer kinds
(``datatunerx_tpu/models/hybrid.py`` docstring): ``layers.run<i>`` per run of
like layers, stacked ``[n, ...]``; adapters mirror it; the head is the tied
embedding (no ``lm_head`` leaf). What is drawn, and how, is the configuration
file's ``assumed``:

- projections and the embedding: normal 0.02; norm scales (``*_layernorm``,
  ``ssm_norm``, the final ``norm``) 1 + normal 0.02;
- the short convolution ``conv.kernel [C, 4]`` normal ``4 ** -0.5`` (it keeps the
  scale of what passes) and ``conv.bias [C]`` normal 0.02;
- Mamba-2's own, float32, so that memory neither dies nor freezes: ``A_log [H] =
  log(uniform(1, 16))`` (``A = -exp(A_log)`` in (-16, -1)); ``dt_bias [H]`` the
  inverse softplus of a ``dt`` drawn log-uniform in (0.001, 0.1), as the
  published initialisation draws it; ``D [H]`` 1 + normal 0.02. A head's decay a
  token is ``exp(-dt |A|)`` with ``dt = softplus(n W_dt + dt_bias)``; the
  projection's columns (normal 0.02 over 2,048 normed inputs: std 0.9) swing
  ``dt`` by a factor of e either way a token, which is the mechanism.
  ``benchmarks/tests/test_granite.py`` holds what share of heads forget (``a ** n
  < 1 / e`` at the head's mean ``dt``) within 10, 100 and 1,000 tokens.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.granite_v4 import ADAPTABLE, runs_of, ssm_dims  # how the reference groups like layers
from weights_ling_v3 import _draw_lora, _items, _key, _sliced_normal  # the seed's key, sliced draws, one adapter program

STD = 0.02
DT_MIN, DT_MAX = 0.001, 0.1
A_MIN, A_MAX = 1.0, 16.0


def mixer_shapes(mc: dict, kind: str) -> dict:
    """{projection: (in, out)} of one mixer kind: all of them take an adapter."""
    D = mc["hidden_size"]
    if kind == "ssm":
        H, P, N, G, _ = ssm_dims(mc)
        return {"in_proj": (D, 2 * H * P + 2 * G * N + H), "o_proj": (H * P, D)}
    assert kind == "global", kind
    H, KV, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    return {"q_proj": (D, H * d), "k_proj": (D, KV * d), "v_proj": (D, KV * d),
            "o_proj": (H * d, D)}


@functools.partial(jax.jit, static_argnames=("mc_items", "kind", "n", "dtype"))
def _draw_run(key, *, mc_items, kind, n, dtype):
    mc = dict(mc_items)
    D, F = mc["hidden_size"], mc["intermediate_size"]

    def normal(k, shape, std=STD, mean=0.0, dtype=dtype):
        return _sliced_normal(k, shape, std, mean, dtype)

    def one_layer(k):
        ks = iter(jax.random.split(k, 16))
        out = {name: {"kernel": normal(next(ks), shape)}
               for name, shape in sorted(mixer_shapes(mc, kind).items())}
        for name in ("input_layernorm", "post_attention_layernorm"):
            out[name] = {"scale": normal(next(ks), (D,), mean=1.0)}
        if kind == "ssm":
            H, P, N, G, K = ssm_dims(mc)
            C = H * P + 2 * G * N
            out["conv"] = {"kernel": normal(next(ks), (C, K), std=K ** -0.5),
                           "bias": normal(next(ks), (C,))}
            out["ssm_norm"] = {"scale": normal(next(ks), (H * P,), mean=1.0)}
            out["A_log"] = jnp.log(jax.random.uniform(next(ks), (H,), jnp.float32, A_MIN, A_MAX))
            dt = jnp.exp(jax.random.uniform(next(ks), (H,), jnp.float32,
                                            math.log(DT_MIN), math.log(DT_MAX)))
            out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) == dt
            out["D"] = normal(next(ks), (H,), mean=1.0, dtype=jnp.float32)
        for name, shape in (("down_proj", (F, D)), ("gate_proj", (D, F)), ("up_proj", (D, F))):
            out[name] = {"kernel": normal(next(ks), shape)}
        return out

    return jax.lax.map(one_layer, jax.random.split(key, n))


def draw_params(mc: dict, seed: int, dtype=jnp.bfloat16):
    D, V = mc["hidden_size"], mc["vocab_size"]
    layers = {}
    for i, (kind, _, n) in enumerate(runs_of(mc)):
        layers[f"run{i}"] = _draw_run(_key(seed, 0x100 + i), mc_items=_items(mc), kind=kind,
                                      n=n, dtype=dtype)
    draw = jax.jit(lambda k, shape, mean: _sliced_normal(k, shape, STD, mean, dtype),
                   static_argnums=(1, 2))
    return {"embed_tokens": {"embedding": draw(_key(seed, 0x11), (V, D), 0.0)},
            "layers": layers,
            "norm": {"scale": draw(_key(seed, 0x12), (D,), 1.0)}}


def draw_lora(mc: dict, seed: int, *, count: int, rank: int, targets, b_std: float):
    """``count`` adapters on the mixer projections named in ``targets``:
    ``{run<i>: {target: {a [count, n, d_in, r], b [count, n, r, d_out]}}}``
    float32, each run with its own kind's geometry (a Mamba run has ``in_proj``
    and ``o_proj``, 4,096 wide on the way in; an attention run ``q_proj``,
    ``k_proj``, ``v_proj`` and ``o_proj``, and takes no ``in_proj``). A as PEFT
    draws it (uniform +-1/sqrt(d_in)), B normal(b_std)."""
    runs = []
    for kind, _, n in runs_of(mc):
        shapes = mixer_shapes(mc, kind)
        runs.append((n, tuple((t, shapes[t][0], shapes[t][1]) for t in sorted(set(targets))
                              if t in ADAPTABLE and t in shapes)))
    return _draw_lora(_key(seed, 0x200), runs=tuple(runs), n=count, rank=rank, b_std=b_std)
