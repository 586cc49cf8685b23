"""LoRA: low-rank adapters as a separate param collection.

Replaces the reference's PEFT `get_peft_model` wrapping (reference
cmd/tuning/train.py:266-280). TPU-native design: adapters live in their own
pytree mirroring `params["layers"]` with stacked [L, ...] leaves, so

- the optimizer state covers ONLY adapter params (the base stays frozen with no
  Adam moments — the memory win that makes LoRA cheap),
- `forward(..., lora=(lora_params, scaling))` applies h·W + (h·A)·B·scale inside
  each projection (fusable by XLA; Pallas fused kernel in ops/lora_matmul.py),
- `merge_lora` folds adapters into base kernels for export/serving, matching
  PEFT's `merge_and_unload` semantics.

Init matches PEFT (reference peft 0.5.0): A ~ kaiming-uniform, B = 0, so the
delta starts at zero. Scaling = lora_alpha / lora_rank. Defaults mirror the
reference CLI: rank 8, alpha 32, dropout 0.1 (reference cmd/tuning/parser.py:138-149);
the controller always passes ``--lora_target q_proj,v_proj`` (reference
internal/controller/finetune/finetune_controller.go:482).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import ModelConfig

# Valid llama-family targets (reference cmd/tuning/parser.py:150-160).
LLAMA_TARGETS = (
    "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj",
)
# ``in_proj``: a state-space mixer's one input projection; ``q_b_proj``: the
# up-projection of a latent-attention mixer's low-rank query (models/hybrid.py)
LORA_TARGETS = LLAMA_TARGETS + ("in_proj", "q_b_proj")
DEFAULT_TARGETS = ("q_proj", "v_proj")


def target_dims(cfg: ModelConfig, name: str) -> tuple[int, int]:
    D, F = cfg.hidden_size, cfg.intermediate_size
    return {
        "q_proj": (D, cfg.q_dim),
        "k_proj": (D, cfg.kv_dim),
        "v_proj": (D, cfg.kv_dim),
        "o_proj": (cfg.q_dim, D),
        "gate_proj": (D, F),
        "up_proj": (D, F),
        "down_proj": (F, D),
    }[name]


def lora_groups(cfg: ModelConfig) -> list:
    """The groups of layers whose adapter leaves are stacked together, as
    ``[(key, layers, {target: (d_in, d_out)})]``. A model whose layers are all
    of one kind is one group with key ``None`` (tree ``layers.<target>``, the
    only layout there was); a model of several kinds has one group per run of
    like layers (tree ``layers.<run>.<target>``), each with its own geometry
    (``v_proj`` is as wide as that run's KV heads; a latent-attention run has
    ``q_proj``, or ``q_b_proj`` where its query is low-rank, and ``o_proj``
    only). Experts take no adapter."""
    if not cfg.hybrid:
        return [(None, cfg.num_layers,
                 {t: target_dims(cfg, t) for t in LLAMA_TARGETS})]
    from datatunerx_tpu.models.config import layer_runs
    from datatunerx_tpu.models.hybrid import attn_dims, run_key

    D, F = cfg.hidden_size, cfg.intermediate_size
    groups = []
    for i, run in enumerate(layer_runs(cfg)):
        dims = dict(attn_dims(cfg, run.mixer))
        if run.ffn == "dense":
            dims.update(gate_proj=(D, F), up_proj=(D, F), down_proj=(F, D))
        groups.append((run_key(i), run.count, dims))
    return groups


def group_tree(layers: dict, key):
    """One group's ``{target: {a, b}}`` of an adapter's ``layers`` tree."""
    return layers if key is None else layers.get(key, {})


def adapter_leaves(layers: dict) -> list:
    """Every ``{a, b}`` leaf pair of an adapter's ``layers`` tree, whichever layout."""
    out = []
    for value in layers.values():
        out.extend([value] if "a" in value else list(value.values()))
    return out


def lora_scaling(alpha: float, rank: int) -> float:
    return float(alpha) / float(rank)


def init_lora_params(
    cfg: ModelConfig,
    key: jax.Array,
    rank: int = 8,
    targets: Sequence[str] = DEFAULT_TARGETS,
    dtype=jnp.float32,
):
    for t in targets:
        if t not in LORA_TARGETS:
            raise ValueError(f"invalid lora target {t!r}; choices: {LORA_TARGETS}")
    layers = {}
    for g, (gkey, L, dims) in enumerate(lora_groups(cfg)):
        group = layers if gkey is None else layers.setdefault(gkey, {})
        for i, t in enumerate(sorted(set(targets) & set(dims))):
            d_in, d_out = dims[t]
            # kaiming-uniform(a=sqrt(5)) over fan_in, like torch Linear / peft LoRA A:
            # bound = sqrt(6 / ((1 + a^2) * fan_in)) = 1 / sqrt(fan_in)
            bound = 1.0 / math.sqrt(d_in)
            k = jax.random.fold_in(key, i) if gkey is None else \
                jax.random.fold_in(jax.random.fold_in(key, 7919 + g), i)
            a = jax.random.uniform(
                k, (L, d_in, rank), jnp.float32, -bound, bound).astype(dtype)
            group[t] = {"a": a, "b": jnp.zeros((L, rank, d_out), dtype)}
    return {"layers": layers}


def num_lora_params(lora_params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(lora_params))


def merge_lora(params, lora_params, scaling: float):
    """Fold adapters into base kernels: W' = W + A·B·scaling (per layer)."""

    def fold(layers, tree):  # one group of like layers
        layers = dict(layers)
        for t, ab in tree.items():
            delta = jnp.einsum(
                "lir,lro->lio",
                ab["a"].astype(jnp.float32),
                ab["b"].astype(jnp.float32),
            ) * scaling
            proj = dict(layers[t])
            proj["kernel"] = (proj["kernel"].astype(jnp.float32) + delta).astype(
                layers[t]["kernel"].dtype
            )
            layers[t] = proj
        return layers

    given = lora_params["layers"]
    if all("a" in leaf for leaf in given.values()):
        layers = fold(params["layers"], given)
    else:  # one group per run of like layers: layers.<run>.<target>
        layers = dict(params["layers"])
        for gkey, tree in given.items():
            layers[gkey] = fold(layers[gkey], tree)
    out = dict(params)
    out["layers"] = layers
    return out
