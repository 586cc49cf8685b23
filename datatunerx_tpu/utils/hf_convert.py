"""HF-transformers ⇄ datatunerx-tpu weight conversion.

The reference loads base models directly from HF checkpoints
(reference cmd/tuning/train.py:236-242, ``--model_name_or_path``). Our param tree
keeps HF leaf names, so conversion is: stack the per-layer tensors along a new
leading layer axis and transpose torch ``Linear`` [out, in] kernels to [in, out].

Works from a plain ``state_dict``-like mapping of numpy arrays (no torch
dependency in the core path; tests use torch-cpu to produce the dict).
"""

from __future__ import annotations

from typing import Mapping

import numpy as np

from datatunerx_tpu.models.config import ModelConfig, refuse_hybrid

_LAYER_KERNELS = [
    ("self_attn.q_proj", "q_proj"),
    ("self_attn.k_proj", "k_proj"),
    ("self_attn.v_proj", "v_proj"),
    ("self_attn.o_proj", "o_proj"),
    ("mlp.gate_proj", "gate_proj"),
    ("mlp.up_proj", "up_proj"),
    ("mlp.down_proj", "down_proj"),
]
_LAYER_NORMS = [
    ("input_layernorm", "input_layernorm"),
    ("post_attention_layernorm", "post_attention_layernorm"),
]


def _np(x) -> np.ndarray:
    if hasattr(x, "detach"):  # torch tensor
        x = x.detach().to("cpu").float().numpy()
    return np.asarray(x, dtype=np.float32)


def convert_hf_state_dict(
    sd: Mapping[str, "np.ndarray"], cfg: ModelConfig, dtype=np.float32
):
    """Convert an HF llama/mistral/qwen2 state_dict to our stacked param tree."""
    refuse_hybrid(cfg, "HF weight conversion")
    L = cfg.num_layers
    prefix = "model." if any(k.startswith("model.") for k in sd) else ""

    def get(k):
        return _np(sd[prefix + k])

    layers: dict = {}
    for hf_name, our_name in _LAYER_KERNELS:
        kernels = np.stack(
            [get(f"layers.{i}.{hf_name}.weight").T for i in range(L)]
        ).astype(dtype)
        layers[our_name] = {"kernel": kernels}
        bias_key = f"{prefix}layers.0.{hf_name}.bias"
        if bias_key in sd:
            layers[our_name]["bias"] = np.stack(
                [_np(sd[f"{prefix}layers.{i}.{hf_name}.bias"]) for i in range(L)]
            ).astype(dtype)
    for hf_name, our_name in _LAYER_NORMS:
        layers[our_name] = {
            "scale": np.stack(
                [get(f"layers.{i}.{hf_name}.weight") for i in range(L)]
            ).astype(dtype)
        }

    params = {
        "embed_tokens": {"embedding": get("embed_tokens.weight").astype(dtype)},
        "layers": layers,
        "norm": {"scale": get("norm.weight").astype(dtype)},
    }
    if "lm_head.weight" in sd and not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": _np(sd["lm_head.weight"]).T.astype(dtype)}
    return params


def export_hf_state_dict(params, cfg: ModelConfig) -> dict:
    """Inverse of convert_hf_state_dict (numpy arrays, HF key names)."""
    refuse_hybrid(cfg, "HF weight export")
    out = {}
    out["model.embed_tokens.weight"] = np.asarray(
        params["embed_tokens"]["embedding"], np.float32
    )
    layers = params["layers"]
    for hf_name, our_name in _LAYER_KERNELS:
        kern = np.asarray(layers[our_name]["kernel"], np.float32)
        for i in range(cfg.num_layers):
            out[f"model.layers.{i}.{hf_name}.weight"] = kern[i].T
        if "bias" in layers[our_name]:
            bias = np.asarray(layers[our_name]["bias"], np.float32)
            for i in range(cfg.num_layers):
                out[f"model.layers.{i}.{hf_name}.bias"] = bias[i]
    for hf_name, our_name in _LAYER_NORMS:
        scale = np.asarray(layers[our_name]["scale"], np.float32)
        for i in range(cfg.num_layers):
            out[f"model.layers.{i}.{hf_name}.weight"] = scale[i]
    out["model.norm.weight"] = np.asarray(params["norm"]["scale"], np.float32)
    if "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["kernel"], np.float32).T
    return out


def config_from_hf(hf_cfg) -> ModelConfig:
    """Build a ModelConfig from an HF PretrainedConfig (llama/mistral/qwen2)."""
    mixed = [k for k in ("hybrid_layer_pattern", "n_routed_experts")
             if getattr(hf_cfg, k, None)]
    if mixed:
        raise NotImplementedError(
            f"HF config of type {getattr(hf_cfg, 'model_type', '?')!r} names "
            f"layers of several kinds ({mixed}): weight conversion handles "
            "the single-kind llama-family decoder only; such a model is "
            "served from a preset with drawn weights")
    return ModelConfig(
        name=getattr(hf_cfg, "model_type", "llama"),
        vocab_size=hf_cfg.vocab_size,
        hidden_size=hf_cfg.hidden_size,
        intermediate_size=hf_cfg.intermediate_size,
        num_layers=hf_cfg.num_hidden_layers,
        num_heads=hf_cfg.num_attention_heads,
        num_kv_heads=getattr(hf_cfg, "num_key_value_heads", hf_cfg.num_attention_heads),
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
        rms_norm_eps=hf_cfg.rms_norm_eps,
        tie_word_embeddings=getattr(hf_cfg, "tie_word_embeddings", False),
        attention_bias=getattr(hf_cfg, "model_type", "") == "qwen2"
        or getattr(hf_cfg, "attention_bias", False),
        sliding_window=getattr(hf_cfg, "sliding_window", None)
        if getattr(hf_cfg, "model_type", "") == "mistral"
        else None,
    )
