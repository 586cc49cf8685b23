"""Model + tokenizer resolution for the trainer CLI.

``--model_name_or_path`` accepts (reference loads HF checkpoints directly,
cmd/tuning/train.py:236-242):

- ``preset:<name>``      — random-init from a ModelConfig preset with the
                           byte-level SimpleTokenizer (smoke/dev/e2e tests);
- a directory with our own ``model.npz`` + ``config.json`` export
                           (training/checkpoint.py export_merged_model);
- an HF checkpoint dir   — config via config_from_hf, weights via
                           AutoModelForCausalLM (torch CPU), tokenizer via
                           AutoTokenizer.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional, Tuple

import jax
import numpy as np

from datatunerx_tpu.models.config import ModelConfig, get_config
from datatunerx_tpu.models.llama import init_params
from datatunerx_tpu.utils.hf_convert import config_from_hf, convert_hf_state_dict
from datatunerx_tpu.utils.simple_tokenizer import SimpleTokenizer


def load_model_and_tokenizer(
    path_or_preset: str,
    dtype=np.float32,
    seed: int = 0,
    config_overrides: Optional[dict] = None,
) -> Tuple[ModelConfig, dict, object]:
    overrides = config_overrides or {}
    if path_or_preset.startswith("preset:"):
        cfg = get_config(path_or_preset.split(":", 1)[1], **overrides)
        tok = SimpleTokenizer()
        # byte-level tokenizer needs vocab >= 3000+specials
        if cfg.vocab_size < 3100:
            cfg = dataclasses.replace(cfg, vocab_size=3104)
        params = init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)
        return cfg, params, tok

    if not os.path.isdir(path_or_preset):
        raise FileNotFoundError(f"model path {path_or_preset!r} does not exist")

    npz = os.path.join(path_or_preset, "model.npz")
    if os.path.exists(npz):
        with open(os.path.join(path_or_preset, "config.json")) as f:
            raw = json.load(f)
        field_names = {f.name for f in dataclasses.fields(ModelConfig)}
        raw = {k: v for k, v in raw.items() if k in field_names}
        for k in ("head_dim", "sliding_window"):
            if raw.get(k) in ("None", ""):
                raw[k] = None
        raw.update(overrides)
        cfg = ModelConfig(**raw)
        # convert_hf_state_dict refuses a model of several layer kinds by
        # name: such a model loads from a preset (drawn weights) only
        sd = dict(np.load(npz))
        params = convert_hf_state_dict(sd, cfg, dtype=dtype)
        tok = _load_hf_tokenizer(path_or_preset) or SimpleTokenizer()
        return cfg, params, tok

    # HF checkpoint directory
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_cfg = AutoConfig.from_pretrained(path_or_preset)
    cfg = config_from_hf(hf_cfg)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = AutoModelForCausalLM.from_pretrained(path_or_preset)
    params = convert_hf_state_dict(model.state_dict(), cfg, dtype=dtype)
    del model
    tok = _load_hf_tokenizer(path_or_preset)
    if tok is None:
        raise FileNotFoundError(f"no tokenizer found under {path_or_preset}")
    return cfg, params, tok


def load_tokenizer(path_or_preset: str):
    """Tokenizer WITHOUT the weights — for components that only need token
    counts (e.g. the gateway's admission estimator). Never initializes params;
    returns None when no tokenizer can be found (callers fall back to a
    chars/token heuristic)."""
    if path_or_preset.startswith("preset:"):
        return SimpleTokenizer()
    if not os.path.isdir(path_or_preset):
        return None
    tok = _load_hf_tokenizer(path_or_preset)
    if tok is None and os.path.exists(
            os.path.join(path_or_preset, "model.npz")):
        # in-repo export format ships without a tokenizer dir: the byte-level
        # SimpleTokenizer is what serving pairs with it
        return SimpleTokenizer()
    return tok


def _load_hf_tokenizer(path: str):
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(path)
    except Exception:
        return None
