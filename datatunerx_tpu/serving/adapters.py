"""Adapter-checkpoint utilities for multi-adapter serving.

``make_adapter_checkpoint`` synthesizes a LoRA adapter checkpoint with
random weights — the shape/layout of a real training run's Orbax state
(``{"lora": {"layers": ...}}``, loadable by
``batched_engine.load_checkpoint_state``) without paying for a training
run. Used by the side-by-side serving test
(``tests/test_sidebyside_serving.py``, BASELINE row 6); numerics are
meaningless by design — only routing and isolation are checked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from datatunerx_tpu.models import get_config
from datatunerx_tpu.models.lora import init_lora_params


def make_adapter_checkpoint(path: str, model: str, seed: int,
                            rank: int = 4,
                            targets=("q_proj", "v_proj")) -> str:
    """Write a synthetic LoRA adapter checkpoint under ``path`` and return
    it. Random A AND B (init's zero-B would make the adapter a no-op and
    every adapter identical)."""
    from datatunerx_tpu.training.checkpoint import CheckpointManager

    cfg = get_config(model.split(":")[-1])
    key = jax.random.PRNGKey(seed)
    lora = init_lora_params(cfg, key, rank=rank, targets=tuple(targets))
    def with_b(tree, salt):  # one group's {target: {a, b}}
        return {t: {"a": leaf["a"], "b": 0.05 * jax.random.normal(
            jax.random.fold_in(key, salt + i), leaf["b"].shape, jnp.float32)}
            for i, (t, leaf) in enumerate(sorted(tree.items()))}

    if cfg.hybrid:  # per-run geometry: layers.<run>.<target>
        layers = {run: with_b(tree, 1000 * (g + 1))
                  for g, (run, tree) in enumerate(sorted(lora["layers"].items()))}
    else:
        layers = with_b(lora["layers"], 1000)
    mngr = CheckpointManager(path)
    mngr.maybe_save({"lora": {"layers": layers}}, step=1, force=True)
    mngr.close()
    return path


def make_adapter_sweep(base_path: str, model: str, count: int,
                       ranks=(2, 4, 8), targets=("q_proj", "v_proj"),
                       seed: int = 0) -> dict:
    """``count`` synthetic adapters cycling through ``ranks`` — the
    mixed-rank tenant population the pooled AdapterStore rank-pads (tests)
    and the adapter-churn serve bench rotates through. Returns
    {name: checkpoint_path}; names are ``ad<i>-r<rank>`` so a failure
    message states the rank that produced it."""
    import os

    out = {}
    for i in range(count):
        rank = ranks[i % len(ranks)]
        name = f"ad{i}-r{rank}"
        out[name] = make_adapter_checkpoint(
            os.path.join(base_path, name), model, seed=seed + i,
            rank=rank, targets=targets)
    return out
