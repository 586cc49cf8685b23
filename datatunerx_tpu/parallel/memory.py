"""Per-device HBM footprint accounting (VERDICT r3 next-round #4).

The reference has nothing like this — an oversized job simply OOMs on the
worker (SURVEY §7.4#1 names capacity the hard part of the TPU port). Here
the byte math is done up front:

- ``estimate_footprint`` sums params, LoRA adapters, optimizer state,
  gradients, and remat-policy activation peaks into bytes/device for a
  given model config, train config, batch geometry, and mesh shape.
- Param/optimizer/gradient trees are counted EXACTLY via ``jax.eval_shape``
  over the same ``init_params`` / ``quantize_model_params`` /
  ``optimizer.init`` calls the trainer makes — no drift between the
  estimate and the real program — then divided per-leaf by the shard
  factors of `parallel/sharding.py`'s partition specs.
- Activations are an analytic model of the remat policy (documented per
  term below) with a safety margin; they are the only approximate term.
- ``check_fits`` turns the estimate into an admission verdict for the
  operator (finetune_controller rejects oversized jobs instead of letting
  them OOM on-slice).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import ModelConfig
from datatunerx_tpu.parallel.sharding import _spec_for

# Usable HBM per chip by generation. Totals are 16/32/95 GB; XLA reserves a
# slice for its own workspace (scratch for fusions, collectives, infeed), so
# admission budgets against ~94% of the total.
HBM_BYTES = {
    "v4": 32e9,
    "v5e": 16e9,
    "v5p": 95e9,
    "v6e": 32e9,
}
XLA_RESERVE_FRACTION = 0.06
# Analytic activation model error margin (the exact terms depend on XLA
# fusion decisions; ±10% covers the observed spread at debug/1B scale).
ACTIVATION_MARGIN = 1.10


@dataclasses.dataclass(frozen=True)
class Footprint:
    """Bytes per device, by component."""

    params: int
    lora: int
    opt_state: int
    grads: int
    activations: int
    logits: int
    fsdp_gather: int = 0  # XLA's whole-stack weight gathers (fsdp>1 only)

    @property
    def total(self) -> int:
        return (self.params + self.lora + self.opt_state + self.grads
                + self.activations + self.logits + self.fsdp_gather)

    def gb(self) -> Dict[str, float]:
        d = {f.name: round(getattr(self, f.name) / 1e9, 3)
             for f in dataclasses.fields(self)}
        d["total"] = round(self.total / 1e9, 3)
        return d


def _shard_divisor(path, x, mesh_shape: Dict[str, int]) -> int:
    """Product of mesh-axis sizes the sharding rules split this leaf over."""
    spec = _spec_for(tuple(getattr(k, "key", k) for k in path), x)
    div = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            div *= mesh_shape.get(ax, 1)
    return div


def _tree_bytes(tree, mesh_shape: Dict[str, int],
                dtype_override=None) -> int:
    """Sum of per-device leaf bytes for a ShapeDtypeStruct (or array) tree."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        size = math.prod(leaf.shape) if leaf.shape else 1
        itemsize = (jnp.dtype(dtype_override).itemsize if dtype_override
                    else jnp.dtype(leaf.dtype).itemsize)
        total += math.ceil(size / _shard_divisor(path, leaf, mesh_shape)
                           ) * itemsize
    return total


def estimate_footprint(
    model_cfg: ModelConfig,
    train_cfg,
    *,
    batch: int,
    seq: int,
    mesh_shape: Optional[Dict[str, int]] = None,
    compute_dtype=jnp.bfloat16,
) -> Footprint:
    """Bytes/device for one train step of ``Trainer`` at this geometry.

    ``mesh_shape`` maps axis name → size ({'dp':1,'fsdp':8,'tp':1,'sp':1});
    missing axes default to 1 (single chip = all 1s).
    """
    from datatunerx_tpu.models import init_params
    from datatunerx_tpu.models.config import refuse_hybrid
    from datatunerx_tpu.models.lora import init_lora_params
    from datatunerx_tpu.training.optimizer import make_optimizer

    # the activation terms below are the single-kind decoder's
    refuse_hybrid(model_cfg, "the train-step memory estimate")
    mesh_shape = dict(mesh_shape or {})
    cdt = jnp.dtype(compute_dtype).itemsize
    key = jax.random.PRNGKey(0)

    # ---- params (exact): the same init(+quantize) call the trainer makes
    def build_params(k):
        p = init_params(model_cfg, k, dtype=compute_dtype)
        if model_cfg.quantization:
            from datatunerx_tpu.ops.quant import quantize_model_params

            p = quantize_model_params(p, model_cfg.quantization)
        return p

    params_shape = jax.eval_shape(build_params, key)
    params_bytes = _tree_bytes(params_shape, mesh_shape)

    # ---- trainable tree (exact)
    lora_bytes = 0
    if train_cfg.finetuning_type == "lora":
        lora_shape = jax.eval_shape(
            lambda k: init_lora_params(
                model_cfg, k, rank=train_cfg.lora_rank,
                targets=tuple(train_cfg.lora_targets)), key)
        lora_bytes = _tree_bytes(lora_shape, mesh_shape)
        trainable_shape = lora_shape
    elif train_cfg.finetuning_type == "none":
        trainable_shape = None
    else:  # full / freeze: the base params are the trainable tree
        trainable_shape = params_shape

    # ---- optimizer state (exact): adamw = 2 fp32 moments per trainable
    opt_bytes = 0
    if trainable_shape is not None:
        optimizer = make_optimizer(
            train_cfg.optimizer, train_cfg.learning_rate,
            weight_decay=train_cfg.weight_decay,
            max_grad_norm=train_cfg.max_grad_norm)
        opt_shape = jax.eval_shape(optimizer.init, trainable_shape)
        opt_bytes = _tree_bytes(opt_shape, mesh_shape)

    # ---- gradients: one trainable-shaped tree, fp32 accumulation worst-case
    grad_bytes = 0
    if trainable_shape is not None:
        grad_bytes = _tree_bytes(trainable_shape, mesh_shape,
                                 dtype_override=jnp.float32)

    # ---- activations (analytic): local batch/seq after sharding.
    # batch shards over (dp, fsdp); seq over sp; grad_accum microbatches the
    # LOCAL batch (scan carries one microbatch of activations at a time).
    data_shards = mesh_shape.get("dp", 1) * mesh_shape.get("fsdp", 1)
    tp = mesh_shape.get("tp", 1)
    b = math.ceil(batch / data_shards)
    b = math.ceil(b / max(1, getattr(train_cfg, "grad_accum", 1)))
    t = math.ceil(seq / mesh_shape.get("sp", 1))
    H = model_cfg.hidden_size
    L = model_cfg.num_layers
    I = model_cfg.intermediate_size  # noqa: E741
    V = model_cfg.vocab_size

    # ---- fsdp weight-gather live set: with parameters sharded over fsdp,
    # XLA all-gathers weights to compute. For the scan-stacked layout it
    # chooses to gather some stacked kernels WHOLE (outside the loop), not
    # per-layer: compiler buffer assignment for Mistral-7B full-param
    # fsdp=16 shows ~9 GB of temps ≈ the two largest stacked kernels
    # gathered in full (AOT_CERTIFY.json step/train_mistral7b_full_fsdp16,
    # r5). Model that observed behavior: the two largest fsdp-sharded
    # stacked leaves, un-sharded. Zero when fsdp == 1 (nothing to gather).
    fsdp = mesh_shape.get("fsdp", 1)
    gather_bytes = 0
    if fsdp > 1:
        stacked = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
            if _shard_divisor(path, leaf, {"fsdp": fsdp}) > 1:
                size = math.prod(leaf.shape) if leaf.shape else 1
                stacked.append(size * jnp.dtype(leaf.dtype).itemsize)
        gather_bytes = sum(sorted(stacked, reverse=True)[:2])

    if model_cfg.remat in ("full", "dots"):
        # stored across the whole fwd: the per-layer boundary residual
        # stream (fwd copy + its gradient in the bwd sweep)
        boundaries = 2 * L * b * t * H * cdt
        if model_cfg.remat == "dots":
            # checkpoint_dots also saves every matmul output inside the
            # layer: qkv+o (≈2H eff. with GQA ≤ 2H + small), gate/up/down
            # (2I + H) — per layer, tp-sharded
            boundaries += L * b * t * (3 * H + 2 * I) // tp * cdt
        # recompute live set: ONE layer's internals during its bwd
        if model_cfg.attention_impl == "xla":
            attn = 2 * b * model_cfg.num_heads * t * t * 4 // tp  # fp32 scores
        else:  # flash/ring never materialize [T, T]
            attn = 4 * b * t * (model_cfg.q_dim + 2 * model_cfg.kv_dim
                                ) // tp * cdt
        mlp = 6 * b * t * I // tp * cdt  # gate/up/act fwd + bwd mirrors
        act_bytes = boundaries + max(attn, mlp)
    else:  # remat none: every layer's internals stay live for the bwd
        if model_cfg.attention_impl == "xla":
            per_layer = (2 * b * model_cfg.num_heads * t * t * 4 // tp
                         + 4 * b * t * H * cdt)
        else:
            per_layer = (4 * b * t * (model_cfg.q_dim + 2 * model_cfg.kv_dim)
                         // tp * cdt + 4 * b * t * H * cdt)
        per_layer += 3 * b * t * I // tp * cdt
        act_bytes = L * per_layer
    act_bytes = int(act_bytes * ACTIVATION_MARGIN)

    # ---- logits: [b, t, V] in compute dtype + the fp32 cast the loss makes
    # (training/loss.py:23) + its gradient; V shards over tp (lm_head spec)
    logits_bytes = b * t * math.ceil(V / tp) * (cdt + 4 + 4)

    return Footprint(
        params=params_bytes, lora=lora_bytes, opt_state=opt_bytes,
        grads=grad_bytes, activations=act_bytes, logits=logits_bytes,
        fsdp_gather=gather_bytes,
    )


def hbm_budget(generation: str = "v5e") -> int:
    """Admission budget: usable HBM/chip after the XLA workspace reserve."""
    if generation not in HBM_BYTES:
        raise KeyError(f"unknown TPU generation {generation!r}; "
                       f"have {sorted(HBM_BYTES)}")
    return int(HBM_BYTES[generation] * (1 - XLA_RESERVE_FRACTION))


def check_fits(
    model_cfg: ModelConfig,
    train_cfg,
    *,
    batch: int,
    seq: int,
    mesh_shape: Optional[Dict[str, int]] = None,
    generation: str = "v5e",
) -> tuple:
    """→ (fits: bool, footprint: Footprint, budget_bytes: int)."""
    fp = estimate_footprint(model_cfg, train_cfg, batch=batch, seq=seq,
                            mesh_shape=mesh_shape)
    budget = hbm_budget(generation)
    return fp.total <= budget, fp, budget
