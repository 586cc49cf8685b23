"""Explicit jitted train/eval loop — the TPU-native replacement for HF Trainer +
Ray Train (reference cmd/tuning/train.py:138-305, trainer.py).

One `Trainer` covers the reference's finetuning types (reference
cmd/tuning/parser.py:121-124):

  lora   — optimizer state over the adapter tree only; base params frozen
  freeze — last `num_layer_trainable` layers of a chosen module group train
           (reference parser.py:125-137), expressed as a per-layer gradient mask
           over the stacked [L, ...] leaves
  full   — everything trains (GSPMD/fsdp shards params + opt state)
  none   — eval only

Gradient accumulation is exact: per-microbatch grads of the *sum* NLL are
accumulated in a `lax.scan` and divided by the total valid-token count, so the
result is identical to one big batch regardless of padding imbalance.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import struct

from datatunerx_tpu.models.config import ModelConfig, refuse_hybrid
from datatunerx_tpu.models.llama import forward
from datatunerx_tpu.models.lora import (
    DEFAULT_TARGETS,
    init_lora_params,
    lora_scaling,
)
from datatunerx_tpu.data.prefetch import PlacedBatch
from datatunerx_tpu.parallel.sharding import (
    place_batch,
    shard_tree,
    tree_shardings,
)
from datatunerx_tpu.training.loss import IGNORE_INDEX, causal_lm_loss
from datatunerx_tpu.training.optimizer import make_optimizer, make_schedule

_ATTN_MODULES = ("q_proj", "k_proj", "v_proj", "o_proj")
_MLP_MODULES = ("gate_proj", "up_proj", "down_proj")


def _freeze_selected_modules(train_cfg) -> tuple:
    """The trainable module group for freeze tuning (reference
    ``--name_module_trainable``, cmd/tuning/parser.py:125-137). Single source
    of truth for BOTH the optimizer labels and the gradient mask."""
    return (_MLP_MODULES if train_cfg.name_module_trainable in ("mlp",)
            else _ATTN_MODULES)


def _in_freeze_group(path, modules) -> bool:
    names = [getattr(p, "key", p) for p in path]
    return "layers" in names and any(m in names for m in modules)


@dataclasses.dataclass
class TrainConfig:
    finetuning_type: str = "lora"  # lora | freeze | full | none
    # LoRA (reference cmd/tuning/parser.py:138-164)
    lora_rank: int = 8
    lora_alpha: float = 32.0
    lora_dropout: float = 0.1
    lora_targets: Sequence[str] = DEFAULT_TARGETS
    # freeze tuning (reference cmd/tuning/parser.py:125-137)
    num_layer_trainable: int = 3
    name_module_trainable: str = "mlp"
    # optimization (Hyperparameter CR fields, SURVEY.md §2.3)
    learning_rate: float = 2e-4
    scheduler: str = "cosine"
    optimizer: str = "adamw"
    warmup_ratio: float = 0.0
    weight_decay: float = 0.0
    max_grad_norm: float = 1.0
    total_steps: int = 1000
    grad_accum: int = 1
    neftune_alpha: float = 0.0
    compute_dtype: Any = jnp.bfloat16
    # stage: sft (default) | dpo | rm | ppo. DPO is LoRA-only by design: the
    # frozen reference policy is the BASE model with the adapter switched off —
    # one weight tree serves both policies, no second 7B copy in HBM (the
    # reference reserves --stage dpo but has no runtime for it). RM (reference
    # cmd/tuning/parser.py:117-120 stage list, reward_model arg :74-76) trains
    # base+LoRA with a scalar value head scored at the last response token,
    # pairwise ranking loss -log σ(r_chosen − r_rejected). PPO (training/
    # ppo.py) adds the same v_head to the POLICY adapter (actor-critic shared
    # trunk) and reuses the adapter-off base as both reference policy and
    # reward-model trunk.
    stage: str = "sft"
    dpo_beta: float = 0.1

    def __post_init__(self):
        assert self.finetuning_type in ("lora", "freeze", "full", "none")
        assert self.stage in ("sft", "dpo", "rm", "ppo")
        if self.stage in ("dpo", "rm", "ppo") and self.finetuning_type != "lora":
            raise ValueError(
                f"stage {self.stage} requires finetuning_type lora (the "
                "frozen base serves as the DPO reference policy / keeps the "
                "reward model a cheap adapter; full/freeze would need a "
                "second copy of the weights)"
            )


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    lora: Any  # None unless finetuning_type == "lora"
    opt_state: Any
    rng: jax.Array


class Trainer:
    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        mesh=None,
    ):
        refuse_hybrid(model_cfg, "the trainer")
        self.model_cfg = model_cfg
        self.cfg = train_cfg
        self.mesh = mesh
        if model_cfg.attention_impl == "ring":
            from datatunerx_tpu.ops.ring_attention import set_ring_context

            set_ring_context(mesh)
        # Mosaic kernels can't be GSPMD-auto-partitioned: the flash call must
        # run under shard_map on a multi-device mesh. ALWAYS (re)set the
        # process-global context — a non-flash or mesh-less trainer must
        # clear a previous trainer's mesh, or later single-device flash
        # calls would wrap over a stale (possibly abstract/dead) mesh
        from datatunerx_tpu.ops.flash_attention import set_flash_context

        set_flash_context(mesh if model_cfg.attention_impl == "flash"
                          else None)
        self.schedule = make_schedule(
            train_cfg.scheduler,
            train_cfg.learning_rate,
            train_cfg.total_steps,
            train_cfg.warmup_ratio,
        )
        self.optimizer = make_optimizer(
            train_cfg.optimizer,
            self.schedule,
            weight_decay=train_cfg.weight_decay,
            max_grad_norm=train_cfg.max_grad_norm,
        )
        if train_cfg.finetuning_type == "freeze":
            # No optimizer moments for fully-frozen leaves (embed/norms/lm_head
            # and the unselected module group) — the memory win freeze tuning
            # exists for. Layer-window freezing within the selected stacked
            # leaves is handled by the gradient mask in _train_step_impl.
            import optax

            modules = _freeze_selected_modules(train_cfg)

            def labels(params):
                def lab(path, x):
                    return ("train" if _in_freeze_group(path, modules)
                            else "frozen")

                return jax.tree_util.tree_map_with_path(lab, params)

            self.optimizer = optax.multi_transform(
                {"train": self.optimizer, "frozen": optax.set_to_zero()}, labels
            )
        self.scaling = lora_scaling(train_cfg.lora_alpha, train_cfg.lora_rank)
        # Process-wide step-program memo: two Trainers built from equal
        # (model_cfg, train_cfg, mesh) produce identical programs, so they
        # share one jitted callable — and with it jax's in-memory executable
        # cache. Spinning up N trainers in one process (scoring controller
        # sweeps, the test suite's dozens of e2e runs) compiles each distinct
        # step program once instead of once per Trainer.
        key = _step_memo_key(model_cfg, train_cfg, mesh, type(self))
        cached = None if key is None else _STEP_MEMO.get(key)
        if cached is None:
            self._train_step = jax.jit(self._train_step_impl, donate_argnums=(0,))
            self._eval_step = jax.jit(self._eval_step_impl)
            if key is not None:
                _STEP_MEMO[key] = (self._train_step, self._eval_step)
                while len(_STEP_MEMO) > _STEP_MEMO_MAX:
                    _STEP_MEMO.popitem(last=False)
        else:
            _STEP_MEMO.move_to_end(key)
            self._train_step, self._eval_step = cached

    # ---------------------------------------------------------------- state
    def init_state(self, params, rng: jax.Array) -> TrainState:
        lora = None
        if self.cfg.finetuning_type == "lora":
            lora = init_lora_params(
                self.model_cfg,
                jax.random.fold_in(rng, 0x10AA),  # distinct stream from step rngs
                rank=self.cfg.lora_rank,
                targets=tuple(self.cfg.lora_targets),
            )
            if self.cfg.stage in ("rm", "ppo"):
                # scalar value head over the final-norm hidden state; rides in
                # the trainable tree (replicated by the sharding rules)
                lora["v_head"] = (
                    jax.random.normal(jax.random.fold_in(rng, 0x4EAD),
                                      (self.model_cfg.hidden_size,),
                                      jnp.float32)
                    / math.sqrt(self.model_cfg.hidden_size)
                )
        if self.mesh is not None:
            params = shard_tree(params, self.mesh)
            if lora is not None:
                lora = shard_tree(lora, self.mesh)
        trainable = self._trainable(params, lora)
        if self.cfg.finetuning_type == "none":
            opt_state = ()
        elif self.mesh is None:
            opt_state = jax.jit(self.optimizer.init)(trainable)
        else:
            # Adam moments mirror the trainable tree's paths, so the param
            # rules shard them like their params (scalars replicate). Left to
            # output-sharding propagation, the all-zeros moments depend on no
            # sharded input and the WHOLE state lands on device 0 — found by
            # the [mesh] line on a four-chip host; at 7B full-parameter that
            # is two extra copies of the model on one chip before step 1.
            out_sh = tree_shardings(
                jax.eval_shape(self.optimizer.init, trainable), self.mesh)
            with self.mesh:
                opt_state = jax.jit(self.optimizer.init,
                                    out_shardings=out_sh)(trainable)
        step = jnp.zeros((), jnp.int32)
        if self.mesh is not None:
            # replicate scalars/keys on the mesh so checkpoint-restore templates
            # carry complete shardings (place_state then exists only for
            # cross-topology restores)
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            step = jax.device_put(step, repl)
            rng = jax.device_put(rng, repl)
        return TrainState(
            step=step,
            params=params,
            lora=lora,
            opt_state=opt_state,
            rng=rng,
        )

    def _trainable(self, params, lora):
        return lora if self.cfg.finetuning_type == "lora" else params

    def place_state(self, state: TrainState) -> TrainState:
        """Re-place a (restored) state onto this trainer's mesh shardings."""
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())
        put = lambda t: None if t is None else shard_tree(t, self.mesh)  # noqa: E731
        return TrainState(
            step=jax.device_put(state.step, repl),
            params=put(state.params),
            lora=put(state.lora),
            opt_state=put(state.opt_state),
            rng=jax.device_put(state.rng, repl),
        )

    def _freeze_mask(self, params):
        """Per-leaf multiplicative masks for freeze tuning."""
        L = self.model_cfg.num_layers
        n = self.cfg.num_layer_trainable
        modules = _freeze_selected_modules(self.cfg)
        layer_ok = (jnp.arange(L) >= L - n).astype(jnp.float32)

        def mask_for(path, x):
            if _in_freeze_group(path, modules):
                return layer_ok.reshape((L,) + (1,) * (x.ndim - 1))
            return jnp.zeros((), jnp.float32)

        return jax.tree_util.tree_map_with_path(mask_for, params)

    # ----------------------------------------------------------------- loss
    def _sequence_logps(self, params, lora, ids, labels, rng, train: bool):
        """Per-sequence sum of response-token log-probs ([B]); response
        positions are where the (shifted) label is not IGNORE_INDEX."""
        logits, _ = forward(
            params, ids, self.model_cfg,
            lora=(lora, self.scaling) if lora is not None else None,
            compute_dtype=self.cfg.compute_dtype,
            lora_dropout=self.cfg.lora_dropout if (train and lora is not None) else 0.0,
            dropout_rng=rng if (train and lora is not None) else None,
        )
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = ids[:, 1:]
        ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        mask = (labels[:, 1:] != IGNORE_INDEX).astype(jnp.float32)
        return jnp.sum(ll * mask, axis=-1)

    def _dpo_loss(self, trainable, state: TrainState, batch, rng, train: bool):
        """DPO (Rafailov et al. 2023): -log σ(β[(π_c − ref_c) − (π_r − ref_r)]).
        Policy = base + adapter; reference = same base, adapter OFF
        (stop-gradient) — both sides in the same program, chosen and rejected
        concatenated so each policy is ONE forward."""
        ids = jnp.concatenate([batch["chosen_ids"], batch["rejected_ids"]], 0)
        labels = jnp.concatenate([batch["chosen_labels"],
                                  batch["rejected_labels"]], 0)
        pol = self._sequence_logps(state.params, trainable, ids, labels, rng, train)
        ref = jax.lax.stop_gradient(
            self._sequence_logps(state.params, None, ids, labels, None, False)
        )
        B = batch["chosen_ids"].shape[0]
        margin = (pol[:B] - ref[:B]) - (pol[B:] - ref[B:])
        loss = -jax.nn.log_sigmoid(self.cfg.dpo_beta * margin)
        # padding pairs (all-IGNORE labels, from eval tail padding) would
        # each contribute ln2: mask them out of sum AND count
        valid = jnp.any(batch["chosen_labels"][:, 1:] != IGNORE_INDEX,
                        axis=-1).astype(jnp.float32)
        # (sum, count) contract shared with the token-NLL path: count = pairs
        return jnp.sum(loss * valid), jnp.sum(valid).astype(jnp.int32)

    def _rm_loss(self, trainable, state: TrainState, batch, rng, train: bool):
        """Pairwise reward-model loss: -log σ(r_chosen − r_rejected), reward =
        v_head · hidden at each sequence's LAST response token (where the
        label stops being IGNORE). Chosen/rejected share one forward."""
        ids = jnp.concatenate([batch["chosen_ids"], batch["rejected_ids"]], 0)
        labels = jnp.concatenate([batch["chosen_labels"],
                                  batch["rejected_labels"]], 0)
        _, _, hidden = forward(
            state.params, ids, self.model_cfg,
            lora=(trainable, self.scaling),
            compute_dtype=self.cfg.compute_dtype,
            lora_dropout=self.cfg.lora_dropout if train else 0.0,
            dropout_rng=rng if train else None,
            return_hidden=True,
            skip_logits=True,  # reward = v_head · hidden; no vocab projection
        )
        resp = labels != IGNORE_INDEX  # [2B, T]
        T = ids.shape[1]
        last = jnp.argmax(
            jnp.where(resp, jnp.arange(T, dtype=jnp.int32)[None, :], -1), axis=1
        )  # [2B] index of last response token (0 for all-pad rows)
        h_last = jnp.take_along_axis(
            hidden, last[:, None, None].astype(jnp.int32), axis=1
        )[:, 0].astype(jnp.float32)  # [2B, D]
        rewards = h_last @ trainable["v_head"].astype(jnp.float32)  # [2B]
        B = batch["chosen_ids"].shape[0]
        loss = -jax.nn.log_sigmoid(rewards[:B] - rewards[B:])
        valid = jnp.any(batch["chosen_labels"][:, 1:] != IGNORE_INDEX,
                        axis=-1).astype(jnp.float32)  # mask eval-tail pad pairs
        return jnp.sum(loss * valid), jnp.sum(valid).astype(jnp.int32)

    def _forward_loss(self, trainable, state: TrainState, batch, rng, train: bool):
        if self.cfg.stage == "dpo":
            return self._dpo_loss(trainable, state, batch, rng, train)
        if self.cfg.stage == "rm":
            return self._rm_loss(trainable, state, batch, rng, train)
        if self.cfg.finetuning_type == "lora":
            params, lora = state.params, trainable
        else:
            params, lora = trainable, None
        logits, _ = forward(
            params,
            batch["input_ids"],
            self.model_cfg,
            attention_mask=batch.get("attention_mask"),
            segment_ids=batch.get("segment_ids"),
            positions=batch.get("positions"),
            lora=(lora, self.scaling) if lora is not None else None,
            compute_dtype=self.cfg.compute_dtype,
            lora_dropout=self.cfg.lora_dropout if train else 0.0,
            dropout_rng=rng if train else None,
            neftune_alpha=self.cfg.neftune_alpha if train else 0.0,
        )
        return causal_lm_loss(logits, batch["labels"])

    # ------------------------------------------------------------ train step
    def _train_step_impl(self, state: TrainState, batch):
        """batch leaves: [A, mb, T] when grad_accum > 1 else [B, T]."""
        cfg = self.cfg
        rng = jax.random.fold_in(jax.random.fold_in(state.rng, 0x57E9), state.step)
        trainable = self._trainable(state.params, state.lora)

        def sum_nll(tr, mb, r):
            s, n = self._forward_loss(tr, state, mb, r, train=True)
            return s, n

        vgrad = jax.value_and_grad(sum_nll, has_aux=True)

        if cfg.grad_accum > 1:
            def micro(carry, xs):
                g_acc, s_acc, n_acc = carry
                mb, i = xs
                (s, n), g = vgrad(trainable, mb, jax.random.fold_in(rng, i))
                return (
                    jax.tree_util.tree_map(jnp.add, g_acc, g),
                    s_acc + s,
                    n_acc + n,
                ), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, trainable)
            A = jax.tree_util.tree_leaves(batch)[0].shape[0]
            (grads, total_nll, total_n), _ = jax.lax.scan(
                micro,
                (zeros, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
                (batch, jnp.arange(A)),
            )
        else:
            (total_nll, total_n), grads = vgrad(trainable, batch, rng)

        denom = jnp.maximum(total_n, 1).astype(jnp.float32)
        grads = jax.tree_util.tree_map(lambda g: g / denom, grads)

        if cfg.finetuning_type == "freeze":
            mask = self._freeze_mask(trainable)
            grads = jax.tree_util.tree_map(jnp.multiply, grads, mask)

        updates, opt_state = self.optimizer.update(grads, state.opt_state, trainable)
        if cfg.finetuning_type == "freeze":
            updates = jax.tree_util.tree_map(jnp.multiply, updates, mask)
        # apply in the update dtype, then cast back to the param dtype: a bare
        # jnp.add promotes bf16 params against fp32 updates, so one full-param
        # step silently doubled the whole state (and broke train-step buffer
        # donation, since output dtypes no longer matched the donated inputs)
        # — caught by AOT buffer-assignment analysis, scripts/aot_certify.py
        new_trainable = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), trainable, updates)

        grad_norm = optax_global_norm(grads)
        metrics = {
            "loss": total_nll / denom,
            "lr": self.schedule(state.step),
            "grad_norm": grad_norm,
            "tokens": total_n,
        }
        if cfg.finetuning_type == "lora":
            new_state = state.replace(
                step=state.step + 1, lora=new_trainable, opt_state=opt_state
            )
        else:
            new_state = state.replace(
                step=state.step + 1, params=new_trainable, opt_state=opt_state
            )
        return new_state, metrics

    def _eval_step_impl(self, state: TrainState, batch):
        trainable = self._trainable(state.params, state.lora)
        s, n = self._forward_loss(trainable, state, batch, None, train=False)
        return {"sum_nll": s, "tokens": n}

    # ------------------------------------------------------------- public API
    def train_step(self, state: TrainState, batch):
        """Accepts host batches (placed inline) or ``PlacedBatch`` objects a
        DevicePrefetcher already put on the mesh (data/prefetch.py)."""
        batch = self._put_batch(batch, accum=self.cfg.grad_accum > 1)
        return self._train_step(state, batch)

    def eval_step(self, state: TrainState, batch):
        batch = self._put_batch(batch)
        return self._eval_step(state, batch)

    def _put_batch(self, batch, accum: bool = False):
        if isinstance(batch, PlacedBatch):
            # already on the mesh via the pipelined path — placing again would
            # misread device arrays as process-local slices on multi-host
            return dict(batch)
        return place_batch(batch, self.mesh, accum=accum)

    def evaluate(self, state: TrainState, batches) -> dict:
        """Aggregate eval: mean loss + perplexity = exp(loss) (reference
        cmd/tuning/trainer.py:324-327)."""
        tot_s, tot_n = 0.0, 0
        for b in batches:
            m = self.eval_step(state, b)
            tot_s += float(m["sum_nll"])
            tot_n += int(m["tokens"])
        loss = tot_s / max(tot_n, 1)
        import math

        return {"eval_loss": loss, "perplexity": math.exp(min(loss, 80.0)), "eval_tokens": tot_n}


# Bounded LRU: each entry pins a Trainer closure + its compiled executables,
# so an unbounded dict would leak across a long-lived controller sweeping
# many distinct configs (each trial would add, never release). 16 covers any
# realistic set of concurrently-live configs; evicted entries free their
# executables once the owning Trainers are gone.
_STEP_MEMO: collections.OrderedDict = collections.OrderedDict()
_STEP_MEMO_MAX = 16


def _step_memo_key(model_cfg, train_cfg, mesh, cls):
    """Hashable identity of the compiled step program, or None when identity
    can't be established (unhashable/exotic field values → compile fresh).
    dataclass reprs cover every field deterministically; the mesh enters by
    axis layout + device ids (devices are process singletons in jax); the
    concrete Trainer class guards subclasses that override step impls."""
    try:
        mesh_key = None
        if mesh is not None:
            mesh_key = (
                tuple(mesh.shape.items()),
                tuple(d.id for d in mesh.devices.flat),
            )
        return (cls.__qualname__, repr(model_cfg), repr(train_cfg), mesh_key)
    except Exception:  # noqa: BLE001 — memoization is best-effort
        return None


def optax_global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros(())
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
