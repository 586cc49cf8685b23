"""Inference engine: jitted prefill + KV-cache decode for chat serving.

Replaces the reference's Ray Serve ``LlamaDeployment`` (deployed from a zip,
reference internal/controller/finetune/finetunejob_controller.go:378-384; env
contract BASE_MODEL_DIR + CHECKPOINT_DIR, pkg/util/generate/generate.go:288-294).
TPU-native: the base model + (optionally) a LoRA adapter checkpoint are loaded
directly (no image bake) and merged for serving; generation runs as a jitted
per-token decode step over a static-shape KV cache (JetStream-style decode loop,
SURVEY.md §7.1).
"""

from __future__ import annotations

import collections
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.data.templates import Template, get_template
from datatunerx_tpu.models.llama import forward, init_cache
from datatunerx_tpu.utils.model_loader import load_model_and_tokenizer

# Bounded LRU of shared _EnginePrograms — see the memo note in
# InferenceEngine.__init__. Entries pin only the model config (params arrive
# as arguments), so a dead donor engine's weights are never kept resident;
# the dict evicts least-recently-used configs.
_ENGINE_MEMO: collections.OrderedDict = collections.OrderedDict()
_ENGINE_MEMO_MAX = 8


def _engine_memo_key(cfg):
    """Hashable program identity, or None when it can't be established
    (memoization is best-effort; the dataclass repr covers every field)."""
    try:
        return repr(cfg)
    except Exception:  # noqa: BLE001
        return None


class _EnginePrograms:
    """The engine's jitted (prefill, decode_loop) pair, factored OFF the
    engine (the BatchedEngine ``_Programs`` pattern) so the process-wide memo
    pins only what tracing actually reads — the model config. Params, cache,
    and sampling state all arrive as arguments, which is what makes the
    programs shareable across engines in the first place."""

    def __init__(self, cfg):
        self.cfg = cfg
        # prefill returns the cache it is given and consumes it (donated),
        # as every such program of batched_engine._Programs does; the decode
        # loop returns no cache, so there is nothing for a donation to alias
        self.prefill = jax.jit(self._prefill_impl,
                               static_argnames=("prompt_len",),
                               donate_argnums=(4,))
        # whole decode loop in ONE device program (lax.while_loop): no
        # per-token Python dispatch
        self.decode_loop = jax.jit(self._decode_loop_impl,
                                   static_argnames=("max_new_tokens",))

    def _prefill_impl(self, params, tokens, mask, positions, cache, prompt_len):
        logits, cache = forward(
            params, tokens, self.cfg, positions=positions,
            attention_mask=mask, cache=cache, compute_dtype=jnp.bfloat16,
        )
        return logits[:, prompt_len - 1], cache

    def _decode_loop_impl(self, params, first_logits, cache, start_pos,
                          stop_arr, rng, temperature, top_p, limit, *,
                          max_new_tokens: int):
        """Greedy/sampled decode as one lax.while_loop program. Returns
        (tokens [max_new_tokens buffer], n_generated); `limit` is the dynamic
        request cap within the static buffer."""
        out0 = jnp.zeros((max_new_tokens,), jnp.int32)

        def sample(logits, rng):
            with jax.named_scope("dtx.sample"):
                return _sample_jit(logits, temperature, top_p, rng)

        def cond(carry):
            i, logits, cache, rng, out, stopped = carry
            return (~stopped) & (i < limit)

        def body(carry):
            i, logits, cache, rng, out, stopped = carry
            rng, sub = jax.random.split(rng)
            nxt = sample(logits[0], sub)
            stopped = jnp.any(nxt == stop_arr)
            out = jnp.where(stopped, out, out.at[i].set(nxt))
            logits2, cache = forward(
                params, nxt[None, None], self.cfg,
                positions=(start_pos + i)[None, None],
                cache=cache, compute_dtype=jnp.bfloat16,
            )
            return (i + jnp.where(stopped, 0, 1), logits2[:, -1], cache, rng,
                    out, stopped)

        i, _, _, _, out, _ = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), first_logits, cache, rng, out0,
             jnp.zeros((), bool)),
        )
        return out, i


class InferenceEngine:
    def __init__(
        self,
        model_path: str,
        checkpoint_path: Optional[str] = None,
        template: str = "llama2",
        max_seq_len: int = 1024,
        dtype=jnp.bfloat16,
        quantization: Optional[str] = None,
    ):
        self.cfg, self.params, self.tokenizer = load_model_and_tokenizer(
            model_path, dtype=dtype
        )
        if checkpoint_path:
            self._apply_checkpoint(checkpoint_path)
        if quantization:
            # serve-time weight quantization (int8 ≈ half, nf4 ≈ quarter of
            # bf16 HBM). Quantize on the HOST, then upload only the quantized
            # tree — quantizing on-device would need full-precision + quantized
            # resident simultaneously, OOMing exactly the big-model case this
            # feature exists for.
            import dataclasses

            from datatunerx_tpu.ops.quant import quantize_model_params

            host_params = jax.device_get(self.params)
            cpu = jax.devices("cpu")[0] if jax.default_backend() != "cpu" else None
            if cpu is not None:
                with jax.default_device(cpu):
                    qparams = quantize_model_params(host_params, quantization)
                self.params = jax.device_put(jax.device_get(qparams))
            else:
                self.params = quantize_model_params(host_params, quantization)
            self.cfg = dataclasses.replace(self.cfg, quantization=quantization)
        self.template: Template = get_template(template, self.tokenizer)
        self.max_seq_len = min(max_seq_len, self.cfg.max_seq_len)
        # Process-wide program memo (the BatchedEngine / Trainer step-memo
        # pattern): the traced programs depend on the engine only through cfg
        # — params, cache, and sampling state all arrive as arguments — so
        # engines with an equal config share one set of jitted callables and
        # jax's in-memory executable cache (N single-slot engines in one
        # process compile once, not N times).
        key = _engine_memo_key(self.cfg)
        progs = None if key is None else _ENGINE_MEMO.get(key)
        if progs is None:
            progs = _EnginePrograms(self.cfg)
            if key is not None:
                _ENGINE_MEMO[key] = progs
                while len(_ENGINE_MEMO) > _ENGINE_MEMO_MAX:
                    _ENGINE_MEMO.popitem(last=False)
        else:
            _ENGINE_MEMO.move_to_end(key)
        self._prefill = progs.prefill
        self._decode_loop = progs.decode_loop

    # ---------------------------------------------------------- checkpoint
    def _apply_checkpoint(self, checkpoint_path: str):
        """Merge a trained adapter (or swap full params) from an Orbax
        TrainState checkpoint or an exported model.npz directory."""
        if os.path.isdir(checkpoint_path) and os.path.exists(
            os.path.join(checkpoint_path, "model.npz")
        ):
            from datatunerx_tpu.utils.hf_convert import convert_hf_state_dict

            sd = dict(np.load(os.path.join(checkpoint_path, "model.npz")))
            self.params = convert_hf_state_dict(sd, self.cfg, dtype=np.float32)
            return
        # Orbax checkpoint dir (…/checkpoints or …/checkpoints/<step>)
        import orbax.checkpoint as ocp

        root = checkpoint_path.rstrip("/")
        step: Optional[int] = None
        if os.path.basename(root).isdigit():
            step = int(os.path.basename(root))
            root = os.path.dirname(root)
        mngr = ocp.CheckpointManager(root)
        step = step if step is not None else mngr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {checkpoint_path}")
        from datatunerx_tpu.training.checkpoint import restore_raw_state

        restored = restore_raw_state(mngr, step)
        mngr.close()
        state = restored if isinstance(restored, dict) else dict(restored)
        lora = state.get("lora")
        if lora:
            from datatunerx_tpu.models.lora import (
                adapter_leaves,
                lora_scaling,
                merge_lora,
            )

            rank = adapter_leaves(lora["layers"])[0]["a"].shape[-1]
            scaling = self._manifest_lora_scaling(root)
            if scaling is None:
                # manifest absent (ad-hoc checkpoint dir): fall back to the
                # reference defaults alpha=32 / r (cmd/tuning/parser.py:138-145)
                scaling = lora_scaling(32.0, rank)
            self.params = merge_lora(self.params, lora, scaling)
        elif state.get("params"):
            self.params = state["params"]

    @staticmethod
    def _manifest_lora_scaling(ckpt_root: str):
        """The completion manifest (written next to the checkpoints dir by
        tuning/train.py) records the trained adapter's alpha/rank scaling;
        merging with any other value serves a silently-wrong model."""
        from datatunerx_tpu.training.checkpoint import read_manifest

        run_dir = os.path.dirname(ckpt_root.rstrip("/"))
        try:
            manifest = read_manifest(os.path.dirname(run_dir),
                                     os.path.basename(run_dir))
            val = (manifest or {}).get("lora_scaling")
            return float(val) if val is not None else None
        except (OSError, ValueError, TypeError):
            return None

    # ------------------------------------------------------------ generate
    def generate(
        self,
        prompt_ids: List[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        stop_ids: Optional[set] = None,
    ) -> List[int]:
        import numbers

        from datatunerx_tpu.utils.decoding import prepare_prompt

        stop_ids = {int(s) for s in (stop_ids or set())
                    if isinstance(s, numbers.Integral)}
        stop_ids.add(self.tokenizer.eos_token_id)
        ids, mask, positions, plen, n_prompt, max_new, buf = prepare_prompt(
            prompt_ids, self.tokenizer.eos_token_id, self.max_seq_len,
            max_new_tokens,
        )

        cache = init_cache(self.cfg, 1, plen + buf, dtype=jnp.bfloat16)
        logits, cache = self._prefill(
            self.params, jnp.asarray([ids], jnp.int32),
            jnp.asarray([mask], jnp.int32), jnp.asarray([positions], jnp.int32),
            cache, prompt_len=plen,
        )
        stop_arr = jnp.asarray(sorted(stop_ids), jnp.int32)
        out, n = self._decode_loop(
            self.params, logits, cache,
            jnp.asarray(n_prompt, jnp.int32), stop_arr,
            jax.random.PRNGKey(seed),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(max_new, jnp.int32),
            max_new_tokens=buf,
        )
        n = int(n)
        return np.asarray(out).tolist()[:n]  # ONE device->host fetch

    def perplexity(self, prompt_ids: List[int], completion_ids: List[int]) -> dict:
        """Mean NLL of the completion given the prompt (LoRA already merged
        at load for this engine)."""
        if not hasattr(self, "_nll"):
            self._nll = jax.jit(
                lambda params, tokens, mask: nll_impl(params, self.cfg, tokens, mask)
            )
        tokens, mask, _ = prepare_nll_inputs(
            prompt_ids, completion_ids, self.tokenizer.eos_token_id,
            self.max_seq_len,
        )
        nll_sum, n_tok = self._nll(self.params, tokens, mask)
        return nll_result(float(nll_sum), int(n_tok))

    def chat(
        self,
        messages: List[dict],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
    ) -> str:
        """OpenAI-ish messages → templated prompt → completion text."""
        prompt_ids, stop_ids = encode_chat_messages(
            self.template, self.tokenizer, messages
        )
        out_ids = self.generate(
            prompt_ids, max_new_tokens=max_new_tokens, temperature=temperature,
            top_p=top_p, seed=seed, stop_ids=stop_ids,
        )
        return self.tokenizer.decode(out_ids, skip_special_tokens=True)


def encode_chat_messages(template: Template, tokenizer, messages: List[dict]):
    """OpenAI-ish messages → (prompt_ids, stop_ids) via the chat template.
    Shared by the single-request and continuous-batching engines so template
    semantics can never diverge between them."""
    system = None
    history: List[tuple] = []
    pending: Optional[str] = None
    for m in messages:
        role, content = m.get("role"), m.get("content", "")
        if role == "system":
            system = content
        elif role == "user":
            if pending is not None:
                history.append((pending, ""))
            pending = content
        elif role == "assistant" and pending is not None:
            history.append((pending, content))
            pending = None
    prompt_ids, _ = template.encode_oneturn(
        tokenizer, pending or "", "", history or None, system
    )
    stop_ids = {tokenizer.eos_token_id}
    for w in template.stop_words:
        tid = tokenizer.convert_tokens_to_ids(w)
        if isinstance(tid, int):  # no-unk fast tokenizers return None
            stop_ids.add(tid)
    return prompt_ids, stop_ids


def nll_result(nll_sum: float, n_tok: int) -> dict:
    import math

    mean = nll_sum / max(n_tok, 1)
    return {"nll_sum": nll_sum, "num_tokens": n_tok,
            "mean_nll": mean, "perplexity": math.exp(mean)}


def nll_impl(params, cfg, tokens, target_mask, **fw_kwargs):
    """Sum of -log p(token) over masked target positions + token count.

    ``target_mask`` marks completion tokens in the ORIGINAL index space;
    column j of the shifted targets corresponds to token j+1, so the mask is
    sliced accordingly. Backs the serving /perplexity endpoint (dataset-driven
    perplexity scoring, scoring/dataset_scoring.py)."""
    logits, _ = forward(params, tokens, cfg, compute_dtype=jnp.bfloat16,
                        **fw_kwargs)
    logprobs = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    ll = jnp.take_along_axis(logprobs, tgt[..., None], axis=-1)[..., 0]
    w = target_mask[:, 1:].astype(jnp.float32)
    return jnp.sum(-ll * w), jnp.sum(w)


def prepare_nll_inputs(prompt_ids, completion_ids, eos_id, max_seq_len,
                       bucket: int = 64):
    """Right-pad prompt+completion to a compile bucket; completion tokens get
    mask 1. Long inputs truncate from the LEFT, keeping the completion."""
    ids = list(prompt_ids) + list(completion_ids)
    if len(ids) > max_seq_len:
        ids = ids[-max_seq_len:]
    n_completion = min(len(completion_ids), len(ids) - 1)
    total = len(ids)
    padded = min(-(-total // bucket) * bucket, max_seq_len)
    mask = [0] * (total - n_completion) + [1] * n_completion
    ids = ids + [eos_id] * (padded - total)
    mask = mask + [0] * (padded - total)
    return (jnp.asarray([ids], jnp.int32), jnp.asarray([mask], jnp.int32),
            n_completion)


def _sample_jit(logits: jnp.ndarray, temperature, top_p, rng) -> jnp.ndarray:
    """Traceable sampling: greedy when temperature<=0, else top-p sampling.
    All branches computed and selected with where (cheap at vocab scale)."""
    greedy = jnp.argmax(logits).astype(jnp.int32)

    t = jnp.maximum(temperature, 1e-6)
    scaled = logits / t
    sorted_idx = jnp.argsort(-scaled)
    sorted_logits = scaled[sorted_idx]
    probs = jax.nn.softmax(sorted_logits)
    cum = jnp.cumsum(probs)
    cut = (cum - probs > top_p) & (top_p < 1.0)
    filtered = jnp.where(cut, -jnp.inf, sorted_logits)
    choice = jax.random.categorical(rng, filtered)
    sampled = sorted_idx[choice].astype(jnp.int32)

    return jnp.where(temperature <= 0.0, greedy, sampled)
