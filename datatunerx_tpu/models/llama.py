"""Llama-family decoder (Llama-2, Mistral, Qwen1.5) as a functional JAX model.

TPU-first design decisions (vs the reference's HF-transformers torch path,
reference cmd/tuning/train.py:236-242):

- **Stacked-layer params + `lax.scan`**: all L transformer blocks share one set
  of leaf arrays with a leading layer axis. One compiled block, O(1) HLO size in
  depth, and GSPMD shards every layer identically.
- **Functional**: params are a plain pytree; `forward` is pure. `pjit`/remat/
  `shard_map` compose without framework hooks.
- **bf16 by default on TPU**, f32 norms/softmax; remat ("gradient checkpointing",
  reference cmd/tuning/train.py:205) is a config knob applied to the scan body.
- **Optional KV cache** threaded through the same forward for serving.
- **Optional LoRA tree** applied inside each projection so one code path covers
  base, LoRA train, and merged inference (reference PEFT usage train.py:266-280).

Param tree (HF-compatible leaf names so weight conversion is mechanical):
  embed_tokens.embedding [V, D]
  layers.{input_layernorm,post_attention_layernorm}.scale [L, D]
  layers.{q,k,v,o}_proj.kernel  [L, in, out] (+ .bias for Qwen q/k/v)
  layers.{gate,up,down}_proj.kernel
  norm.scale [D];  lm_head.kernel [D, V] (absent when tied)
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Any, Optional

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import ModelConfig
from datatunerx_tpu.ops.attention import (
    KVStep,
    attention,
    attention_allow,
    cache_positions_update,
    kv_cache_update,
    kv_cache_width,
    kv_cache_write,
    make_causal_bias,
)
from datatunerx_tpu.ops.paged_attention import POS_SENTINEL, kv_leaf_keys
from datatunerx_tpu.ops.rope import apply_rope, rope_cos_sin

Params = Any  # nested dict pytree


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    if cfg.hybrid:  # layers of several kinds: models/hybrid.py
        from datatunerx_tpu.models import hybrid

        return hybrid.init_params(cfg, key, dtype=dtype)
    keys = jax.random.split(key, 16)
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers

    def dense(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers = {
        "input_layernorm": {"scale": jnp.ones((L, D), dtype)},
        "post_attention_layernorm": {"scale": jnp.ones((L, D), dtype)},
        "q_proj": {"kernel": dense(keys[0], (L, D, cfg.q_dim))},
        "k_proj": {"kernel": dense(keys[1], (L, D, cfg.kv_dim))},
        "v_proj": {"kernel": dense(keys[2], (L, D, cfg.kv_dim))},
        "o_proj": {"kernel": dense(keys[3], (L, cfg.q_dim, D))},
        "gate_proj": {"kernel": dense(keys[4], (L, D, F))},
        "up_proj": {"kernel": dense(keys[5], (L, D, F))},
        "down_proj": {"kernel": dense(keys[6], (L, F, D))},
    }
    if cfg.attention_bias:
        layers["q_proj"]["bias"] = jnp.zeros((L, cfg.q_dim), dtype)
        layers["k_proj"]["bias"] = jnp.zeros((L, cfg.kv_dim), dtype)
        layers["v_proj"]["bias"] = jnp.zeros((L, cfg.kv_dim), dtype)
    params = {
        "embed_tokens": {"embedding": dense(keys[7], (cfg.vocab_size, D))},
        "layers": layers,
        "norm": {"scale": jnp.ones((D,), dtype)},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense(keys[8], (D, cfg.vocab_size))}
    return params


def num_params(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def _proj(h, p, lora_p, lora_scale, drop_key=None, drop_rate=0.0,
          quant_mode=None, dims=None, use_pallas=False, lora_idx=None):
    """Dense projection with optional LoRA delta: h W + drop(h) A B * scale.

    The base weight is either a full-precision kernel or a quantized collection
    (ops/quant.py) — QLoRA = quantized frozen base + full-precision adapters
    (reference bnb int4/int8 + peft, cmd/tuning/train.py:224-280).
    LoRA dropout applies to the adapter branch input only, matching peft's
    ``lora_dropout`` (reference cmd/tuning/parser.py:146-149, default 0.1).

    Multi-adapter serving: with ``lora_idx`` ([B] int32), lora_p leaves are
    STACKED over adapters ([E, d_in, r]/[E, r, d_out], per layer) and
    ``lora_scale`` is a vector [E]; each batch row applies its own adapter —
    one decode program serves mixed-adapter batches (no per-adapter merge).
    """
    if "quant" in p:
        from datatunerx_tpu.ops.quant import quantized_matmul

        out = quantized_matmul(h, p["quant"], quant_mode, dims,
                               use_pallas=use_pallas)
    else:
        out = h @ p["kernel"].astype(h.dtype)
    if "bias" in p:
        out = out + p["bias"].astype(h.dtype)
    if lora_p is not None:
        with jax.named_scope("dtx.lora"):
            a = lora_p["a"].astype(h.dtype)
            b = lora_p["b"].astype(h.dtype)
            hl = h
            if drop_key is not None and drop_rate > 0.0:
                keep = jax.random.bernoulli(drop_key, 1.0 - drop_rate,
                                            h.shape)
                hl = jnp.where(keep, h / (1.0 - drop_rate),
                               0.0).astype(h.dtype)
            if lora_idx is not None:
                a_sel = a[lora_idx]  # [B, d_in, r]
                b_sel = b[lora_idx]  # [B, r, d_out]
                scale = jnp.asarray(
                    lora_scale, h.dtype)[lora_idx][:, None, None]
                delta = jnp.einsum("btd,bdr->btr", hl, a_sel)
                out = out + jnp.einsum("btr,bro->bto", delta, b_sel) * scale
            else:
                out = out + ((hl @ a) @ b) * jnp.asarray(lora_scale, h.dtype)
    return out


# POS_SENTINEL (imported above) marks invalid/pad cache slots: the causal
# check kv_pos <= q_pos masks them with no separate validity plumbing. The
# paged block-pool cache (ops/paged_attention.py ``init_paged_cache``) is the
# elastic alternative to this dense layout; both satisfy the same
# ops/attention.py cache interface.


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               per_slot: bool = False, quantize: Optional[str] = None):
    """KV cache. ``per_slot=True`` gives each batch row its own write cursor
    (``len`` is [batch]) — continuous batching needs rows at different depths
    in one decode program (serving/batched_engine.py).

    ``quantize="int8"`` stores k/v as int8 with a per-vector (over head_dim)
    scale — half the cache HBM of bf16, so double the slot × context budget
    for serving; dequantized on read inside the same program."""
    if cfg.hybrid:  # a KV pool per attention kind: models/hybrid.py
        from datatunerx_tpu.models import hybrid

        return hybrid.init_cache(cfg, batch, max_len, dtype=dtype,
                                 per_slot=per_slot, quantize=quantize)
    L = cfg.num_layers
    # heads and width are one axis, as in the paged pool (ops/paged_attention.py)
    shape = (L, batch, max_len, cfg.num_kv_heads * cfg.head_dim)
    scales = shape[:-1] + (cfg.num_kv_heads,)
    cache = {
        "len": (jnp.zeros((batch,), jnp.int32) if per_slot
                else jnp.zeros((), jnp.int32)),
        # rope position of each written slot (slots ≠ positions under
        # left-padded prefill); sentinel = unwritten or pad
        "pos": jnp.full((batch, max_len), POS_SENTINEL, jnp.int32),
    }
    if quantize == "int8":
        cache["k"] = jnp.zeros(shape, jnp.int8)
        cache["v"] = jnp.zeros(shape, jnp.int8)
        cache["k_scale"] = jnp.zeros(scales, jnp.float32)
        cache["v_scale"] = jnp.zeros(scales, jnp.float32)
    elif quantize:
        raise ValueError(f"unsupported cache quantization {quantize!r}")
    else:
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
    return cache


@functools.lru_cache(maxsize=64)
def _log_attention_once(requested: str, traced: str, seq_len: int,
                        sliding_window, packed: bool) -> None:
    """Say, once per distinct case, which attention implementation a
    cache-less forward (training / eval) actually traced — a flash or ring
    request that this shape or config cannot take becomes plain einsum
    attention, and that must be visible in the run's log."""
    note = ""
    if traced != requested:
        note = (" — DOWNGRADED: flash needs T % 128 == 0 or T < 128, ring "
                "takes no packed segments and no sliding window")
    print(f"[attention] requested={requested} traced={traced} T={seq_len} "
          f"sliding_window={sliding_window} packed={packed}{note}",
          file=sys.stderr, flush=True)


def lm_logits(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Project final-norm hidden states onto the vocabulary ([..., D] →
    [..., V] float32). Exposed so rm/ppo can project only the response
    window instead of paying the lm_head matmul for every prompt position."""
    if cfg.tie_word_embeddings or "lm_head" not in params:
        logits = x @ params["embed_tokens"]["embedding"].astype(x.dtype).T
    else:
        logits = x @ params["lm_head"]["kernel"].astype(x.dtype)
    return logits.astype(jnp.float32)


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, T] int32
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, T]
    attention_mask: Optional[jnp.ndarray] = None,  # [B, T] 1=valid, 0=pad
    segment_ids: Optional[jnp.ndarray] = None,  # [B, T] for packed sequences
    cache: Optional[dict] = None,
    lora: Optional[tuple[Params, float]] = None,
    lora_adapter_idx: Optional[jnp.ndarray] = None,  # [B] — stacked adapters
    compute_dtype=None,
    lora_dropout: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    neftune_alpha: float = 0.0,
    return_hidden: bool = False,
    skip_logits: bool = False,
    window_mask: Optional[jnp.ndarray] = None,  # [B, T, WN] bool — see below
    window_start: Optional[jnp.ndarray] = None,  # [B] linear window start
):
    """Returns (logits [B, T, V] float32, new_cache | None); with
    ``return_hidden`` also the final-norm hidden states [B, T, D].
    ``skip_logits`` (requires return_hidden) returns logits=None — value-head
    consumers (rm/ppo) skip the [T, V] lm_head matmul entirely and project
    only the positions they need via ``lm_logits``.

    ``window_mask``/``window_start`` (tree-draft speculative verification,
    serving/speculative.py): an extra attendability mask over the WN cache
    lanes starting at ``window_start`` (this step's own writes — tree
    branches sharing rope positions attend only their own root-to-leaf
    path). ``window_start`` defaults to the pre-step ``cache["len"]``.
    Outside the window, masking is untouched; ``None`` is byte-identical
    to before the parameter existed."""
    if skip_logits and not return_hidden:
        raise ValueError("skip_logits without return_hidden returns nothing")
    if cfg.hybrid:
        # the one dispatch on the layer description: a model whose layers are
        # of several kinds is run by models/hybrid.py (inference only)
        from datatunerx_tpu.models import hybrid

        unsupported = {"segment_ids": segment_ids, "dropout_rng": dropout_rng,
                       "window_mask": window_mask, "window_start": window_start}
        given = sorted(k for k, v in unsupported.items() if v is not None)
        if given or lora_dropout or neftune_alpha:
            raise NotImplementedError(
                f"model {cfg.name!r} has layers of several kinds and is served, "
                f"not trained: {given or 'dropout/noise'} is not handled for it")
        return hybrid.forward(
            params, tokens, cfg, positions=positions,
            attention_mask=attention_mask, cache=cache, lora=lora,
            lora_adapter_idx=lora_adapter_idx, compute_dtype=compute_dtype,
            return_hidden=return_hidden, skip_logits=skip_logits)
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    x = params["embed_tokens"]["embedding"][tokens]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    if neftune_alpha > 0.0 and dropout_rng is not None:
        # NEFTune (reference cmd/tuning/parser.py:190-193): uniform noise on the
        # embedding output, magnitude alpha / sqrt(T * D), training only.
        mag = neftune_alpha / jnp.sqrt(jnp.asarray(T * x.shape[-1], jnp.float32))
        noise = jax.random.uniform(
            jax.random.fold_in(dropout_rng, 0x4EF), x.shape, jnp.float32, -1.0, 1.0
        )
        x = x + (noise * mag).astype(x.dtype)

    seq_len = T if cache is None else kv_cache_width(cache)
    cos, sin = rope_cos_sin(
        positions,
        cfg.head_dim,
        theta=cfg.rope_theta,
        scaling_type=cfg.rope_scaling_type,
        scaling_factor=cfg.rope_scaling_factor,
        max_seq_len=cfg.max_seq_len,
        seq_len=seq_len,
    )

    # Pallas in-place decode: single-token steps over a paged cache read the
    # K/V blocks through the block table inside the kernel — no gathered
    # [B, W, KV, d] view, no [B, 1, T, W] bias tensor; the kernel takes the
    # model's sliding window (and drops one the cache is not wider than).
    # Multi-token steps over a paged cache (chunked-prefill chunks, spec
    # verify-k columns, tree-verify windows) of a model with no window ride
    # the multi-token variant, which consumes the oracle's own attendability
    # tensor as a mask operand. Everything else (prefill into dense caches,
    # a windowed model's multi-token steps, packed segments) keeps the
    # gather path, which doubles as the kernels' parity oracle.
    _paged_cfg = (
        cache is not None
        and "block_tables" in cache
        and getattr(cfg, "paged_kernel", False)
    )
    paged_kernel = _paged_cfg and T == 1 and window_mask is None
    paged_kernel_mt = (_paged_cfg and not paged_kernel
                       and segment_ids is None
                       and cfg.sliding_window is None)
    if window_mask is not None and window_start is None:
        if cache is None:
            raise ValueError("window_mask without a cache needs window_start")
        window_start = jnp.broadcast_to(cache["len"], (B,))
    if cache is None:
        kv_positions = positions
        kv_valid = attention_mask.astype(bool) if attention_mask is not None else None
        kv_seg = segment_ids
        cache_pos = None
    else:
        # record each new slot's rope position; pads (attention_mask 0) get
        # the sentinel so the causal check masks them everywhere. The paged
        # cache returns the gathered per-slot linear view as kv_positions
        # (or None on the kernel path, which masks the pos POOL in place).
        cache_pos, kv_positions = cache_positions_update(
            cache, positions, attention_mask, gather=not paged_kernel)
        kv_valid = None  # sentinel positions handle both unwritten and pads
        kv_seg = None
    # flash/ring kernels skip the [B, T, S] bias entirely (building it would
    # defeat their O(T) memory win). Flash handles causal + packed segments +
    # sliding window in-kernel; ring is causal-only. Cache decode needs the
    # biased path.
    _flash_ok = (
        cfg.attention_impl in ("flash", "ring")
        and cache is None
        and (cfg.attention_impl != "ring"
             or (segment_ids is None and cfg.sliding_window is None))
        and (cfg.attention_impl != "flash" or T % 128 == 0 or T < 128)
    )
    allow = None
    if _flash_ok or paged_kernel:
        bias = None
    elif paged_kernel_mt:
        # the oracle's boolean, handed to the kernel instead of a bias —
        # mask parity with the gather path holds by construction
        bias = None
        allow = attention_allow(
            positions,
            kv_positions,
            kv_valid,
            window_mask=window_mask,
            window_start=window_start,
        )
    else:
        bias = make_causal_bias(
            positions,
            kv_positions,
            kv_valid,
            sliding_window=cfg.sliding_window,
            q_segment_ids=segment_ids,
            kv_segment_ids=kv_seg,
            window_mask=window_mask,
            window_start=window_start,
        )

    lora_layers, lora_scale = (None, 0.0)
    if lora is not None:
        lora_params, lora_scale = lora
        lora_layers = lora_params.get("layers", lora_params)

    drop = lora_dropout if (dropout_rng is not None and lora is not None) else 0.0

    # a forward with a cache, and a shape or config the requested kernel
    # cannot take, go the biased path
    att_impl = cfg.attention_impl if _flash_ok else (
        "xla" if cfg.attention_impl in ("flash", "ring") else cfg.attention_impl
    )
    if cache is None:
        _log_attention_once(cfg.attention_impl, att_impl, T,
                            cfg.sliding_window, segment_ids is not None)

    # where this step's tokens land in the cache leaves and what attention
    # reads back: the same for every layer but for the layer's index
    kv_step = KVStep(cache, T) if cache is not None else None

    def block(carry, scanned):
        # the cache leaves travel in the CARRY beside x and each layer writes
        # and reads them at its own index, so the scan moves nothing of them;
        # without a cache (training) the carry holds x alone
        x, pools = carry
        lp, ll, layer_idx = scanned
        lget = (lambda name: ll.get(name)) if ll else (lambda name: None)
        if drop > 0.0:
            lkey = jax.random.fold_in(dropout_rng, layer_idx)
            kget = lambda j: jax.random.fold_in(lkey, j)  # noqa: E731
        else:
            kget = lambda j: None  # noqa: E731

        qm, qp = cfg.quantization, cfg.quant_impl == "pallas"
        D, F = cfg.hidden_size, cfg.intermediate_size

        # one named scope per region, so that every op of a layer is in
        # exactly one of them (benchmarks/scope_readers.py reads them from
        # the device trace); what the scan itself moves (the carry, a
        # layer's parameters) carries dtx.layers and no inner scope
        with jax.named_scope("dtx.qkv"):
            h = rms_norm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
            q = _proj(h, lp["q_proj"], lget("q_proj"), lora_scale, kget(0),
                      drop, qm, (D, cfg.q_dim), qp, lora_adapter_idx)
            k = _proj(h, lp["k_proj"], lget("k_proj"), lora_scale, kget(1),
                      drop, qm, (D, cfg.kv_dim), qp, lora_adapter_idx)
            v = _proj(h, lp["v_proj"], lget("v_proj"), lora_scale, kget(2),
                      drop, qm, (D, cfg.kv_dim), qp, lora_adapter_idx)
            q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
            k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        if pools is not None and paged_kernel:
            # in-place decode: scatter the token's K/V into its blocks, then
            # the Pallas kernel reads them back through the block table —
            # the [B, W, KV, d] gathered view never materializes
            from datatunerx_tpu.ops.pallas_paged_attention import (
                paged_attention_decode_step,
            )

            with jax.named_scope("dtx.kv_write"):
                pools = kv_cache_write(kv_step, pools, layer_idx, k, v)
            with jax.named_scope("dtx.attn"):
                attn = paged_attention_decode_step(
                    q, pools, layer_idx, cache, cache_pos, positions,
                    window=cfg.sliding_window)
        elif pools is not None and paged_kernel_mt:
            # multi-token in-place: same scatter-then-read-through-the-table
            # scheme with the precomputed attendability operand standing in
            # for the oracle's bias
            from datatunerx_tpu.ops.pallas_paged_attention import (
                paged_attention_multitoken_step,
            )

            with jax.named_scope("dtx.kv_write"):
                pools = kv_cache_write(kv_step, pools, layer_idx, k, v)
            with jax.named_scope("dtx.attn"):
                attn = paged_attention_multitoken_step(
                    q, pools, layer_idx, cache, allow)
        else:
            if pools is not None:
                # dense (scalar/per-slot cursor) or paged (block-table)
                # write + full-width read via the shared cache interface
                # (the gather counts as pool traffic)
                with jax.named_scope("dtx.kv_write"):
                    pools, k_att, v_att = kv_cache_update(
                        kv_step, pools, layer_idx, k, v)
            else:
                k_att, v_att = k, v

            with jax.named_scope("dtx.attn"):
                attn = attention(
                    q, k_att, v_att, bias, impl=att_impl,
                    segment_ids=segment_ids if att_impl == "flash" else None,
                    sliding_window=cfg.sliding_window)
        with jax.named_scope("dtx.attn_out"):
            attn = attn.reshape(B, T, cfg.q_dim)
            x = x + _proj(attn, lp["o_proj"], lget("o_proj"), lora_scale,
                          kget(3), drop, qm, (cfg.q_dim, D), qp,
                          lora_adapter_idx)

        with jax.named_scope("dtx.mlp"):
            h = rms_norm(x, lp["post_attention_layernorm"]["scale"],
                         cfg.rms_norm_eps)
            gate = _proj(h, lp["gate_proj"], lget("gate_proj"), lora_scale,
                         kget(4), drop, qm, (D, F), qp, lora_adapter_idx)
            up = _proj(h, lp["up_proj"], lget("up_proj"), lora_scale,
                       kget(5), drop, qm, (D, F), qp, lora_adapter_idx)
            mlp = _proj(
                jax.nn.silu(gate) * up, lp["down_proj"], lget("down_proj"),
                lora_scale, kget(6), drop, qm, (F, D), qp, lora_adapter_idx,
            )
            x = x + mlp
        return (x, pools), None

    if cfg.remat == "full":
        block = jax.checkpoint(block)
    elif cfg.remat == "dots":
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )

    pools = (None if cache is None
             else {key: cache[key] for key in kv_leaf_keys(cache)})
    xs = (
        params["layers"],
        lora_layers,
        jnp.arange(cfg.num_layers, dtype=jnp.int32),
    )
    # DTX_SCAN_UNROLL: cost-analysis instrumentation (scripts/aot_certify.py).
    # XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    # count, so a compiled train step under-reports flops/bytes by ~L×;
    # compiling at unroll=1 vs unroll=2 and differencing recovers the exact
    # per-layer cost. Default 1 = production behavior, byte-identical program.
    _unroll = int(os.environ.get("DTX_SCAN_UNROLL", "1"))
    with jax.named_scope("dtx.layers"):
        (x, pools), _ = jax.lax.scan(block, (x, pools), xs, unroll=_unroll)

    with jax.named_scope("dtx.unembed"):
        x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        logits = None if skip_logits else lm_logits(params, x, cfg)

    new_cache = None
    if cache is not None:
        new_cache = dict(pools, len=cache["len"] + T, pos=cache_pos)
        if "block_tables" in cache:
            new_cache["block_tables"] = cache["block_tables"]
    if return_hidden:
        # final-norm hidden states, for value heads (reward modelling)
        return logits, new_cache, x
    return logits, new_cache
