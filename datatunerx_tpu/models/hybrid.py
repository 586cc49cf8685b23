"""A decoder whose layers are of several kinds: window and global attention
layers with KV geometry of their own in one stack, dense and sparse-expert
feed-forward layers.

``models/config.py:layer_runs`` describes the model as runs of like layers;
this module stacks each run's parameters ``[n, ...]`` and scans it, so the
program holds one block per run, not per layer. ``models/llama.py``'s entry
points (``init_params``, ``init_cache``, ``forward``) hand a model to this
module when its config names layer kinds (``cfg.hybrid``); nothing here asks
for a model by name.

Every layer is a pre-norm residual block (RMSNorm, no bias anywhere):

- attention, both kinds: q and k heads of width ``head_dim``, v heads of
  width ``v_head_dim``; RoPE on the first ``rotary_dim`` dims of each q and k
  head (half-split), the rest pass through; ``v <- value_scale * v`` before
  the cache and the product; scores scaled by ``head_dim ** -0.5``, softmax
  in float32. A window layer sees key j from query i iff ``0 <= i - j <
  window`` (ops/attention.py:attention_allow), has its own number of KV heads
  and its own RoPE theta, and a learned sink logit per head
  (``attention_sink_bias``).
- dense feed-forward: SwiGLU; expert feed-forward: ops/moe.py.

Param tree (HF leaf names):
  embed_tokens.embedding [V, D];  norm.scale [D];  lm_head.kernel [D, V]
  layers.run<i>.{input_layernorm,post_attention_layernorm}.scale [n, D]
  layers.run<i>.{q,k,v,o}_proj.kernel [n, in, out]
  layers.run<i>.attention_sink_bias [n, H]                (kinds with a sink)
  layers.run<i>.{gate,up,down}_proj.kernel                (dense runs)
  layers.run<i>.router.kernel [n, D, E_total]             (expert runs)
  layers.run<i>.e_score_correction_bias [n, E_total]
  layers.run<i>.experts.{gate,up,down}_proj [n, E_held, in, out]
A LoRA tree mirrors it: ``layers.run<i>.<target>.{a,b}``.

Cache: one ``k_<kind>``/``v_<kind>`` pool per attention kind, with that
kind's head count and the two widths, ``[layers of the kind, blocks | rows,
offset | lane, KV * width]``: laid out as the single-kind cache is but for the
last axis, where heads and width are one (a 192-wide head pads to 256 lanes on
the TPU, whose runtime then stores the pool in a layout of its own choosing
and every program converts the whole pool on its way in and out; 8 x 192 =
1536 lanes pad nothing). ``len``, ``pos`` and ``block_tables`` are shared.
Over a paged cache a window layer reads a window-wide view of each slot's
table (ops/paged_attention.py:window_tables), a global layer the full width.
``moe_stats`` (int32 [2, N_STATS], decode steps and prefill steps apart)
accumulates what the expert layers count; it wraps, and the engine adds up
differences.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import (
    ModelConfig,
    attention_kinds,
    kind_layers,
    layer_runs,
)
from datatunerx_tpu.ops import moe
from datatunerx_tpu.ops.attention import (
    KVStep,
    cache_positions_update,
    make_causal_bias,
    xla_attention,
)
from datatunerx_tpu.ops.paged_attention import POS_SENTINEL, gathered_positions
from datatunerx_tpu.ops.rope import apply_rope, rope_cos_sin


def run_key(i: int) -> str:
    return f"run{i}"


def attn_dims(cfg: ModelConfig, kind) -> dict:
    """in/out widths of one attention kind's projections."""
    D = cfg.hidden_size
    return {"q_proj": (D, cfg.num_heads * kind.head_dim),
            "k_proj": (D, kind.num_kv_heads * kind.head_dim),
            "v_proj": (D, kind.num_kv_heads * kind.v_head_dim),
            "o_proj": (cfg.num_heads * kind.v_head_dim, D)}


def run_shapes(cfg: ModelConfig, run) -> dict:
    """{leaf path: shape} of one run's stacked parameters."""
    D, n = cfg.hidden_size, run.count
    out = {("input_layernorm", "scale"): (n, D),
           ("post_attention_layernorm", "scale"): (n, D)}
    for name, (d_in, d_out) in attn_dims(cfg, run.attn).items():
        out[(name, "kernel")] = (n, d_in, d_out)
    if run.attn.sink:
        out[("attention_sink_bias",)] = (n, cfg.num_heads)
    if run.ffn == "dense":
        F = cfg.intermediate_size
        out[("gate_proj", "kernel")] = (n, D, F)
        out[("up_proj", "kernel")] = (n, D, F)
        out[("down_proj", "kernel")] = (n, F, D)
    else:
        E, Eh, F = cfg.experts_total, cfg.experts_held, cfg.expert_intermediate_size
        out[("router", "kernel")] = (n, D, E)
        out[("e_score_correction_bias",)] = (n, E)
        out[("experts", "gate_proj")] = (n, Eh, D, F)
        out[("experts", "up_proj")] = (n, Eh, D, F)
        out[("experts", "down_proj")] = (n, Eh, F, D)
    return out


def _set(tree: dict, path: tuple, value) -> None:
    for part in path[:-1]:
        tree = tree.setdefault(part, {})
    tree[path[-1]] = value


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, scale, dtype):
    # one fused program a leaf: no float32 copy of a stacked leaf stays alive
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    D, V = cfg.hidden_size, cfg.vocab_size
    dtype = jnp.dtype(dtype)

    def dense(k, shape, scale=0.02):
        return _normal(k, tuple(shape), scale, dtype)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = {}
    for i, run in enumerate(layer_runs(cfg)):
        tree: dict = {}
        for j, (path, shape) in enumerate(sorted(run_shapes(cfg, run).items())):
            k = jax.random.fold_in(jax.random.fold_in(k_layers, i), j)
            if path[-1] == "scale":
                leaf = jnp.ones(shape, dtype)
            elif path[-1] == "e_score_correction_bias":
                leaf = dense(k, shape, 0.1)  # a preset's init; benchmark cells draw their own
            elif path[-1] == "attention_sink_bias":
                leaf = dense(k, shape, 1.0)
            else:
                leaf = dense(k, shape)
            _set(tree, path, leaf)
        layers[run_key(i)] = tree
    params = {"embed_tokens": {"embedding": dense(k_emb, (V, D))},
              "layers": layers, "norm": {"scale": jnp.ones((D,), dtype)}}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense(k_head, (D, V))}
    return params


# ------------------------------------------------------------------- caches

def _pools(cfg: ModelConfig, lead: tuple, dtype) -> dict:
    """One k/v pool per attention kind: ``[layers of the kind, *lead, KV * width]``."""
    out = {}
    layers = kind_layers(cfg)
    for name, kind in attention_kinds(cfg).items():
        shape = (layers[name],) + lead
        out[f"k_{name}"] = jnp.zeros(
            shape + (kind.num_kv_heads * kind.head_dim,), dtype)
        out[f"v_{name}"] = jnp.zeros(
            shape + (kind.num_kv_heads * kind.v_head_dim,), dtype)
    if cfg.ffn_types is not None and "experts" in cfg.ffn_types:
        out["moe_stats"] = jnp.zeros((2, moe.N_STATS), jnp.int32)
    return out


def _no_quant(cfg: ModelConfig, quantize) -> None:
    if quantize:
        raise NotImplementedError(
            f"model {cfg.name!r} has a KV pool per attention kind; the int8 "
            "cache (kv_quant) does not handle that yet")


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               per_slot: bool = False, quantize: Optional[str] = None):
    _no_quant(cfg, quantize)
    cache = {"len": (jnp.zeros((batch,), jnp.int32) if per_slot
                     else jnp.zeros((), jnp.int32)),
             "pos": jnp.full((batch, max_len), POS_SENTINEL, jnp.int32)}
    cache.update(_pools(cfg, (batch, max_len), dtype))
    return cache


def init_paged_cache(cfg: ModelConfig, slots: int, num_blocks: int,
                     block_size: int, blocks_per_slot: int,
                     dtype=jnp.bfloat16, quantize: Optional[str] = None):
    _no_quant(cfg, quantize)
    cache = {"len": jnp.zeros((slots,), jnp.int32),
             "pos": jnp.full((num_blocks, block_size), POS_SENTINEL, jnp.int32),
             "block_tables": jnp.full((slots, blocks_per_slot), -1, jnp.int32)}
    cache.update(_pools(cfg, (num_blocks, block_size), dtype))
    return cache


# ------------------------------------------------------- cache write / read

class _View:
    """How one step writes its tokens into a kind's pool and what its
    attention reads back: built once a forward, shared by the kind's layers.
    The targets and the view are ops/attention.py's ``KVStep``, the one the
    single-kind decoder uses; a kind adds its window and its bias."""

    def __init__(self, cache, kind, positions, kv_pos_full, cache_pos, T):
        self.step = KVStep(cache, T, window=kind.window)
        kv_pos = kv_pos_full
        if self.step.paged and self.step.view_tables is not cache["block_tables"]:
            kv_pos = gathered_positions(cache_pos, self.step.view_tables)
        self.bias = make_causal_bias(positions, kv_pos, None,
                                     sliding_window=kind.window)

    def update(self, pool, li, new):
        """Write ``new`` [B, T, KV, w] into layer ``li`` of ``pool`` and
        return (pool, what attention reads [B, S, KV, w])."""
        B, T, KV, w = new.shape
        pool = self.step.write(
            pool, li, new.astype(pool.dtype).reshape(B, T, KV * w))
        return pool, self.step.read(pool, li).reshape(B, -1, KV, w)


# ------------------------------------------------------------------ forward

def forward(params, tokens, cfg: ModelConfig, *, positions=None,
            attention_mask=None, cache=None, lora=None, lora_adapter_idx=None,
            compute_dtype=None, return_hidden: bool = False,
            skip_logits: bool = False):
    """As ``models/llama.py:forward`` for a model of several layer kinds:
    (logits [B, T, V] float32, new cache | None[, hidden])."""
    from datatunerx_tpu.models.llama import _proj, lm_logits, rms_norm

    if cfg.quantization:
        raise NotImplementedError(
            f"model {cfg.name!r}: quantized base weights are not handled for "
            "a model of several layer kinds yet")
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = params["embed_tokens"]["embedding"][tokens]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)

    kinds = attention_kinds(cfg)
    rope = {name: rope_cos_sin(positions, kind.rotary_dim, theta=kind.rope_theta)
            for name, kind in kinds.items()}
    valid = attention_mask.astype(bool) if attention_mask is not None else None
    views, bias, cache_pos = {}, {}, None
    if cache is None:
        for name, kind in kinds.items():
            bias[name] = make_causal_bias(positions, positions, valid,
                                          sliding_window=kind.window)
    else:
        cache_pos, kv_pos_full = cache_positions_update(
            cache, positions, attention_mask)
        for name, kind in kinds.items():
            views[name] = _View(cache, kind, positions, kv_pos_full, cache_pos, T)
            bias[name] = views[name].bias

    lora_layers, lora_scale = (None, 0.0)
    if lora is not None:
        lora_params, lora_scale = lora
        lora_layers = lora_params.get("layers", lora_params)
    D, H = cfg.hidden_size, cfg.num_heads
    rows_valid = valid.reshape(B * T) if valid is not None else None

    def make_block(run, experts):
        kind = run.attn
        cos, sin = rope[kind.name]
        view = views.get(kind.name)

        def block(carry, scanned):
            x, pool_k, pool_v, stats = carry
            lp, ll, li, lr = scanned
            lget = (lambda name: ll.get(name)) if ll else (lambda name: None)

            def proj(h, name):
                return _proj(h, lp[name], lget(name), lora_scale,
                             lora_idx=lora_adapter_idx)

            with jax.named_scope("dtx.qkv"):
                h = rms_norm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
                q = proj(h, "q_proj").reshape(B, T, H, kind.head_dim)
                k = proj(h, "k_proj").reshape(B, T, kind.num_kv_heads, kind.head_dim)
                v = proj(h, "v_proj").reshape(B, T, kind.num_kv_heads, kind.v_head_dim)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                if kind.value_scale != 1.0:
                    v = v * jnp.asarray(kind.value_scale, v.dtype)
            if view is not None:
                with jax.named_scope("dtx.kv_write"):
                    pool_k, k_att = view.update(pool_k, li, k)
                    pool_v, v_att = view.update(pool_v, li, v)
                    k_att, v_att = k_att.astype(k.dtype), v_att.astype(v.dtype)
            else:
                k_att, v_att = k, v
            with jax.named_scope("dtx.attn"):
                attn = xla_attention(
                    q, k_att, v_att, bias[kind.name],
                    sink=lp["attention_sink_bias"] if kind.sink else None)
            with jax.named_scope("dtx.attn_out"):
                x = x + proj(attn.reshape(B, T, H * kind.v_head_dim), "o_proj")
            if run.ffn == "dense":
                with jax.named_scope("dtx.mlp"):
                    h = rms_norm(x, lp["post_attention_layernorm"]["scale"],
                                 cfg.rms_norm_eps)
                    x = x + proj(jax.nn.silu(proj(h, "gate_proj"))
                                 * proj(h, "up_proj"), "down_proj")
            else:
                with jax.named_scope("dtx.moe_route"):
                    h = rms_norm(x, lp["post_attention_layernorm"]["scale"],
                                 cfg.rms_norm_eps)
                y, counts = moe.expert_layer(
                    h.reshape(B * T, D), rows_valid, dict(lp, experts=experts),
                    experts_total=cfg.experts_total,
                    experts_held=cfg.experts_held, first_held=cfg.first_held,
                    top_k=cfg.experts_per_token, normalize=cfg.norm_topk_prob,
                    scaling=cfg.routed_scaling_factor, layer=lr)
                with jax.named_scope("dtx.moe_combine"):
                    x = x + y.reshape(B, T, D)
                    stats = stats + counts
            return (x, pool_k, pool_v, stats), None

        return block

    new_cache = dict(cache) if cache is not None else None
    stats = jnp.zeros((moe.N_STATS,), jnp.int32)
    with jax.named_scope("dtx.layers"):
        for i, run in enumerate(layer_runs(cfg)):
            name = run.attn.name
            pools = ((new_cache[f"k_{name}"], new_cache[f"v_{name}"])
                     if cache is not None else (None, None))
            # the run's experts go to every layer whole, not a slice a
            # layer (ops/moe.py:grouped_swiglu); everything else is scanned
            scanned = dict(params["layers"][run_key(i)])
            experts = scanned.pop("experts", None)
            steps = jnp.arange(run.count, dtype=jnp.int32)
            xs = (scanned, lora_layers.get(run_key(i)) if lora_layers else None,
                  run.kind_start + steps, steps)
            (x, pool_k, pool_v, stats), _ = jax.lax.scan(
                make_block(run, experts), (x,) + pools + (stats,), xs)
            if cache is not None:
                new_cache[f"k_{name}"], new_cache[f"v_{name}"] = pool_k, pool_v

    with jax.named_scope("dtx.unembed"):
        x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        logits = None if skip_logits else lm_logits(params, x, cfg)

    if cache is not None:
        new_cache["len"] = cache["len"] + T
        new_cache["pos"] = cache_pos
        if "moe_stats" in cache:
            new_cache["moe_stats"] = cache["moe_stats"].at[0 if T == 1 else 1].add(stats)
    if return_hidden:
        return logits, new_cache, x
    return logits, new_cache
