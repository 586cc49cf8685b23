"""A decoder whose layers are of several kinds: window and global softmax
attention with KV geometry of their own, latent attention (MLA), linear
attention (KDA) and state-space layers (Mamba-2) in one stack, dense and
sparse-expert feed-forward layers.

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
  the cache and the product; scores scaled by ``head_dim ** -0.5`` (or the
  model's ``attention_multiplier``), softmax in float32; ``rotary_dim`` 0 is
  no rotation at all. A window layer sees key j from query i iff ``0 <= i - j <
  window`` (ops/attention.py:attention_allow), has its own number of KV heads
  and its own RoPE theta, and a learned sink logit per head
  (``attention_sink_bias``).
- latent attention (``mla``, ops/mla.py): q heads ``[nope | rope]`` straight
  from x (``q_proj``) or through a low-rank bottleneck (``q_lora_rank``:
  ``q_b_proj(RMSNorm(q_a_proj(x)))``); ``kv_a_proj`` gives a latent row ``c``
  (RMS-normed) and one rotated key ``kR`` for all heads (interleaved RoPE on
  it and on q's rope lanes); ``[c | kR]`` is the cached row; attention runs
  absorbed over those rows, scaled by ``(nope + rope) ** -0.5``; a sigmoid
  gate per head on the output where the model has one (``head_gate``). Under
  YaRN (``rope_scaling_type`` "yarn", ops/rope.py) the rope lanes' frequencies
  are blended with their own over the factor and the scores are scaled by the
  temperature squared besides (``MlaKind.score_scale``).
- learned selection (an ``mla`` kind with ``index_topk``, ops/dsa.py): an
  indexer beside the mixer (index queries ``wq_b`` from the compressed query,
  one index key a token ``k_norm(wk(x))`` with a LayerNorm's scale and bias,
  a weight a head ``weights_proj(x)``; the first ``rope_dim`` lanes of both
  rotated, interleaved) scores every cached token, and a query attends to its
  ``index_topk`` best only. The index key is cached in a pool of its own.
- linear attention (``kda``, ops/kda.py): q, k, v through a short causal
  convolution and SiLU, q and k L2-normalised per head, no RoPE; a decay per
  key channel from ``f_proj`` and a write strength per head from ``b_proj``
  drive the gated delta rule on a float32 state per head; the read-out is
  RMS-normed per head and gated channel-wise (``g_proj``).
- state space (``ssm``, ops/ssm.py): one ``in_proj`` gives ``[z | x B C |
  dt]``; ``[x | B | C]`` pass a short causal convolution with bias and SiLU;
  ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` per head drive ``S <-
  exp(dt A) S + dt x (x) B`` on a float32 state per head, read out as ``S C + D
  x``; the read-out is gated by ``SiLU(z)`` and THEN RMS-normed, per group.
- dense feed-forward: SwiGLU; expert feed-forward: ops/moe.py, plus one
  shared expert (SwiGLU over every row) where the model has one.
- Granite's scalars, each 1 (None) unless the config says otherwise: the
  embedding times ``embedding_multiplier``, every branch times
  ``residual_multiplier`` before it joins the stream, the logits divided by
  ``logits_scaling``; a tied head reads the embedding.

Param tree (HF leaf names):
  embed_tokens.embedding [V, D];  norm.scale [D];  lm_head.kernel [D, V]
  layers.run<i>.{input_layernorm,post_attention_layernorm}.scale [n, D]
  layers.run<i>.{q,k,v,o}_proj.kernel [n, in, out]
  layers.run<i>.attention_sink_bias [n, H]                (kinds with a sink)
  layers.run<i>.{q,kv_a,kv_b,g,o}_proj.kernel, kv_a_layernorm.scale   (mla runs)
  layers.run<i>.{q_a,q_b}_proj.kernel, q_a_layernorm.scale in place of q_proj
      (a low-rank query); no g_proj without a head gate
  layers.run<i>.indexer.{wq_b,wk,weights_proj}.kernel, indexer.k_norm.{scale,
      bias}                                                (mla runs that select)
  layers.run<i>.{q,k,v,f,b,g,o}_proj.kernel, conv.kernel [n, C, K],
      A_log [n, H], dt_bias [n, H*d], o_norm.scale [n, d_v]            (kda runs)
  layers.run<i>.{in,o}_proj.kernel, conv.{kernel [n, C, K], bias [n, C]},
      A_log, D, dt_bias [n, H], ssm_norm.scale [n, H*P]                (ssm runs)
  layers.run<i>.{gate,up,down}_proj.kernel                (dense runs)
  layers.run<i>.router.kernel [n, D, E_total]             (expert runs)
  layers.run<i>.e_score_correction_bias [n, E_total]
  layers.run<i>.experts.{gate,up,down}_proj [n, E_held, in, out]
  layers.run<i>.shared_expert.{gate,up,down}_proj.kernel  (models with one)
A LoRA tree mirrors it: ``layers.run<i>.<target>.{a,b}``.

Cache, two kinds of leaf in one dict (ops/paged_attention.py tells them apart:
``kv_leaf_keys`` / ``state_leaf_keys``). POOLS hold rows and are moved by
block table: one ``k_<kind>``/``v_<kind>`` pair per softmax-attention kind,
one ``k_mla`` (no v pool) for latent attention and beside it ``k_idx`` (the
index keys) where it selects. STATE leaves hold what a
linear-attention or state-space layer remembers, of constant size per slot,
``[layers of the kind, slots, ...]``, moved by slot: ``state_kda`` (float32
``[.., H, d_k, d_v]``) and ``state_kda_conv`` (the last pre-convolution rows);
``state_ssm`` (float32 ``[.., H, P, N]``) and ``state_ssm_conv``. A slot whose
cursor is 0 reads its state as zero, so admission resets nothing. A pool has
its kind's head count and the two widths, ``[layers of the kind, blocks | rows,
offset | lane, KV * width]``: laid out as the single-kind cache is but for the
last axis, where heads and width are one (a 192-wide head pads to 256 lanes on
the TPU, whose runtime then stores the pool in a layout of its own choosing
and every program converts the whole pool on its way in and out; 8 x 192 =
1536 lanes pad nothing). ``len``, ``pos`` and ``block_tables`` are shared.
Over a paged cache a window layer reads a window-wide view of each slot's
table (ops/paged_attention.py:window_tables), a global layer the full width,
a latent layer's chunk as far as its longest row reaches, in whole steps of
the kind's own width (ops/mla.py:view_steps). A TOKEN step of an
engine that asked for the paged kernels gathers no view of a softmax-attention
kind the decode kernel can express (``in_place_kinds``: no sink, pool rows of
whole lane tiles): it scatters the token's row and the kernel reads the kind's
blocks in place through the block table, as far as each slot's cursor
(ops/pallas_paged_attention.py; the kind's v width, score scale and window go
in as they are). The choice is per kind, from the step's shapes, the cache and
the kind's own fields; every other step and kind reads its view, which is the
kernel's parity oracle.
``moe_stats`` (int32 [2, N_STATS], decode steps and prefill steps apart)
accumulates what the expert layers count, ``dsa_stats`` (the same form,
ops/dsa.py) what the selecting steps do; they wrap, and the engine adds up
differences.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from datatunerx_tpu.models.config import (
    AttentionKind,
    ModelConfig,
    has_recurrent_state,
    kind_layers,
    layer_runs,
    mixer_kinds,
)
from datatunerx_tpu.ops import dsa, kda, mla, moe, pallas_ssm, ssm
from datatunerx_tpu.ops.attention import (
    KVStep,
    cache_positions_update,
    make_causal_bias,
    xla_attention,
)
from datatunerx_tpu.ops.paged_attention import POS_SENTINEL, gathered_positions
from datatunerx_tpu.ops.pallas_paged_attention import (
    paged_attention_decode_step,
    walks_in_place,
)
from datatunerx_tpu.ops.rope import apply_rope, rope_cos_sin


def run_key(i: int) -> str:
    return f"run{i}"


def attn_dims(cfg: ModelConfig, kind) -> dict:
    """in/out widths of the adaptable projections of one mixer kind (a LoRA
    target exists in a run iff its name is here)."""
    D, H = cfg.hidden_size, cfg.num_heads
    if kind.name == "mla":
        q = H * (kind.nope_dim + kind.rope_dim)
        query = ({"q_b_proj": (kind.q_lora_rank, q)} if kind.q_lora_rank
                 else {"q_proj": (D, q)})
        return {**query, "o_proj": (H * kind.v_head_dim, D)}
    if kind.name == "kda":
        return {"q_proj": (D, H * kind.head_dim),
                "k_proj": (D, H * kind.head_dim),
                "v_proj": (D, H * kind.v_head_dim),
                "o_proj": (H * kind.v_head_dim, D)}
    if kind.name == "ssm":  # [z | x B C | dt]
        return {"in_proj": (D, kind.inner + kind.conv_dim + kind.heads),
                "o_proj": (kind.inner, D)}
    return {"q_proj": (D, H * kind.head_dim),
            "k_proj": (D, kind.num_kv_heads * kind.head_dim),
            "v_proj": (D, kind.num_kv_heads * kind.v_head_dim),
            "o_proj": (H * kind.v_head_dim, D)}


def mixer_shapes(cfg: ModelConfig, kind) -> dict:
    """{leaf path: shape of one layer} of a mixer's parameters."""
    D, H = cfg.hidden_size, cfg.num_heads
    out = {(name, "kernel"): dims for name, dims in attn_dims(cfg, kind).items()}
    if kind.name == "mla":
        out[("kv_a_proj", "kernel")] = (D, kind.kv_lora_rank + kind.rope_dim)
        out[("kv_a_layernorm", "scale")] = (kind.kv_lora_rank,)
        out[("kv_b_proj", "kernel")] = (
            kind.kv_lora_rank, H * (kind.nope_dim + kind.v_head_dim))
        if kind.head_gate:
            out[("g_proj", "kernel")] = (D, H)
        if kind.q_lora_rank:
            out[("q_a_proj", "kernel")] = (D, kind.q_lora_rank)
            out[("q_a_layernorm", "scale")] = (kind.q_lora_rank,)
        if kind.index_topk:
            Hi, di = kind.index_heads, kind.index_dim
            out[("indexer", "wq_b", "kernel")] = (kind.q_lora_rank, Hi * di)
            out[("indexer", "wk", "kernel")] = (D, di)
            out[("indexer", "k_norm", "scale")] = (di,)
            out[("indexer", "k_norm", "bias")] = (di,)
            out[("indexer", "weights_proj", "kernel")] = (D, Hi)
    elif kind.name == "kda":
        C = H * (2 * kind.head_dim + kind.v_head_dim)
        out[("conv", "kernel")] = (C, kind.conv_kernel)
        out[("f_proj", "kernel")] = (D, H * kind.head_dim)
        out[("b_proj", "kernel")] = (D, H)
        out[("g_proj", "kernel")] = (D, H * kind.v_head_dim)
        out[("A_log",)] = (H,)
        out[("dt_bias",)] = (H * kind.head_dim,)
        out[("o_norm", "scale")] = (kind.v_head_dim,)
    elif kind.name == "ssm":
        out[("conv", "kernel")] = (kind.conv_dim, kind.conv_kernel)
        out[("conv", "bias")] = (kind.conv_dim,)
        out[("A_log",)] = out[("D",)] = out[("dt_bias",)] = (kind.heads,)
        out[("ssm_norm", "scale")] = (kind.inner,)
    elif kind.sink:
        out[("attention_sink_bias",)] = (H,)
    return out


def run_shapes(cfg: ModelConfig, run) -> dict:
    """{leaf path: shape} of one run's stacked parameters."""
    D, n = cfg.hidden_size, run.count
    out = {("input_layernorm", "scale"): (n, D),
           ("post_attention_layernorm", "scale"): (n, D)}
    for path, shape in mixer_shapes(cfg, run.mixer).items():
        out[path] = (n,) + shape
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
        Fs = cfg.shared_expert_intermediate_size
        if Fs:
            out[("shared_expert", "gate_proj", "kernel")] = (n, D, Fs)
            out[("shared_expert", "up_proj", "kernel")] = (n, D, Fs)
            out[("shared_expert", "down_proj", "kernel")] = (n, Fs, D)
    return out


def _set(tree: dict, path: tuple, value) -> None:
    for part in path[:-1]:
        tree = tree.setdefault(part, {})
    tree[path[-1]] = value


_DRAW = 1 << 19  # elements a single draw makes
_TOGETHER = 1 << 26  # a layer's leaves up to this size share one draw
_CONSTANT = {"scale": 1.0, "A_log": 0.0, "dt_bias": -3.0, "D": 1.0}  # dt_bias: a decay near exp(-0.24) a token (kda), exp(-0.05) (ssm)


def _scale_of(path) -> float:
    if path[-1] == "e_score_correction_bias":
        return 0.1  # a preset's init; benchmark cells draw their own
    return 1.0 if path[-1] == "attention_sink_bias" else 0.02


def _normal(key, shape, scale, dtype):
    """A large leaf is drawn in slices of at most ``_DRAW`` elements under a
    loop: no float32 copy of a stacked leaf is ever alive, and the TPU's
    compiler takes ten seconds over one draw of gigabytes where a slice's
    takes half of one."""
    rows, last = math.prod(shape[:-1]), shape[-1]
    r = max(1, min(rows, _DRAW // last))
    while rows % r:
        r -= 1

    def one(k):
        return (jax.random.normal(k, (r, last), jnp.float32) * scale).astype(dtype)

    if r == rows:
        return one(key).reshape(shape)
    return jax.lax.map(one, jax.random.split(key, rows // r)).reshape(shape)


def _draw_together(key, shapes: dict, dtype) -> dict:
    """{path: normal(0, _scale_of(path)) of shape} for the smaller leaves of
    ONE layer, cut from one flat float32 draw: a generator costs the TPU's
    compiler about a second wherever it stands, and a layer has twenty leaves."""
    total = sum(math.prod(shape) for shape in shapes.values())
    z = _normal(key, (-(-total // _DRAW), _DRAW), 1.0, jnp.float32).reshape(-1)
    out, at = {}, 0
    for path, shape in shapes.items():
        n = math.prod(shape)
        # the barrier keeps XLA from reshaping the WHOLE vector to a leaf's
        # last dimension first (lanes pad a width of 4 thirty-two times over)
        cut = jax.lax.optimization_barrier(z[at:at + n])
        out[path] = (cut.reshape(shape) * _scale_of(path)).astype(dtype)
        at += n
    return out


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    return _init_params(cfg, key, jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_params(cfg: ModelConfig, key: jax.Array, dtype):
    # ONE program for the whole tree, and in it a dozen generators: an engine
    # on a preset compiled a program a leaf, some forty, before
    D, V = cfg.hidden_size, cfg.vocab_size
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = {}
    for i, run in enumerate(layer_runs(cfg)):
        k_run = jax.random.fold_in(k_layers, i)
        shapes = dict(sorted(run_shapes(cfg, run).items()))
        small = {path: shape[1:] for path, shape in shapes.items()
                 if path[-1] not in _CONSTANT and math.prod(shape[1:]) <= _TOGETHER}
        together = jax.lax.map(lambda k, small=small: _draw_together(k, small, dtype),
                               jax.random.split(k_run, run.count))
        tree: dict = {}
        for j, (path, shape) in enumerate(shapes.items()):
            if path in together:
                leaf = together[path]
            elif path[-1] in _CONSTANT:
                leaf = jnp.full(shape, _CONSTANT[path[-1]], dtype)
            else:  # a layer's experts: gigabytes, drawn on their own
                leaf = _normal(jax.random.fold_in(k_run, j), shape, _scale_of(path), dtype)
            _set(tree, path, leaf)
        layers[run_key(i)] = tree
    params = {"embed_tokens": {"embedding": _normal(k_emb, (V, D), 0.02, dtype)},
              "layers": layers, "norm": {"scale": jnp.ones((D,), dtype)}}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": _normal(k_head, (D, V), 0.02, dtype)}
    return params


# ------------------------------------------------------------------- caches

def _leaves(cfg: ModelConfig, lead: tuple, slots: int, dtype) -> dict:
    """The cache's leaves: per mixer kind its pools ``[layers of the kind,
    *lead, row width]`` and its state leaves ``[layers of the kind, slots,
    ...]``; the experts' counters."""
    out = {}
    layers = kind_layers(cfg)
    for name, kind in mixer_kinds(cfg).items():
        for key, width in kind.pools().items():
            out[key] = jnp.zeros((layers[name],) + lead + (width,), dtype)
        for key, (shape, leaf_dtype) in kind.states(cfg).items():
            out[key] = jnp.zeros((layers[name], slots) + shape, leaf_dtype or dtype)
    if cfg.ffn_types is not None and "experts" in cfg.ffn_types:
        out["moe_stats"] = jnp.zeros((2, moe.N_STATS), jnp.int32)
    if "k_idx" in out:
        out["dsa_stats"] = jnp.zeros((2, dsa.N_STATS), jnp.int32)
    return out


def _no_quant(cfg: ModelConfig, quantize) -> None:
    if quantize:
        raise NotImplementedError(
            f"model {cfg.name!r} has a cache leaf per mixer kind; the int8 "
            "cache (kv_quant) does not handle that yet")


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               per_slot: bool = False, quantize: Optional[str] = None):
    _no_quant(cfg, quantize)
    cache = {"len": (jnp.zeros((batch,), jnp.int32) if per_slot
                     else jnp.zeros((), jnp.int32)),
             "pos": jnp.full((batch, max_len), POS_SENTINEL, jnp.int32)}
    cache.update(_leaves(cfg, (batch, max_len), batch, dtype))
    return cache


def init_paged_cache(cfg: ModelConfig, slots: int, num_blocks: int,
                     block_size: int, blocks_per_slot: int,
                     dtype=jnp.bfloat16, quantize: Optional[str] = None):
    _no_quant(cfg, quantize)
    cache = {"len": jnp.zeros((slots,), jnp.int32),
             "pos": jnp.full((num_blocks, block_size), POS_SENTINEL, jnp.int32),
             "block_tables": jnp.full((slots, blocks_per_slot), -1, jnp.int32)}
    cache.update(_leaves(cfg, (num_blocks, block_size), slots, dtype))
    return cache


# ------------------------------------------------------- cache write / read

def in_place_kinds(cfg: ModelConfig, cache, T: int) -> tuple:
    """Names of the attending kinds whose step reads its blocks IN PLACE
    through the paged decode kernel (ops/pallas_paged_attention.py), no view
    of them gathered: a token step over a paged cache of an engine that asked
    for the kernels, and of its softmax-attention kinds those the kernel can
    express: no sink (its softmax has no column but the keys'), pools the
    chip's compiler can cut blocks out of. The kernel takes the kind's v
    width, score scale and window as they are. Every other step and kind
    reads a gathered view, which is the kernel's parity oracle."""
    if not (T == 1 and cache is not None and "block_tables" in cache
            and cfg.paged_kernel):
        return ()
    return tuple(
        name for name, kind in mixer_kinds(cfg).items()
        if isinstance(kind, AttentionKind) and not kind.sink
        and walks_in_place(*(cache[key] for key in kind.pools())))


# jitted so that a program traces and lowers the kernel's body once a kind,
# not once a run of that kind's layers (a second or two each: Granite's four
# attention layers are four runs); XLA inlines the call
_decode_step = jax.jit(paged_attention_decode_step,
                       static_argnames=("window", "scale", "interpret"))


class _View:
    """How one step writes its tokens into a kind's pool and what its
    attention reads back: built once a forward, shared by the kind's layers.
    The targets and the view are ops/attention.py's ``KVStep``, the one the
    single-kind decoder uses; a kind adds its window and its bias. A kind
    whose step reads its blocks in place (``in_place``) writes through the
    same targets and has neither view nor bias."""

    def __init__(self, cache, kind, positions, kv_pos_full, cache_pos, T,
                 in_place=False):
        self.in_place = in_place
        if in_place:  # the kernel walks the whole table and masks by position
            self.step, self.bias = KVStep(cache, T), None
            return
        self.step = KVStep(cache, T, window=kind.window)
        kv_pos = kv_pos_full
        if self.step.paged and self.step.view_tables is not cache["block_tables"]:
            kv_pos = gathered_positions(cache_pos, self.step.view_tables)
        self.bias = make_causal_bias(positions, kv_pos, None,
                                     sliding_window=kind.window)

    def write(self, pool, li, new):
        """``new`` [B, T, KV, w] into layer ``li`` of ``pool``."""
        B, T, KV, w = new.shape
        return self.step.write(
            pool, li, new.astype(pool.dtype).reshape(B, T, KV * w))

    def update(self, pool, li, new):
        """Write ``new`` [B, T, KV, w] into layer ``li`` of ``pool`` and
        return (pool, what attention reads [B, S, KV, w])."""
        B, _, KV, w = new.shape
        pool = self.write(pool, li, new)
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
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)

    kinds = mixer_kinds(cfg)
    attending = {name: kind for name, kind in kinds.items() if kind.pools()}
    rope = {name: rope_cos_sin(positions, kind.rotary_dim, theta=kind.rope_theta,
                               yarn=kind.yarn)
            for name, kind in attending.items() if kind.rotary_dim}
    valid = attention_mask.astype(bool) if attention_mask is not None else None
    views, bias, cache_pos = {}, {}, None
    if cache is None:
        for name, kind in attending.items():
            bias[name] = make_causal_bias(positions, positions, valid,
                                          sliding_window=kind.window)
    else:
        # a token step's kinds that read their blocks in place; where every
        # attending kind does, no position view is gathered either
        in_place = in_place_kinds(cfg, cache, T)
        cache_pos, kv_pos_full = cache_positions_update(
            cache, positions, attention_mask,
            gather=len(in_place) < len(attending))
        for name, kind in attending.items():
            views[name] = _View(cache, kind, positions, kv_pos_full, cache_pos, T,
                                in_place=name in in_place)
            bias[name] = views[name].bias
    # a latent kind's step of several tokens views a paged cache as far as
    # its longest row reaches, in whole steps (ops/mla.py:view_steps): the
    # widths it may take, in table columns, and which of them this step takes
    # (traced; a reach past the table takes the last); None where the view
    # stays the table's. ``len`` is the slot's LANE cursor: under the prefix
    # cache a suffix whose pads lie mid-row still views every lane written
    reach = None
    if cache is not None and "block_tables" in cache and "k_mla" in cache:
        columns, block_size = cache["block_tables"].shape[1], cache["pos"].shape[1]
        widths = mla.view_steps(T, columns, block_size, kinds["mla"].index_topk)
        if widths:
            reach = (widths, block_size,
                     (jnp.max(cache["len"]) + T - 1) // (widths[0] * block_size))
    # a slot at cursor 0 starts from nothing, whatever its state leaves hold
    fresh = (jnp.broadcast_to(cache["len"] == 0, (B,))
             if cache is not None and has_recurrent_state(cfg) else None)

    lora_layers, lora_scale = (None, 0.0)
    if lora is not None:
        lora_params, lora_scale = lora
        lora_layers = lora_params.get("layers", lora_params)
    D, H = cfg.hidden_size, cfg.num_heads
    rows_valid = valid.reshape(B * T) if valid is not None else None

    def attention_mixer(kind, h, lp, proj, leaves, li):
        view = views.get(kind.name)
        pool_k, pool_v = leaves
        with jax.named_scope("dtx.qkv"):
            q = proj(h, "q_proj").reshape(B, T, H, kind.head_dim)
            k = proj(h, "k_proj").reshape(B, T, kind.num_kv_heads, kind.head_dim)
            v = proj(h, "v_proj").reshape(B, T, kind.num_kv_heads, kind.v_head_dim)
            if kind.rotary_dim:
                q = apply_rope(q, *rope[kind.name])
                k = apply_rope(k, *rope[kind.name])
            if kind.value_scale != 1.0:
                v = v * jnp.asarray(kind.value_scale, v.dtype)
        if view is not None and view.in_place:
            # scatter the token's row into its blocks, then the kernel reads
            # them back through the block table: no view of this kind exists
            with jax.named_scope("dtx.kv_write"):
                pool_k = view.write(pool_k, li, k)
                pool_v = view.write(pool_v, li, v)
            with jax.named_scope("dtx.attn"):
                attn = _decode_step(
                    q, {"k": pool_k, "v": pool_v}, li,
                    {key: cache[key] for key in ("block_tables", "len")},
                    cache_pos, positions, window=kind.window, scale=kind.scale)
            return attn.reshape(B, T, H * kind.v_head_dim), (pool_k, pool_v)
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
                sink=lp["attention_sink_bias"] if kind.sink else None,
                scale=kind.scale)
        return attn.reshape(B, T, H * kind.v_head_dim), (pool_k, pool_v)

    def mla_mixer(kind, h, lp, proj, leaves, li):
        cos, sin = rope["mla"]
        view = views.get("mla")
        rank = kind.kv_lora_rank
        with jax.named_scope("dtx.qkv"):
            if kind.q_lora_rank:
                c_q = rms_norm(_proj(h, lp["q_a_proj"], None, 0.0),
                               lp["q_a_layernorm"]["scale"], cfg.rms_norm_eps)
                q = proj(c_q, "q_b_proj")
            else:
                q = proj(h, "q_proj")
            q = q.reshape(B, T, H, kind.nope_dim + kind.rope_dim)
            q_nope = q[..., :kind.nope_dim]
            q_rope = mla.rope_interleaved(q[..., kind.nope_dim:], cos, sin)
            row = _proj(h, lp["kv_a_proj"], None, 0.0)
            c = rms_norm(row[..., :rank], lp["kv_a_layernorm"]["scale"],
                         cfg.rms_norm_eps)
            k_rope = mla.rope_interleaved(row[..., None, rank:], cos, sin)[:, :, 0]
            # as wide as the pool's rows: lanes past [c | kR] are zeros
            tail = kind.pools()["k_mla"] - rank - kind.rope_dim

            def widen(a):
                return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, tail)]) if tail else a

            row = widen(jnp.concatenate([c, k_rope], axis=-1))
            if kind.head_gate:
                gate = jax.nn.sigmoid(
                    _proj(h, lp["g_proj"], None, 0.0).astype(jnp.float32))
        if kind.index_topk:
            ip, rot = lp["indexer"], kind.index_rope_dim

            def lead_rotated(a):  # [B, T, heads, d]: the first ``rot`` lanes rotated
                return jnp.concatenate(
                    [mla.rope_interleaved(a[..., :rot], cos, sin), a[..., rot:]], axis=-1)

            with jax.named_scope("dtx.dsa_index"):
                k_idx = dsa.key_norm(_proj(h, ip["wk"], None, 0.0),
                                     ip["k_norm"]["scale"], ip["k_norm"]["bias"])
                k_idx = lead_rotated(k_idx[:, :, None])[:, :, 0]
        if view is not None:
            with jax.named_scope("dtx.kv_write"):
                pool = view.step.write(leaves[0], li, row.astype(leaves[0].dtype))
                leaves = (pool,) + leaves[1:]
                if kind.index_topk:
                    leaves = (pool, view.step.write(
                        leaves[1], li, k_idx.astype(leaves[1].dtype)))
        # a chunk's view is as wide as its context reaches, a branch of static
        # width a count of steps; every other step's is the table's
        widths, block_size, taken = reach or ((), 0, None)
        if kind.index_topk and (
                widths or dsa.selection_path(T, bias["mla"].shape[-1], kind.index_topk) != "all"):
            with jax.named_scope("dtx.dsa_index"):
                q_idx = lead_rotated(_proj(c_q, ip["wq_b"], None, 0.0).reshape(
                    B, T, kind.index_heads, kind.index_dim))
                w_idx = _proj(h, ip["weights_proj"], None, 0.0).astype(jnp.float32) * (
                    kind.index_heads * kind.index_dim) ** -0.5
        wkb, wvb = mla.split_kv_b(lp["kv_b_proj"]["kernel"], H, kind.nope_dim)
        with jax.named_scope("dtx.mla_absorb"):
            q_lat = widen(jnp.concatenate([mla.absorb_query(q_nope, wkb), q_rope], axis=-1))

        def attend(leaves, columns=None):
            """The step's attention over its view's first ``columns`` table
            columns (None: all of the view). Which tokens its queries read:
            every one the causal bias lets through, or (a selecting kind over
            a view wider than its selection) the indexer's picks among them.
            The pools come in and only the output leaves: a branch that
            returned a pool would have it copied."""
            mask = bias["mla"]
            if columns is not None:
                mask = mask[..., :columns * block_size]
            path = dsa.selection_path(T, mask.shape[-1], kind.index_topk)
            if path != "all":
                with jax.named_scope("dtx.dsa_index"):
                    keys = (view.step.read(leaves[1], li, columns).astype(k_idx.dtype)
                            if view is not None else k_idx)
                    scores = dsa.index_scores(q_idx, w_idx, keys)
                with jax.named_scope("dtx.dsa_select"):
                    visible = mask[:, 0] == 0
                    if path == "mask":  # the set over the view: no lanes, no sort
                        real = dsa.top_mask(scores, visible, kind.index_topk)
                    else:
                        lanes, real = dsa.top_lanes(scores, visible, kind.index_topk)
                    mask = jnp.where(real, 0.0, jnp.finfo(mask.dtype).min)[:, None]
            if path == "gather":  # one token a slot: its chosen rows and no others
                with jax.named_scope("dtx.dsa_gather"):
                    rows = dsa.gather_rows(
                        leaves[0], li, lanes[:, 0],
                        view.step.view_tables if view.step.paged else None)
                    rows = rows.astype(row.dtype)[:, :, None, :]
            elif view is not None:
                with jax.named_scope("dtx.kv_write"):
                    rows = view.step.read(leaves[0], li, columns).astype(row.dtype)[:, :, None, :]
            else:
                rows = row[:, :, None, :]
            with jax.named_scope("dtx.attn"):
                o_lat = xla_attention(
                    q_lat, rows, rows[..., :rank], mask, scale=kind.score_scale)
            with jax.named_scope("dtx.mla_absorb"):
                return mla.expand_value(o_lat, wvb)

        if widths:
            o = jax.lax.switch(
                taken, [functools.partial(attend, columns=n) for n in widths], leaves)
        else:
            o = attend(leaves)
        if kind.head_gate:
            with jax.named_scope("dtx.mla_absorb"):
                o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        return o.reshape(B, T, H * kind.v_head_dim), leaves

    def kda_mixer(kind, h, lp, proj, leaves, li):
        d, dv = kind.head_dim, kind.v_head_dim
        with jax.named_scope("dtx.qkv"):
            qkv = jnp.concatenate(
                [proj(h, name) for name in ("q_proj", "k_proj", "v_proj")], axis=-1)
            f = _proj(h, lp["f_proj"], None, 0.0).reshape(B, T, H, d)
            beta = jax.nn.sigmoid(
                _proj(h, lp["b_proj"], None, 0.0).astype(jnp.float32))
            out_gate = jax.nn.sigmoid(
                _proj(h, lp["g_proj"], None, 0.0).astype(jnp.float32))
        with jax.named_scope("dtx.kda_conv"):
            conv_state = None
            if leaves:
                conv_state = jnp.where(fresh[:, None, None], 0, leaves[1][li])
            y, conv_state = kda.short_conv(qkv, lp["conv"]["kernel"], conv_state, valid)
            q = kda.l2_normalize(y[..., :H * d].reshape(B, T, H, d)) * d ** -0.5
            k = kda.l2_normalize(y[..., H * d:2 * H * d].reshape(B, T, H, d))
            v = y[..., 2 * H * d:].reshape(B, T, H, dv)
            g = kda.gate(f, lp["A_log"], lp["dt_bias"].reshape(H, d), kind.lower_bound)
            if valid is not None:  # a pad moves nothing
                g = jnp.where(valid[:, :, None, None], g, 0.0)
                beta = jnp.where(valid[:, :, None], beta, 0.0)
        with jax.named_scope("dtx.kda_state"):
            if leaves:
                state = jnp.where(fresh[:, None, None, None], 0.0, leaves[0][li])
            else:
                state = jnp.zeros((B, H, d, dv), jnp.float32)
            if T == 1:
                o, state = kda.state_step(
                    state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                o, state = kda.chunk_states(state, q, k, v, g, beta)
            if leaves:
                leaves = (leaves[0].at[li].set(state),
                          leaves[1].at[li].set(conv_state.astype(leaves[1].dtype)))
        with jax.named_scope("dtx.kda_out"):
            o = rms_norm(o, lp["o_norm"]["scale"], cfg.rms_norm_eps)
            o = (o.astype(jnp.float32)
                 * out_gate.reshape(B, T, H, dv)).astype(h.dtype)
        return o.reshape(B, T, H * dv), leaves

    def ssm_mixer(kind, h, lp, proj, leaves, li):
        Hs, P, N, G = kind.heads, kind.head_dim, kind.state, kind.groups
        inner = kind.inner
        with jax.named_scope("dtx.qkv"):
            zxbcdt = proj(h, "in_proj")
        with jax.named_scope("dtx.ssm_conv"):
            z = zxbcdt[..., :inner]
            conv_state = None
            if leaves:
                conv_state = jnp.where(fresh[:, None, None], 0, leaves[1][li])
            y, conv_state = kda.short_conv(
                zxbcdt[..., inner:inner + kind.conv_dim], lp["conv"]["kernel"],
                conv_state, valid, bias=lp["conv"]["bias"])
            xs = y[..., :inner].reshape(B, T, Hs, P)
            Bm = y[..., inner:inner + G * N].reshape(B, T, G, N)
            Cm = y[..., inner + G * N:].reshape(B, T, G, N)
            dt, dA = ssm.discretize(zxbcdt[..., -Hs:], lp["dt_bias"], lp["A_log"])
            if valid is not None:  # a pad moves nothing
                dt = jnp.where(valid[:, :, None], dt, 0.0)
                dA = jnp.where(valid[:, :, None], dA, 0.0)
        with jax.named_scope("dtx.ssm_state"):
            _, th = pallas_ssm.step_kernel(leaves[0] if leaves else None, T)
            if th:  # the leaf whole, stepped in place: no layer sliced out and set back
                o, stepped = pallas_ssm.ssm_step(
                    leaves[0], li, fresh, xs, Bm, Cm, dt, dA, lp["D"], th=th)
            else:
                if leaves:
                    state = jnp.where(fresh[:, None, None, None], 0.0, leaves[0][li])
                else:
                    state = jnp.zeros((B, Hs, P, N), jnp.float32)
                if T == 1:
                    o, state = ssm.state_step(
                        state, xs[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], dA[:, 0], lp["D"])
                    o = o[:, None]
                else:
                    o, state = ssm.chunk_states(state, xs, Bm, Cm, dt, dA, lp["D"])
                stepped = leaves[0].at[li].set(state) if leaves else None
            if leaves:
                leaves = (stepped,
                          leaves[1].at[li].set(conv_state.astype(leaves[1].dtype)))
        with jax.named_scope("dtx.ssm_out"):
            # the gate BEFORE the norm, which runs over each group's channels
            o = o.reshape(B, T, inner) * jax.nn.silu(z.astype(jnp.float32))
            o = rms_norm(o.reshape(B, T, G, inner // G),
                         lp["ssm_norm"]["scale"].reshape(G, inner // G),
                         cfg.rms_norm_eps)
        return o.reshape(B, T, inner).astype(h.dtype), leaves

    mixers = {"mla": mla_mixer, "kda": kda_mixer, "ssm": ssm_mixer}
    # a branch joins the stream times the model's residual multiplier
    if cfg.residual_multiplier == 1.0:
        branch = lambda y: y  # noqa: E731
    else:
        branch = lambda y: y * jnp.asarray(cfg.residual_multiplier, y.dtype)  # noqa: E731

    def make_block(run, experts):
        kind = run.mixer
        mixer = mixers.get(kind.name, attention_mixer)

        def block(carry, scanned):
            x, leaves, stats = carry
            lp, ll, li, lr = scanned
            lget = (lambda name: ll.get(name)) if ll else (lambda name: None)

            def proj(h, name):
                return _proj(h, lp[name], lget(name), lora_scale,
                             lora_idx=lora_adapter_idx)

            with jax.named_scope("dtx.qkv"):
                h = rms_norm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
            mixed, leaves = mixer(kind, h, lp, proj, leaves, li)
            with jax.named_scope("dtx.attn_out"):
                x = x + branch(proj(mixed, "o_proj"))
            if run.ffn == "dense":
                with jax.named_scope("dtx.mlp"):
                    h = rms_norm(x, lp["post_attention_layernorm"]["scale"],
                                 cfg.rms_norm_eps)
                    x = x + branch(proj(jax.nn.silu(proj(h, "gate_proj"))
                                        * proj(h, "up_proj"), "down_proj"))
            else:
                with jax.named_scope("dtx.moe_route"):
                    h = rms_norm(x, lp["post_attention_layernorm"]["scale"],
                                 cfg.rms_norm_eps)
                y, counts = moe.expert_layer(
                    h.reshape(B * T, D), rows_valid, dict(lp, experts=experts),
                    experts_total=cfg.experts_total,
                    experts_held=cfg.experts_held, first_held=cfg.first_held,
                    top_k=cfg.experts_per_token, normalize=cfg.norm_topk_prob,
                    scaling=cfg.routed_scaling_factor, layer=lr,
                    n_group=cfg.n_group, topk_group=cfg.topk_group)
                if "shared_expert" in lp:
                    with jax.named_scope("dtx.moe_shared"):
                        sp = lp["shared_expert"]
                        dot = lambda a, name: _proj(a, sp[name], None, 0.0)  # noqa: E731
                        x = x + branch(dot(jax.nn.silu(dot(h, "gate_proj"))
                                           * dot(h, "up_proj"), "down_proj"))
                with jax.named_scope("dtx.moe_combine"):
                    x = x + branch(y.reshape(B, T, D))
                    stats = stats + counts
            return (x, leaves, stats), None

        return block

    new_cache = dict(cache) if cache is not None else None
    stats = jnp.zeros((moe.N_STATS,), jnp.int32)
    with jax.named_scope("dtx.layers"):
        for i, run in enumerate(layer_runs(cfg)):
            kind = run.mixer
            keys = tuple(kind.pools()) + tuple(kind.states(cfg))
            leaves = (tuple(new_cache[key] for key in keys) if cache is not None
                      else (None,) * len(kind.pools()))
            # the run's experts go to every layer whole, not a slice a
            # layer (ops/moe.py:grouped_swiglu); everything else is scanned
            scanned = dict(params["layers"][run_key(i)])
            experts = scanned.pop("experts", None)
            steps = jnp.arange(run.count, dtype=jnp.int32)
            xs = (scanned, lora_layers.get(run_key(i)) if lora_layers else None,
                  run.kind_start + steps, steps)
            (x, leaves, stats), _ = jax.lax.scan(
                make_block(run, experts), (x, leaves, stats), xs)
            if cache is not None:
                new_cache.update(zip(keys, leaves))

    with jax.named_scope("dtx.unembed"):
        x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        logits = None if skip_logits else lm_logits(params, x, cfg)
        if logits is not None and cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling

    if cache is not None:
        new_cache["len"] = cache["len"] + T
        new_cache["pos"] = cache_pos
        if "moe_stats" in cache:
            new_cache["moe_stats"] = cache["moe_stats"].at[0 if T == 1 else 1].add(stats)
        if "dsa_stats" in cache:
            new_cache["dsa_stats"] = cache["dsa_stats"].at[0 if T == 1 else 1].add(
                dsa.step_stats(positions, valid, kinds["mla"].index_topk))
    if return_hidden:
        return logits, new_cache, x
    return logits, new_cache
