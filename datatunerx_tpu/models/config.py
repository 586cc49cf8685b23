"""Model configurations for the llama-family decoder.

The reference platform targets Llama-2-7B LoRA SFT (reference
pkg/util/generate/generate.go:21, internal/controller/finetune/finetunejob_controller.go:310)
and its BASELINE configs add Mistral-7B (full-param FSDP) and Qwen1.5-14B (QLoRA).
All three are the same decoder family: RMSNorm + RoPE + GQA + SwiGLU, differing in
dims, kv-head count, qkv bias (Qwen) and sliding window (Mistral) — so one
implementation with a config dataclass covers the model inventory.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    # RoPE scaling: reference exposes --rope_scaling {linear,dynamic}
    # (reference cmd/tuning/parser.py:57-60); None disables.
    rope_scaling_type: Optional[str] = None
    rope_scaling_factor: float = 1.0
    # "yarn" (latent-attention layers only, ops/rope.py): the length the
    # frequencies were trained at, the turns over it between which a pair's
    # frequency is blended, and the two temperatures (``YarnScaling``)
    rope_original_max_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen1.5 uses bias on q/k/v projections
    sliding_window: Optional[int] = None  # Mistral local attention window
    # remat ("gradient checkpointing", reference cmd/tuning/train.py:205) policy:
    # "none" | "full" | "dots" (checkpoint_dots_with_no_batch_dims)
    remat: str = "full"
    # attention implementation: "xla" (einsum softmax) | "flash" (Pallas) |
    # "ring" (sequence-parallel ring attention over a mesh axis)
    attention_impl: str = "xla"
    # base-weight quantization: None | "int8" | "int4"/"nf4" (QLoRA).
    # Replaces bitsandbytes (reference cmd/tuning/train.py:224-234).
    quantization: Optional[str] = None
    quant_impl: str = "xla"  # "xla" | "pallas"
    # paged-decode attention kernel (ops/pallas_paged_attention.py): True
    # routes single-token decode over a block-table cache through the Pallas
    # in-place kernel instead of the XLA gather; engages only when the cache
    # is paged and T == 1 (sliding_window goes to the kernel; multi-token
    # steps take their kernel only where it is None, and everything else
    # keeps the gather oracle). Resolved by the serving engine from its
    # --paged_kernel auto|on|off flag; training never sets it.
    paged_kernel: bool = False
    # ---- layers of different kinds in one model (models/hybrid.py). Every
    # default is today's single kind: all layers attend alike (the fields
    # above) and feed forward through one dense SwiGLU. ``layer_types`` names
    # each layer's mixer ("global" | "window" softmax attention, "mla" latent
    # attention, "kda" linear attention, "ssm" state space) and ``ffn_types``
    # its feed-forward
    # ("dense" | "experts"); a model that sets either is run by runs of like
    # layers (``layer_runs``), each stacked and scanned. The fields above keep
    # their published meaning for such a model: num_kv_heads/rope_theta are
    # the global layers' (rope_theta the MLA layers' too), sliding_window the
    # window layers' window.
    layer_types: Optional[tuple] = None
    ffn_types: Optional[tuple] = None
    v_head_dim: Optional[int] = None  # value/output head width; head_dim if None
    # RoPE on the first int(head_dim*f) dims; 0: no rotation at all (a model
    # whose order comes from its recurrent layers)
    partial_rotary_factor: float = 1.0
    attention_value_scale: float = 1.0  # v <- scale * v before cache and product
    window_num_kv_heads: Optional[int] = None  # KV heads of window layers
    window_rope_theta: Optional[float] = None
    window_sink: bool = False  # learned per-head sink logit in window layers
    # sparse experts: the router scores all ``experts_total``; this chip holds
    # ``experts_held`` of them, from ``first_held`` on, and computes their part
    experts_total: int = 0
    experts_held: int = 0
    first_held: int = 0
    experts_per_token: int = 0
    expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # group-limited routing: the experts lie in ``n_group`` groups of equal
    # size, of which the ``topk_group`` with the best two-expert score are kept
    n_group: int = 1
    topk_group: int = 1
    # one shared expert of this width beside the routed ones (0: none)
    shared_expert_intermediate_size: int = 0
    # the published per-layer clamp of an expert's SwiGLU is not implemented:
    # a model whose held layers carry a non-zero limit is refused, not served
    # without it (``expert_swiglu_limits``: one number per held layer)
    expert_swiglu_limits: Optional[tuple] = None
    # "mla" layers: q heads [nope | rope], straight from x or (``q_lora_rank``)
    # through a low-rank bottleneck with a norm; k and v from one latent row; a
    # sigmoid gate a head on the output unless ``mla_head_gate`` is off
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    q_lora_rank: int = 0
    mla_head_gate: bool = True
    # learned selection of cached tokens (ops/dsa.py): ``index_heads`` index
    # queries of ``index_head_dim`` score one cached index key a token, and a
    # query attends to its ``index_topk`` best tokens only (0: no indexer)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # "kda" layers: heads of head_dim (keys) x v_head_dim (values), a short
    # causal convolution before them, a decay gate bounded below
    kda_conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    # "ssm" layers (Mamba-2): ``ssm_heads`` heads of ``ssm_head_dim`` channels
    # (together ``ssm_expand * hidden_size``), a state of ``ssm_state`` per
    # channel, B and C shared by the heads of each of ``ssm_groups`` groups, a
    # short causal convolution with bias before them
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_expand: int = 2
    # four scalars on the residual stream (Granite): the embedding is
    # multiplied by the first, attention scores by the second (None:
    # ``head_dim ** -0.5``), each branch by the third before it is added, and
    # the logits are divided by the fourth
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_heads)
        assert self.num_heads % self.num_kv_heads == 0
        if self.rope_scaling_type is not None:
            assert self.rope_scaling_type in ("linear", "dynamic", "yarn"), self.rope_scaling_type
        if self.rope_scaling_type == "yarn":
            assert self.layer_types and set(self.layer_types) == {"mla"}, (
                "yarn scaling is implemented for latent-attention layers only")
            assert self.rope_original_max_len > 0 and self.rope_scaling_factor >= 1
        for name in ("layer_types", "ffn_types", "expert_swiglu_limits"):
            value = getattr(self, name)
            if value is not None:  # a list from JSON: keep the config hashable
                assert len(value) == self.num_layers, (name, len(value), self.num_layers)
                object.__setattr__(self, name, tuple(value))
        if self.ffn_types is not None and "experts" in self.ffn_types:
            assert 0 < self.experts_held <= self.experts_total
            assert 0 <= self.first_held <= self.experts_total - self.experts_held
            assert 0 < self.experts_per_token <= self.experts_total
            assert self.expert_intermediate_size > 0
            assert self.experts_total % self.n_group == 0
            assert 0 < self.topk_group <= self.n_group
        if self.expert_swiglu_limits and any(self.expert_swiglu_limits):
            raise NotImplementedError(
                f"model {self.name!r}: a held layer carries a non-zero SwiGLU "
                f"limit {self.expert_swiglu_limits}; the clamp is not "
                "implemented, and serving without it would be another model")

    @property
    def hybrid(self) -> bool:
        """Layers of more than one kind: run by models/hybrid.py."""
        return self.layer_types is not None or self.ffn_types is not None

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """One kind of softmax-attention layer: what it projects, rotates, caches, sees."""
    name: str  # "global" | "window": also names its KV pool (k_<name>, v_<name>)
    num_kv_heads: int
    head_dim: int  # q and k
    v_head_dim: int  # v and the attention output
    rope_theta: float
    rotary_dim: int
    window: Optional[int]
    sink: bool
    value_scale: float
    scale: Optional[float] = None  # of the scores; ``head_dim ** -0.5`` if None
    yarn = None

    def pools(self) -> dict:
        """{cache leaf: row width} of the rows this kind caches per token."""
        return {f"k_{self.name}": self.num_kv_heads * self.head_dim,
                f"v_{self.name}": self.num_kv_heads * self.v_head_dim}

    def states(self, cfg) -> dict:
        return {}


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN on a kind's rope lanes (ops/rope.py:rope_cos_sin, yarn_mscale)."""
    factor: float
    original_max_len: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclasses.dataclass(frozen=True)
class MlaKind:
    """Latent attention: one cached row per token, ``[c kv_lora_rank | kR
    rope_dim]``, shared by every head; no v pool (ops/mla.py). With an
    indexer (``index_topk``) a second row per token, the index key, in a pool
    of its own, and every query reads its ``index_topk`` best tokens
    (ops/dsa.py). ``whole_tiles``: the pool's rows are stored in whole lane
    tiles (128 lanes; a row's tail is zeros)."""
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_head_dim: int
    rope_theta: float
    q_lora_rank: int = 0  # 0: ``q_proj`` straight from x
    head_gate: bool = True
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0  # 0: no indexer
    yarn: Optional[YarnScaling] = None
    whole_tiles: bool = False
    name: str = "mla"
    window = None

    @property
    def rotary_dim(self) -> int:
        return self.rope_dim

    @property
    def score_scale(self) -> float:
        """``(nope + rope) ** -0.5``, times YaRN's temperature squared where
        the model states one for all lanes (``mscale_all_dim``)."""
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        if self.yarn is not None and self.yarn.mscale_all_dim:
            from datatunerx_tpu.ops.rope import yarn_mscale

            scale *= yarn_mscale(self.yarn.factor, self.yarn.mscale_all_dim) ** 2
        return scale

    @property
    def index_rope_dim(self) -> int:
        """The FIRST lanes of an index query and key are rotated, as many as
        q's rope lanes and by the same table."""
        return self.rope_dim

    def pools(self) -> dict:
        row = self.kv_lora_rank + self.rope_dim
        if self.whole_tiles:
            row = -(-row // 128) * 128
        return {"k_mla": row, **({"k_idx": self.index_dim} if self.index_topk else {})}

    def states(self, cfg) -> dict:
        return {}


@dataclasses.dataclass(frozen=True)
class KdaKind:
    """Linear attention: no rows, a state of constant size per slot (ops/kda.py)."""
    head_dim: int  # q, k and the decay, per head
    v_head_dim: int
    conv_kernel: int
    lower_bound: float
    name: str = "kda"
    window = None

    def pools(self) -> dict:
        return {}

    def states(self, cfg) -> dict:
        """{cache leaf: (shape per slot, dtype name | None for the pools'
        dtype)}: the float32 memory matrix per head, and the last
        pre-convolution rows of q, k and v in the type they are computed in."""
        H = cfg.num_heads
        return {"state_kda": ((H, self.head_dim, self.v_head_dim), "float32"),
                "state_kda_conv": ((self.conv_kernel - 1,
                                    H * (2 * self.head_dim + self.v_head_dim)),
                                   None)}


@dataclasses.dataclass(frozen=True)
class SsmKind:
    """State space (Mamba-2): no rows, a state of constant size per slot with
    one scalar decay a head (ops/ssm.py)."""
    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int
    expand: int
    name: str = "ssm"
    window = None

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution sees: ``[x | B | C]``."""
        return self.inner + 2 * self.groups * self.state

    def pools(self) -> dict:
        return {}

    def states(self, cfg) -> dict:
        """As ``KdaKind.states``: the float32 state per head, and the last
        pre-convolution rows of ``[x | B | C]``."""
        return {"state_ssm": ((self.heads, self.head_dim, self.state), "float32"),
                "state_ssm_conv": ((self.conv_kernel - 1, self.conv_dim), None)}


@dataclasses.dataclass(frozen=True)
class LayerRun:
    """``count`` consecutive layers of one kind, stacked and scanned together.
    ``mixer`` is what mixes tokens in them (an ``AttentionKind``, ``MlaKind``,
    ``KdaKind`` or ``SsmKind``); ``kind_start`` is where they lie among the layers of
    their mixer kind (the layer axis of that kind's cache leaves)."""
    mixer: object
    ffn: str  # "dense" | "experts"
    count: int
    kind_start: int


def mixer_kinds(cfg: ModelConfig) -> dict:
    """{name: kind} of the mixers this model has, softmax attention first."""
    types = cfg.layer_types or (
        ("window" if cfg.sliding_window else "global",) * cfg.num_layers)
    rotary = int(cfg.head_dim * cfg.partial_rotary_factor)  # dtxlint: disable=DTX001 — config scalars, host only
    common = dict(head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim or cfg.head_dim,
                  rotary_dim=rotary - rotary % 2, value_scale=cfg.attention_value_scale,
                  scale=cfg.attention_multiplier)
    kinds = {}
    if "global" in types:
        kinds["global"] = AttentionKind(
            name="global", num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
            window=None, sink=False, **common)
    if "window" in types:
        kinds["window"] = AttentionKind(
            name="window", num_kv_heads=cfg.window_num_kv_heads or cfg.num_kv_heads,
            rope_theta=cfg.window_rope_theta or cfg.rope_theta,
            window=cfg.sliding_window, sink=cfg.window_sink, **common)
    if "mla" in types:
        assert cfg.kv_lora_rank > 0 and cfg.qk_nope_head_dim > 0
        assert cfg.qk_rope_head_dim > 0 and cfg.qk_rope_head_dim % 2 == 0
        if cfg.index_topk:  # the index query comes from the compressed query
            assert cfg.q_lora_rank > 0 and cfg.index_heads > 0
            assert cfg.index_head_dim >= cfg.qk_rope_head_dim
        yarn = None
        if cfg.rope_scaling_type == "yarn":
            yarn = YarnScaling(
                factor=cfg.rope_scaling_factor, original_max_len=cfg.rope_original_max_len,
                beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
                mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim)
        kinds["mla"] = MlaKind(
            # whole lane tiles where single rows are gathered (a selecting
            # kind) and where the latent pool is the model's whole cache (every
            # layer latent): rows of 4.5 tiles make XLA keep the pool in a
            # layout of its own and copy it in and out of every program
            yarn=yarn, whole_tiles=bool(cfg.index_topk) or set(types) == {"mla"},
            kv_lora_rank=cfg.kv_lora_rank, nope_dim=cfg.qk_nope_head_dim,
            rope_dim=cfg.qk_rope_head_dim, rope_theta=cfg.rope_theta,
            v_head_dim=cfg.v_head_dim or cfg.head_dim,
            q_lora_rank=cfg.q_lora_rank, head_gate=cfg.mla_head_gate,
            index_heads=cfg.index_heads, index_dim=cfg.index_head_dim,
            index_topk=cfg.index_topk)
    if "kda" in types:
        kinds["kda"] = KdaKind(
            head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim or cfg.head_dim,
            conv_kernel=cfg.kda_conv_kernel, lower_bound=cfg.kda_lower_bound)
    if "ssm" in types:
        assert cfg.ssm_heads * cfg.ssm_head_dim == cfg.ssm_expand * cfg.hidden_size
        assert cfg.ssm_state > 0 and cfg.ssm_heads % cfg.ssm_groups == 0
        kinds["ssm"] = SsmKind(
            heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
            groups=cfg.ssm_groups, conv_kernel=cfg.ssm_conv_kernel,
            expand=cfg.ssm_expand)
    return kinds


def layer_runs(cfg: ModelConfig) -> tuple:
    """The model as runs of like layers, in order: the one description that
    ``forward``, the cache constructors, the adapters and the estimators read."""
    kinds = mixer_kinds(cfg)
    types = cfg.layer_types or (next(iter(kinds)),) * cfg.num_layers
    ffns = cfg.ffn_types or ("dense",) * cfg.num_layers
    runs, seen = [], {name: 0 for name in kinds}
    for i, (t, f) in enumerate(zip(types, ffns)):
        if t not in kinds or f not in ("dense", "experts"):
            raise ValueError(f"layer {i}: unknown kind {t!r}/{f!r}")
        last = runs[-1] if runs else None
        if last is not None and last.mixer.name == t and last.ffn == f:
            runs[-1] = dataclasses.replace(last, count=last.count + 1)
        else:
            runs.append(LayerRun(mixer=kinds[t], ffn=f, count=1,
                                 kind_start=seen[t]))
        seen[t] += 1
    return tuple(runs)


def kind_layers(cfg: ModelConfig) -> dict:
    """{mixer kind name: how many layers are of it} (its cache leaves' layer axis)."""
    out = {}
    for run in layer_runs(cfg):
        out[run.mixer.name] = out.get(run.mixer.name, 0) + run.count
    return out


def has_recurrent_state(cfg: ModelConfig) -> bool:
    """Some layer keeps state of constant size per slot instead of rows."""
    return any(kind.states(cfg) for kind in mixer_kinds(cfg).values())


def refuse_hybrid(cfg: ModelConfig, what: str) -> None:
    """One clear message from every entry that handles only the single-kind
    decoder: a model with layers of several kinds is served, not ``what``."""
    if cfg.hybrid:
        named = sorted(set(cfg.layer_types or ()) | set(cfg.ffn_types or ()))
        raise NotImplementedError(
            f"model {cfg.name!r} has layers of several kinds ({', '.join(named)}): "
            f"it is served by the batched engine, and {what} does not handle "
            f"it yet")


def refuse_recurrent_state(cfg: ModelConfig, what: str) -> None:
    """One clear message from every entry that would have to SNAPSHOT a slot's
    recurrent state (rows can be trimmed at a cursor; a state cannot)."""
    if has_recurrent_state(cfg):
        named = sorted(name for name, kind in mixer_kinds(cfg).items()
                       if kind.states(cfg))
        raise NotImplementedError(
            f"model {cfg.name!r} has layers ({', '.join(named)}) that keep a "
            f"recurrent state per slot, which cannot be rewound to an earlier "
            f"cursor: {what} needs snapshots of that state and does not handle "
            f"it yet")


PRESETS = {
    # Debug-scale configs for tests and CPU smoke runs.
    "debug": ModelConfig(
        name="debug", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
    ),
    "debug-350m": ModelConfig(
        name="debug-350m", vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=20, num_heads=16, num_kv_heads=16, max_seq_len=2048,
    ),
    "tinyllama-1.1b": ModelConfig(
        name="tinyllama-1.1b", vocab_size=32000, hidden_size=2048,
        intermediate_size=5632, num_layers=22, num_heads=32, num_kv_heads=4,
        max_seq_len=2048,
    ),
    "llama2-7b": ModelConfig(
        name="llama2-7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=11008, num_layers=32, num_heads=32, num_kv_heads=32,
        max_seq_len=4096,
    ),
    "llama2-13b": ModelConfig(
        name="llama2-13b", vocab_size=32000, hidden_size=5120,
        intermediate_size=13824, num_layers=40, num_heads=40, num_kv_heads=40,
        max_seq_len=4096,
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        max_seq_len=8192, sliding_window=4096, rms_norm_eps=1e-5,
    ),
    "qwen1.5-14b": ModelConfig(
        name="qwen1.5-14b", vocab_size=152064, hidden_size=5120,
        intermediate_size=13696, num_layers=40, num_heads=40, num_kv_heads=40,
        max_seq_len=8192, rope_theta=1_000_000.0, attention_bias=True,
        rms_norm_eps=1e-6,
    ),
    # Debug size of a model with window and global attention layers of their
    # own KV geometry and sparse experts of which this chip holds a share:
    # tests and the CPU smoke of the serving path (models/hybrid.py).
    "debug-hybrid": ModelConfig(
        name="debug-hybrid", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=1, head_dim=24, v_head_dim=16,
        max_seq_len=512, rope_theta=1e7, sliding_window=24,
        partial_rotary_factor=0.334, attention_value_scale=0.707,
        layer_types=("global", "window", "window", "window", "global"),
        ffn_types=("dense", "experts", "experts", "experts", "experts"),
        window_num_kv_heads=2, window_rope_theta=1e4, window_sink=True,
        experts_total=8, experts_held=4, first_held=0, experts_per_token=2,
        expert_intermediate_size=32,
    ),
    # Debug size of a model whose mixers are linear attention (KDA: a
    # recurrent state per slot, no rows) and latent attention (MLA: one pool,
    # no v pool), with group-limited routing and a shared expert.
    "debug-ling": ModelConfig(
        name="debug-ling", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=4, head_dim=16, v_head_dim=16,
        max_seq_len=512, rope_theta=6e6, rms_norm_eps=1e-6,
        layer_types=("kda", "kda", "kda", "mla", "kda"),
        ffn_types=("dense", "experts", "experts", "experts", "experts"),
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        experts_total=16, experts_held=4, first_held=0, experts_per_token=2,
        expert_intermediate_size=32, n_group=4, topk_group=2,
        shared_expert_intermediate_size=32, routed_scaling_factor=2.5,
    ),
    # Debug size of a model whose mixers are state-space layers (Mamba-2: a
    # recurrent state per slot, no rows) and softmax attention without
    # positions, with Granite's four multipliers, dense feed-forward, tied head.
    "debug-granite": ModelConfig(
        name="debug-granite", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=6, num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=512,
        tie_word_embeddings=True, partial_rotary_factor=0.0,
        layer_types=("ssm", "ssm", "global", "ssm", "ssm", "ssm"),
        ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_groups=1,
        embedding_multiplier=12.0, attention_multiplier=0.125,
        residual_multiplier=0.22, logits_scaling=8.0,
    ),
    # Debug size of a model whose every mixer is latent attention with a
    # low-rank query and a learned indexer: a query reads its ``index_topk``
    # best cached tokens (an index-key pool beside the latent pool), no head
    # gate; one dense layer, then sigmoid-routed experts with a shared one.
    "debug-glm": ModelConfig(
        name="debug-glm", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=4, head_dim=16, v_head_dim=24,
        max_seq_len=512, rope_theta=1e6,
        layer_types=("mla",) * 5,
        ffn_types=("dense", "experts", "experts", "experts", "experts"),
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        q_lora_rank=48, mla_head_gate=False,
        index_heads=3, index_head_dim=16, index_topk=32,
        experts_total=16, experts_held=4, first_held=0, experts_per_token=2,
        expert_intermediate_size=32, shared_expert_intermediate_size=32,
        routed_scaling_factor=2.5,
    ),
    # Debug size of a model whose every mixer is DENSE latent attention (a
    # low-rank query, no indexer, no head gate: every query reads every cached
    # row) under YaRN, v heads narrower than q/k; one dense layer, then
    # sigmoid-routed experts with a shared one.
    "debug-kimi": ModelConfig(
        name="debug-kimi", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=5, num_heads=4, num_kv_heads=4, head_dim=24, v_head_dim=16,
        max_seq_len=512, rope_theta=100.0,
        rope_scaling_type="yarn", rope_scaling_factor=8.0, rope_original_max_len=64,
        rope_mscale=1.0, rope_mscale_all_dim=1.0,
        layer_types=("mla",) * 5,
        ffn_types=("dense", "experts", "experts", "experts", "experts"),
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        q_lora_rank=48, mla_head_gate=False,
        experts_total=16, experts_held=4, first_held=0, experts_per_token=2,
        expert_intermediate_size=32, shared_expert_intermediate_size=32,
        routed_scaling_factor=2.827,
    ),
    "qwen1.5-7b": ModelConfig(
        name="qwen1.5-7b", vocab_size=151936, hidden_size=4096,
        intermediate_size=11008, num_layers=32, num_heads=32, num_kv_heads=32,
        max_seq_len=8192, rope_theta=1_000_000.0, attention_bias=True,
        rms_norm_eps=1e-6,
    ),
}


def get_config(name: str, **overrides) -> ModelConfig:
    """Look up a preset by name, optionally overriding fields."""
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    cfg = PRESETS[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
