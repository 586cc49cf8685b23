"""The options a deployment may set on a serving replica: ONE table.

Every surface that names a serving option reads it from here —
``serving.server``'s parser, the gateway's spawn pass-through, ``dtx serve``,
the argv the local and kube backends hand a replica, the keywords
``BatchedEngine`` is built with, and the ``serveConfig`` half of the
FinetuneJob CRD (schema, admission checks, spec rendering). A new option is
one row. Stdlib only: the admission webhook imports this without JAX.

Not here: what only a gateway knows (``--policy``, ``--role``, ``--fleet_*``,
``--replicas`` …) and the two path selectors the engine resolves for itself
from the platform and the model (the Pallas decode kernel, the fused sampler:
constructor keywords for tests and the parity oracle, never flags).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional


def parse_adapters(spec: str) -> dict:
    """``name=ckpt_path[,name=path…]`` → {name: path}"""
    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, path = part.partition("=")
        if not name or not path:
            raise ValueError(f"bad adapter spec {part!r}; want name=path")
        out[name] = path
    return out


def parse_spec_tree(spec: str) -> tuple:
    """``"WxD"`` → (branch width, draft depth); the one parser of the format
    (``serving.speculative.parse_spec_tree`` wraps it in a ``TreeSpec``)."""
    err = (f"spec_tree must be 'WxD' (branch width x draft depth, e.g. "
           f"'4x3'), got {spec!r}")
    parts = str(spec).strip().lower().split("x")
    if len(parts) != 2:
        raise ValueError(err)
    try:
        width, depth = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(err) from None
    if not 1 <= width <= 64 or not 1 <= depth <= 16:
        raise ValueError(
            f"spec_tree {spec!r} out of range: width must be in [1, 64] "
            "and depth in [1, 16]")
    return width, depth


def _csv(spec: str) -> Optional[list]:
    return [t.strip() for t in spec.split(",") if t.strip()] or None


class Option(NamedTuple):
    name: str  # the flag (``--name``) and the key of a serving spec dict
    type: type
    default: object
    help: str
    choices: Optional[tuple] = None
    crd: Optional[str] = None  # serveConfig key, where the CRD has one
    # BatchedEngine keyword: the name unless given; None = not an engine
    # keyword (--quantization selects the single-slot engine instead)
    engine: Optional[str] = ""
    batched_only: bool = False  # a non-default value needs the batched engine
    parse: Optional[Callable] = None  # flag string → the engine's value
    check: Optional[Callable] = None  # raises ValueError on a bad value
    requires: Optional[str] = None  # serveConfig key it is meaningless without


OPTIONS = (
    Option("model_path", str, "", "model directory or preset:<name>"),
    Option("checkpoint_path", str, "", "fine-tuned checkpoint: a LoRA one "
           "serves as adapter 'default', a full one replaces the base"),
    Option("template", str, "llama2", "chat template"),
    Option("max_seq_len", int, 1024, "context length (prompt + reply)"),
    Option("quantization", str, "", "serve-time base-weight quantization "
           "(single-request engine)", choices=("", "int8", "int4", "nf4"),
           crd="quantization", engine=None),
    Option("slots", int, 4, "continuous-batching cache slots "
           "(1 = single-request engine)", crd="slots"),
    Option("decode_chunk", int, 8,
           "tokens per decode program (admission latency bound)"),
    Option("adapters", str, "", "named LoRA adapters name=ckpt[,name=ckpt…]; "
           "requests select one via the 'model' field",
           batched_only=True, parse=parse_adapters),
    Option("adapter_pool", int, 0, "dynamic adapter pool: N HBM slots "
           "adapters load into at runtime (load-on-miss, LRU evict, "
           "/admin/adapters); 0 = static --adapters stack",
           crd="adapterPool", batched_only=True),
    Option("adapter_rank_max", int, 8, "pool rank ceiling: lower ranks are "
           "zero-padded, higher rejected",
           crd="adapterRankMax", requires="adapterPool"),
    Option("adapter_targets", str, "", "pool LoRA targets, comma-separated "
           "(default q_proj,v_proj); others are rejected", parse=_csv),
    Option("kv_quant", str, "", "int8 KV cache: half the cache HBM",
           choices=("", "int8"), batched_only=True),
    Option("prefix_cache", int, 0, "LRU entries of reusable prefilled prompt "
           "prefixes (one cache row of HBM each)", batched_only=True),
    Option("kv_block_size", int, 0, "paged KV cache block size in tokens "
           "(0 = dense slots×max_seq_len cache)", batched_only=True),
    Option("kv_blocks", int, 0, "blocks in the paged pool (default "
           "slots × max_seq_len / kv_block_size)"),
    Option("kv_overcommit", str, "off", "on: admission reserves the prompt's "
           "blocks plus headroom, tables grow at each cursor, prefix hits "
           "share blocks copy-on-write, exhaustion preempts youngest-first "
           "(sessions park and resume token-exactly); off = eager reserve",
           choices=("off", "on"), crd="kvOvercommit", batched_only=True),
    Option("spec_draft_config", str, "", "speculative draft model: a path, "
           "preset:<name> (same vocab), or take:N (the target's first N "
           "layers); empty = off",
           crd="specDraft", engine="spec_draft", batched_only=True),
    Option("spec_k", int, 4, "draft proposals per verify step (the adaptive "
           "controller's ceiling)", crd="specK"),
    Option("spec_mode", str, "auto", "auto = adaptive (shrink k, fall back "
           "when acceptance collapses), on = always draft, off = plain "
           "decode", choices=("auto", "on", "off"), crd="specMode"),
    Option("spec_tree", str, "", "tree drafts 'WxD' (branch width x draft "
           "depth, e.g. 4x3): one batched verify accepts the longest "
           "surviving path; empty = chain drafts", crd="specTree",
           batched_only=True, check=parse_spec_tree, requires="specDraft"),
    Option("prefill_chunk", int, 256, "chunked-prefill program length in "
           "tokens (paged cache); long prompts interleave with decode"),
    Option("prefill_token_budget", int, 0, "prefill tokens the scheduler "
           "spends between decode chunks (0 = unbounded)"),
    Option("tenants_config", str, "", "tenant directory, a JSON file path or "
           "inline JSON: tenant → {tier, adapters, share, kv_block_quota, "
           "ttft_p95_ms}; empty = tenancy plane off",
           crd="tenantsConfig", engine="tenants", batched_only=True),
    Option("host_adapter_cache_mb", float, 0.0, "host-RAM adapter tier in MB: "
           "evicted adapters reload from host arrays, not orbax; 0 = off",
           crd="hostAdapterCacheMb", batched_only=True),
    Option("trace_ring", int, 256,
           "completed request traces kept for GET /debug/trace/<id>"),
    Option("trace_log", str, "", "append every completed request span as one "
           "JSON line to this file", engine="trace_log_path"),
)
# the process that parses these keeps them: a gateway's ring and log are its
# own, and it hands neither to the replicas it spawns
PER_PROCESS = ("trace_ring", "trace_log")


def add_arguments(parser, model_required: bool = True):
    for o in OPTIONS:
        kw = {"choices": list(o.choices)} if o.choices else {}
        if o.name == "model_path" and model_required:
            kw["required"] = True
        parser.add_argument(f"--{o.name}", type=o.type, default=o.default,
                            help=o.help, **kw)


def _get(values, name):
    return (values.get(name) if isinstance(values, dict)
            else getattr(values, name, None))


def _is_set(o: Option, v) -> bool:
    return v not in (None, "") and str(v) != str(o.default)


def argv(values, skip=()) -> list:
    """``["--flag", "value", …]`` for every option ``values`` (a parsed
    namespace or a serving spec dict) sets to other than its default."""
    out = []
    for o in OPTIONS:
        v = _get(values, o.name)
        if o.name not in skip and _is_set(o, v):
            out += [f"--{o.name}", str(v)]
    return out


def requires_batched(namespace) -> list:
    """Flags whose value only the batched engine can honour."""
    return [f"--{o.name}" for o in OPTIONS
            if o.batched_only and _is_set(o, _get(namespace, o.name))]


def engine_kwargs(namespace) -> dict:
    """The ``BatchedEngine`` keywords a parsed namespace stands for."""
    out = {}
    for o in OPTIONS:
        if o.engine is None:
            continue
        v = getattr(namespace, o.name)
        if o.parse is not None:
            v = o.parse(v)
        out[o.engine or o.name] = None if v == "" else v
    return out


# --------------------------------------------------- serveConfig (the CRD)

def from_serve_config(cfg: dict) -> dict:
    """serveConfig's engine options as serving-spec entries (``argv`` keys)."""
    return {o.name: cfg[o.crd] for o in OPTIONS
            if o.crd and cfg.get(o.crd) not in (None, "")}


def crd_properties() -> dict:
    """OpenAPI properties of serveConfig's engine options."""
    props = {}
    for o in OPTIONS:
        if not o.crd:
            continue
        if o.choices:
            props[o.crd] = {"type": "string",
                            "enum": [""] + [c for c in o.choices if c]}
        else:
            props[o.crd] = {"type": {int: "integer", float: "number",
                                     str: "string"}[o.type]}
    return props


def validate_serve_config(cfg: dict):
    """Per-option admission checks; raises ValueError naming
    ``serveConfig.<key>``. An empty string means unset, as in the CRD enums."""
    for o in OPTIONS:
        v = cfg.get(o.crd) if o.crd else None
        if v in (None, ""):
            continue
        key = f"serveConfig.{o.crd}"
        if o.type is not str:
            try:
                n = float(v)
            except (TypeError, ValueError):
                raise ValueError(f"{key} must be numeric, got {v!r}") from None
            if o.type is int and not (n >= 1 and n.is_integer()):
                raise ValueError(f"{key} must be a positive integer")
            if o.type is float and n < 0:
                raise ValueError(f"{key} must be >= 0")
        elif o.choices and str(v) not in o.choices:
            raise ValueError(
                f"{key} must be one of {', '.join(c for c in o.choices if c)}")
        if o.requires and cfg.get(o.requires) in (None, ""):
            raise ValueError(f"{key} requires {o.requires}")
        if o.check is not None:
            try:
                o.check(v)
            except ValueError as e:
                raise ValueError(f"{key}: {e}") from None

