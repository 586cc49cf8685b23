"""KV session wire format: serialize a live decode session for transfer.

The KV migration fabric (ROADMAP) moves an in-flight session between
replicas without re-prefilling: the source engine exports the slot's KV
prefix (a dense row, trimmed to its live cursor), the decode state that
makes resumption token-exact (next-token logits, the slot's live PRNG key,
position/remaining cursors, sampling params), the generated-token tail,
and the adapter *name* (PR 10: names are the stable cross-fleet identity —
pool slot indices are replica-local). The target allocates blocks, scatters
the row back in via ``paged_insert_row``, and decode continues as if the
session had never moved.

Wire encodings for the KV row:

  bf16  — the cache's native bf16 bytes (LOSSLESS: resumed decode is
          bit-identical to an undisturbed run). The default for bf16
          caches.
  int8  — the ``kv_quant`` representation (int8 values + per-vector f32
          scales over head_dim, ``ops/attention.py kv_quantize``). The
          default — and exact — encoding for ``kv_quant="int8"`` engines,
          whose cache already holds these bytes; selecting it for a bf16
          cache halves the payload but rounds the prefix through int8
          (bounded, but no longer bit-exact).

Cross-encoding imports are supported in every direction (bf16 wire into an
int8 cache re-quantizes through the same kv_quantize path; int8 wire into a
bf16 cache dequantizes), so heterogeneous fleets can still hand sessions
around. Payloads are JSON with base64 array bodies — they ride the admin
HTTP surface (``POST /admin/sessions/export`` / ``/import``).
"""

from __future__ import annotations

import base64
import hashlib
from typing import Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.ops.attention import kv_quantize
from datatunerx_tpu.ops.paged_attention import (
    POS_SENTINEL,
    kv_leaf_keys,
    row_trim,
)

PAYLOAD_KIND = "dtx-kv-session"
# fleet prefix tier (datatunerx_tpu/fleet/prefix_tier.py): a prefilled
# prefix-cache entry serialized for cross-replica publish/import. Same KV
# row encoding as a session payload, but no decode state — the importer
# builds a local _PrefixCache entry, not a live slot.
PREFIX_KIND = "dtx-kv-prefix"
PAYLOAD_VERSION = 1

# The error string a migrated-away request dies with. The gateway matches
# on it (gateway/replica_pool.py MIGRATED_MARKER keeps the same literal —
# it must survive an SSE error event crossing the wire as plain text) to
# splice the imported continuation instead of re-prefilling.
MIGRATED_SESSION = "session migrated"


def _b64(arr: np.ndarray) -> str:
    return base64.b64encode(np.ascontiguousarray(arr).tobytes()).decode()


def _body(arr, b64: bool):
    """One array body: base64 text for the JSON wire, or the host numpy
    array itself for payloads that never leave the process (the engine's
    preemption parking — paying a base64 round-trip to sit in a host list
    would be pure overhead)."""
    host = np.asarray(arr)  # dtxlint: disable=DTX001 — migration serialization point
    return _b64(host) if b64 else host


def _unb64(data, dtype, shape) -> np.ndarray:
    if isinstance(data, np.ndarray):  # raw in-process body (b64=False)
        arr = np.ascontiguousarray(data).reshape(-1).view(np.dtype(dtype))
    else:
        arr = np.frombuffer(base64.b64decode(data.encode()), dtype=dtype)
    if arr.size != int(np.prod(shape)):
        raise ValueError(
            f"kv payload body holds {arr.size} elements, shape {shape} "
            f"needs {int(np.prod(shape))}")
    return arr.reshape(shape)


def encode_payload(payload: dict) -> dict:
    """Make a payload JSON-wire-safe: base64-encode any raw numpy bodies a
    ``b64=False`` (in-process) export left behind. Idempotent — already-
    encoded payloads pass through untouched — so the export surface can
    apply it unconditionally before a payload crosses the admin HTTP
    wire (e.g. a gateway drain exporting preemption-parked sessions)."""
    out = dict(payload)
    if isinstance(out.get("logits"), np.ndarray):
        out["logits"] = _b64(np.asarray(out["logits"], np.float32))
    kv = out.get("kv")
    if isinstance(kv, dict):
        kv = dict(kv)
        for key in ("k", "v", "pos", "k_scale", "v_scale"):
            if isinstance(kv.get(key), np.ndarray):
                kv[key] = _b64(kv[key])
        if isinstance(kv.get("pools"), dict):
            kv["pools"] = {
                name: dict(leaf, body=(_b64(leaf["body"])
                                       if isinstance(leaf["body"], np.ndarray)
                                       else leaf["body"]))
                for name, leaf in kv["pools"].items()}
        out["kv"] = kv
    return out


def model_signature(cfg, kv_quant: Optional[str]) -> dict:
    """What must match (or be convertible) for an import to be correct."""
    sig = {"layers": cfg.num_layers, "kv_heads": cfg.num_kv_heads,
           "head_dim": cfg.head_dim, "vocab": cfg.vocab_size,
           "kv_quant": kv_quant or ""}
    if cfg.hybrid:
        sig["pools"] = _pool_signature(cfg)
    return sig


def _pool_signature(cfg) -> dict:
    """{kind: [layers, kv_heads, k width, v width]} of a model with a KV pool
    per attention kind; a latent-attention kind signs its layers and the row
    width of each of its pools (the latent rows, the index keys)."""
    from datatunerx_tpu.models.config import kind_layers, mixer_kinds

    layers = kind_layers(cfg)
    sig = {}
    for name, kind in mixer_kinds(cfg).items():
        if name in ("global", "window"):
            sig[name] = [layers[name], kind.num_kv_heads, kind.head_dim,
                         kind.v_head_dim]
        elif kind.pools():
            sig[name] = [layers[name], *kind.pools().values()]
    return sig


def _check_model_sig(payload: dict, cfg) -> None:
    sig = payload.get("model_sig") or {}
    for key, want in (("layers", cfg.num_layers),
                      ("kv_heads", cfg.num_kv_heads),
                      ("head_dim", cfg.head_dim),
                      ("vocab", cfg.vocab_size)):
        if sig.get(key) != want:
            raise ValueError(
                f"session payload is from an incompatible model: "
                f"{key}={sig.get(key)} here {want}")
    pools = _pool_signature(cfg) if cfg.hybrid else None
    if sig.get("pools") != pools:
        raise ValueError(
            f"session payload is from an incompatible model: "
            f"pools={sig.get('pools')} here {pools}")


def check_signature(payload: dict, cfg) -> None:
    from datatunerx_tpu.models.config import refuse_recurrent_state

    refuse_recurrent_state(cfg, "session migration")
    _check_model_sig(payload, cfg)
    if payload.get("kind") != PAYLOAD_KIND:
        raise ValueError(
            f"not a {PAYLOAD_KIND} payload (kind={payload.get('kind')!r})")
    if payload.get("version") != PAYLOAD_VERSION:
        raise ValueError(
            f"unsupported session payload version {payload.get('version')!r}")


def check_prefix_signature(payload: dict, cfg) -> None:
    _check_model_sig(payload, cfg)
    if payload.get("kind") != PREFIX_KIND:
        raise ValueError(
            f"not a {PREFIX_KIND} payload (kind={payload.get('kind')!r})")
    if payload.get("version") != PAYLOAD_VERSION:
        raise ValueError(
            f"unsupported prefix payload version {payload.get('version')!r}")


def prefix_fingerprint(adapter: str, prompt_ids: Sequence[int]) -> str:
    """Stable fleet-wide identity of a prefix entry: (adapter NAME, prompt
    token ids). Names, not pool indices — indices are replica-local."""
    h = hashlib.sha1()
    h.update(str(adapter or "").encode("utf-8", "replace"))
    h.update(b"\x00")
    h.update(np.asarray(list(prompt_ids), np.int64).tobytes())
    return h.hexdigest()


def pack_kv_row(row: Dict, cursor: int, wire: str, b64: bool = True,
                kv_heads: Optional[int] = None) -> dict:
    """A dense row cache (``paged_extract_row`` output or a dense-cache
    slot slice) → JSON-safe wire doc, trimmed to the live ``cursor``.
    ``kv_heads`` splits the row's last axis (heads and width are one axis in
    the cache, two on the wire; the bytes are the same); an int8 row's
    scales say it themselves.

    ``wire`` is "int8" or "bf16"; int8 input rows (kv_quant caches) are
    shipped as-is under "int8" (exact), and a bf16 row asked for "int8"
    goes through kv_quantize (the over-the-wire compression path).
    ``b64=False`` keeps the array bodies as host numpy (in-process
    payloads: engine preemption parking); ``encode_payload`` upgrades
    them to base64 if they ever need the wire."""
    row = row_trim(row, max(1, cursor))
    if "k" not in row:
        return _pack_pools(row, wire, b64)
    quantized_cache = "k_scale" in row
    KV = row["k_scale"].shape[-1] if quantized_cache else kv_heads
    if not KV:
        raise ValueError("pack_kv_row: a bf16 row needs kv_heads")
    k, v = (row[key].reshape(row[key].shape[:3] + (KV, -1))
            for key in ("k", "v"))
    if wire == "int8" and not quantized_cache:
        # host transfer happens inside kv_quantize's consumers; do the
        # quantization on device, then pull the small int8 bodies
        k, ks = kv_quantize(k)
        v, vs = kv_quantize(v)
    elif quantized_cache:
        wire = "int8"  # an int8 cache's bytes ARE the int8 wire encoding
        ks, vs = row["k_scale"], row["v_scale"]
    else:
        wire = "bf16"
        ks = vs = None
    # the migration path's designed host sync: one device_get per array
    k_np = np.asarray(k)  # dtxlint: disable=DTX001 — migration serialization point
    v_np = np.asarray(v)  # dtxlint: disable=DTX001 — migration serialization point
    pos_np = np.asarray(row["pos"], np.int32)  # dtxlint: disable=DTX001 — migration serialization point
    L, _, W, KV, d = k_np.shape
    doc = {
        "wire": wire, "width": int(W), "layers": int(L),
        "kv_heads": int(KV), "head_dim": int(d),
        "k": _b64(k_np) if b64 else k_np,
        "v": _b64(v_np) if b64 else v_np,
        "pos": _b64(pos_np) if b64 else pos_np,
    }
    if wire == "int8":
        doc["k_scale"] = _body(np.asarray(ks, np.float32), b64)  # dtxlint: disable=DTX001 — migration serialization point
        doc["v_scale"] = _body(np.asarray(vs, np.float32), b64)  # dtxlint: disable=DTX001 — migration serialization point
    return doc


def _pack_pools(row: Dict, wire: str, b64: bool) -> dict:
    """The wire doc of a row with one k/v pool per attention kind
    (models/hybrid.py): each pool's own shape beside its bf16 bytes."""
    if wire != "bf16":
        raise ValueError(
            "a row with a KV pool per attention kind travels as bf16 only "
            f"(asked for {wire!r})")
    pos_np = np.asarray(row["pos"], np.int32)  # dtxlint: disable=DTX001 — migration serialization point
    pools = {}
    for key in kv_leaf_keys(row):
        host = np.asarray(row[key].astype(jnp.bfloat16))  # dtxlint: disable=DTX001 — migration serialization point
        pools[key] = {"shape": [int(n) for n in host.shape],
                      "body": _b64(host) if b64 else host}
    return {"wire": "bf16", "width": int(pos_np.shape[1]), "pools": pools,
            "pos": _b64(pos_np) if b64 else pos_np}


def _unpack_pools(doc: dict, full_width: int) -> Dict:
    W = int(doc["width"])
    if W > full_width:
        raise ValueError(
            f"session KV depth {W} exceeds this replica's context "
            f"{full_width}")
    pos = _unb64(doc["pos"], np.int32, (1, W))
    row: Dict = {"pos": jnp.asarray(np.pad(
        pos, [(0, 0), (0, full_width - W)], constant_values=POS_SENTINEL))}
    for key, leaf in doc["pools"].items():
        a = _unb64(leaf["body"], jnp.bfloat16, tuple(leaf["shape"]))
        widths = [(0, 0)] * a.ndim
        widths[2] = (0, full_width - W)
        row[key] = jnp.asarray(np.pad(a, widths))
    return row


def unpack_kv_row(doc: dict, full_width: int,
                  quantize: Optional[str]) -> Dict:
    """Wire doc → a dense row cache dict shaped for this engine's cache
    (``[L, 1, full_width, KV * d]`` + sentinel-padded positions), converting
    between int8 and bf16 encodings as the target's ``quantize`` demands."""
    if "pools" in doc:
        return _unpack_pools(doc, full_width)
    L, W = int(doc["layers"]), int(doc["width"])
    KV, d = int(doc["kv_heads"]), int(doc["head_dim"])
    if W > full_width:
        raise ValueError(
            f"session KV depth {W} exceeds this replica's context "
            f"{full_width}")
    wire = doc.get("wire") or "bf16"
    shape = (L, 1, W, KV, d)
    if wire == "int8":
        k = _unb64(doc["k"], np.int8, shape)
        v = _unb64(doc["v"], np.int8, shape)
        ks = _unb64(doc["k_scale"], np.float32, shape[:-1])
        vs = _unb64(doc["v_scale"], np.float32, shape[:-1])
    elif wire == "bf16":
        k = _unb64(doc["k"], jnp.bfloat16, shape)
        v = _unb64(doc["v"], jnp.bfloat16, shape)
        ks = vs = None
    else:
        raise ValueError(f"unknown kv wire encoding {wire!r}")
    pos = _unb64(doc["pos"], np.int32, (1, W))

    def _pad(a: np.ndarray, fill=0) -> jnp.ndarray:
        widths = [(0, 0)] * a.ndim
        widths[2 if a.ndim >= 3 else 1] = (0, full_width - W)
        return jnp.asarray(np.pad(a, widths, constant_values=fill))

    row: Dict = {"pos": _pad(pos, fill=POS_SENTINEL)}
    if quantize == "int8":
        if wire != "int8":  # bf16 wire into an int8 cache: re-quantize
            kq, ks_j = kv_quantize(jnp.asarray(k))
            vq, vs_j = kv_quantize(jnp.asarray(v))
            k = np.asarray(kq)  # dtxlint: disable=DTX001 — migration deserialization point
            v = np.asarray(vq)  # dtxlint: disable=DTX001 — migration deserialization point
            ks = np.asarray(ks_j)  # dtxlint: disable=DTX001 — migration deserialization point
            vs = np.asarray(vs_j)  # dtxlint: disable=DTX001 — migration deserialization point
        row["k"], row["v"] = _pad(k), _pad(v)
        row["k_scale"], row["v_scale"] = _pad(ks), _pad(vs)
    else:
        if wire == "int8":  # int8 wire into a bf16 cache: dequantize
            k = (k.astype(np.float32) * ks[..., None])
            v = (v.astype(np.float32) * vs[..., None])
        row["k"] = _pad(k.astype(jnp.bfloat16))
        row["v"] = _pad(v.astype(jnp.bfloat16))
    for key in ("k", "v"):  # heads and width are one axis in the cache
        row[key] = row[key].reshape(row[key].shape[:3] + (KV * d,))
    return row


def pack_logits(logits, b64: bool = True):
    return _body(np.asarray(logits, np.float32), b64)  # dtxlint: disable=DTX001 — migration serialization point


def unpack_logits(payload: dict, vocab: int) -> jnp.ndarray:
    return jnp.asarray(_unb64(payload["logits"], np.float32, (vocab,)))


def build_payload(cfg, kv_quant: Optional[str], request: dict, row: Dict,
                  cursor, pos, remaining, rng, logits,
                  wire: Optional[str] = None, b64: bool = True) -> dict:
    """Assemble the full wire payload for one exported session.

    ``request`` carries the Request's host-side fields (trace_id, adapter
    name, prompt/token lists, sampling params); ``cursor``/``pos``/
    ``remaining``/``rng``/``logits`` are the slot's decode-state scalars,
    already device_get'd by the engine; ``row`` is the (device) dense KV
    row this function trims, encodes, and pulls to host. ``b64=False``
    keeps array bodies as raw numpy for payloads that stay in-process
    (engine preemption parking); ``encode_payload`` makes them wire-safe."""
    from datatunerx_tpu.models.config import refuse_recurrent_state

    refuse_recurrent_state(cfg, "session migration")
    cursor = int(cursor)
    default_wire = "int8" if kv_quant == "int8" else "bf16"
    return {
        "kind": PAYLOAD_KIND, "version": PAYLOAD_VERSION,
        **request,
        "pos": int(pos), "remaining": int(remaining), "cursor": cursor,
        "rng": [int(x) for x in np.asarray(rng, np.uint32)],
        "logits": pack_logits(logits, b64=b64),
        "kv": pack_kv_row(row, cursor, wire or default_wire, b64=b64,
                          kv_heads=cfg.num_kv_heads),
        "model_sig": model_signature(cfg, kv_quant),
    }


def normalize_payload(payload: dict, cfg) -> dict:
    """Validate an incoming payload against this engine's model and cast
    every scalar the import consumes to its canonical host type — the one
    place JSON-shaped input is trusted-but-verified."""
    check_signature(payload, cfg)
    out = dict(payload)
    out["cursor"] = int(payload["cursor"])
    out["pos"] = int(payload["pos"])
    out["remaining"] = int(payload["remaining"])
    out["max_new_tokens"] = int(payload.get("max_new_tokens",
                                            out["remaining"]))
    out["temperature"] = float(payload.get("temperature", 0.0))
    out["top_p"] = float(payload.get("top_p", 1.0))
    out["seed"] = int(payload.get("seed", 0))
    out["stop_ids"] = [int(s) for s in (payload.get("stop_ids") or [])]
    out["prompt_ids"] = [int(t) for t in (payload.get("prompt_ids") or [])]
    out["tokens"] = [int(t) for t in (payload.get("tokens") or [])]
    out["adapter"] = str(payload.get("adapter") or "")
    out["trace_id"] = str(payload.get("trace_id") or "")
    rng = payload.get("rng") or []
    if len(rng) != 2:
        raise ValueError("session payload rng must be a 2-word PRNG key")
    out["rng"] = [int(x) for x in rng]
    return out
