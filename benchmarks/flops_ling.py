"""Parameters, operations and bytes of a ``bailing_hybrid`` (Ling-3.0)
configuration, from shapes alone: the KDA and MLA mixers, the recurrent state a
slot keeps, the latent row a token caches, the weights one decode step streams.
Kept with the benchmark so that no PR that claims a gain can change what a
roofline share is measured against. A multiply-add counts as 2 operations;
``mc`` is the configuration file's ``model_config``; expert arithmetic that
does not depend on the mixer is ``flops_moe``'s.
"""

from __future__ import annotations

import flops_moe


def _dims(mc: dict):
    return mc["hidden_size"], mc["num_heads"], mc["head_dim"], mc.get("v_head_dim") or mc["head_dim"]


def kda_params(mc: dict) -> int:
    """q, k, v, the decay gate f and the output gate g (D x H*d each), o, the
    write strength b (D x H), the convolution, A_log, dt_bias, the head norm."""
    D, H, d, dv = _dims(mc)
    K = int(mc.get("kda_conv_kernel", 4))
    return (3 * D * H * d + D * H * dv + D * H * dv + H * dv * D + D * H
            + H * (2 * d + dv) * K + H + H * d + dv)


def mla_params(mc: dict) -> int:
    """q uncompressed, kv_a (latent + rotated key), its norm, kv_b, the head gate, o."""
    D, H, _, dv = _dims(mc)
    rank, nope, rot = mc["kv_lora_rank"], mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    return (D * H * (nope + rot) + D * (rank + rot) + rank + rank * H * (nope + dv)
            + D * H + H * dv * D)


def mixer_params(mc: dict, kind: str) -> int:
    return {"kda": kda_params, "mla": mla_params}[kind](mc)


def shared_expert_params(mc: dict) -> int:
    return 3 * mc["hidden_size"] * int(mc.get("shared_expert_intermediate_size") or 0)


def layer_params(mc: dict, kind: str, ffn: str) -> int:
    """One layer as this chip holds it (``experts_held`` of its experts), norms included."""
    n = mixer_params(mc, kind) + 2 * mc["hidden_size"]
    if ffn == "dense":
        return n + flops_moe.dense_ffn_params(mc)
    return (n + flops_moe.router_params(mc) + shared_expert_params(mc)
            + mc["experts_held"] * flops_moe.expert_params(mc))


def total_params(mc: dict) -> int:
    n = sum(layer_params(mc, k, f) for k, f in zip(mc["layer_types"], mc["ffn_types"]))
    return n + 2 * mc["vocab_size"] * mc["hidden_size"] + mc["hidden_size"]


def latent_bytes_per_token(mc: dict, kv_bytes: int = 2) -> int:
    """What one token caches: the normed latent and the rotated key, per MLA layer."""
    return mc["layer_types"].count("mla") * (mc["kv_lora_rank"] + mc["qk_rope_head_dim"]) * kv_bytes


def state_bytes_per_slot(mc: dict, state_bytes: int = 4, conv_bytes: int = 2) -> int:
    """What ONE KDA layer keeps for one slot, whatever its context: the memory
    matrix per head and the last ``kernel - 1`` pre-convolution rows of q, k, v."""
    _, H, d, dv = _dims(mc)
    K = int(mc.get("kda_conv_kernel", 4))
    return H * d * dv * state_bytes + (K - 1) * H * (2 * d + dv) * conv_bytes


def kda_state_step(mc: dict, live_slots: float, state_bytes: int = 4, conv_bytes: int = 2) -> dict:
    """One token step of ONE KDA layer for ``live_slots`` slots: each slot's
    state and convolution rows are read once and written once, in the type
    they are stored in; per head the decay, the prediction ``S^T k``, the
    rank-one update and the read-out ``S^T q`` are 4 passes of ``d x dv``
    multiply-adds. Whatever implements the update has this to do."""
    _, H, d, dv = _dims(mc)
    return {"flops": 2.0 * 4 * H * d * dv * live_slots,
            "bytes": 2.0 * state_bytes_per_slot(mc, state_bytes, conv_bytes) * live_slots}


def mla_decode_step(mc: dict, context_tokens: list, kv_bytes: int = 2) -> dict:
    """One decode step's absorbed attention in ONE MLA layer: every head's
    query meets each cached row (latent + rotated key) and the weighted sum
    is over the latent; a row is read once."""
    _, H, _, _ = _dims(mc)
    rank, rot = mc["kv_lora_rank"], mc["qk_rope_head_dim"]
    ctx = float(sum(context_tokens))
    return {"flops": 2.0 * H * (2 * rank + rot) * ctx,
            "bytes": (rank + rot) * ctx * kv_bytes + len(context_tokens) * H * (2 * rank + rot) * 2}


def decode_weight_bytes(mc: dict, experts_hit_per_layer: float, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step must stream: every layer's mixer, the
    dense feed-forward or the router, the shared expert and the experts that
    got a row, the output head."""
    n = 0.0
    for kind, ffn in zip(mc["layer_types"], mc["ffn_types"]):
        n += mixer_params(mc, kind)
        n += flops_moe.dense_ffn_params(mc) if ffn == "dense" else (
            flops_moe.router_params(mc) + shared_expert_params(mc)
            + experts_hit_per_layer * flops_moe.expert_params(mc))
    return (n + mc["vocab_size"] * mc["hidden_size"]) * weight_bytes
