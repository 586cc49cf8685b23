"""Parameters, operations and bytes of a ``kimi_k2`` (Kimi-K2.5) configuration,
from shapes alone: dense latent attention with a low-rank query, the one row a
token caches, what one token step's attention has to read. Kept with the
benchmark so that no PR that claims a gain can change what a roofline share is
measured against. A multiply-add counts as 2 operations; ``mc`` is the
configuration file's ``model_config``; expert arithmetic that does not depend
on the mixer is ``flops_moe``'s.
"""

from __future__ import annotations

import flops_ling
import flops_moe


def _dims(mc: dict):
    return mc["hidden_size"], mc["num_heads"], mc.get("v_head_dim") or mc["head_dim"]


def kv_b_params(mc: dict) -> int:
    _, H, dv = _dims(mc)
    return mc["kv_lora_rank"] * H * (mc["qk_nope_head_dim"] + dv)


def mixer_params(mc: dict) -> int:
    """q_a, its norm, q_b, kv_a (latent + rotated key), its norm, kv_b, o."""
    D, H, dv = _dims(mc)
    rank, nope, rot, q_rank = (mc["kv_lora_rank"], mc["qk_nope_head_dim"], mc["qk_rope_head_dim"],
                               mc["q_lora_rank"])
    return (D * q_rank + q_rank + q_rank * H * (nope + rot) + D * (rank + rot) + rank
            + kv_b_params(mc) + H * dv * D)


def layer_params(mc: dict, ffn: str) -> int:
    """One layer as this chip holds it (``experts_held`` of its experts), norms included."""
    n = mixer_params(mc) + 2 * mc["hidden_size"]
    if ffn == "dense":
        return n + flops_moe.dense_ffn_params(mc)
    return (n + flops_moe.router_params(mc) + flops_ling.shared_expert_params(mc)
            + mc["experts_held"] * flops_moe.expert_params(mc))


def total_params(mc: dict) -> int:
    n = sum(layer_params(mc, f) for f in mc["ffn_types"])
    return n + 2 * mc["vocab_size"] * mc["hidden_size"] + mc["hidden_size"]


def latent_row_bytes(mc: dict, kv_bytes: int = 2) -> int:
    """What one token caches a layer: the normed latent and the rotated key."""
    return (mc["kv_lora_rank"] + mc["qk_rope_head_dim"]) * kv_bytes


def stored_bytes_per_token(mc: dict, kv_bytes: int = 2, lanes: int = 128) -> int:
    """What the program's pool holds a token: rows of whole lane tiles (576 values in 640)."""
    row = -(-(mc["kv_lora_rank"] + mc["qk_rope_head_dim"]) // lanes) * lanes
    return len(mc["layer_types"]) * row * kv_bytes


def mla_decode_step(mc: dict, context_tokens: list, kv_bytes: int = 2, weight_bytes: int = 2) -> dict:
    """One token step's latent attention in EVERY layer, for live slots that
    hold the given numbers of context tokens: ONE read of each slot's latent
    rows up to its cursor, one row written a slot, and ``kv_b_proj`` read once
    a layer (absorbed into the query and out of the output); the operations are
    the two absorptions and the scores and values over every row (absorbed: a
    head's query is ``rank + rope`` wide against a row, its output ``rank``).
    What any implementation has to do: no gathered view, no table look-up, no
    second pass over the rows, no float32 copy of the scores is counted."""
    _, H, dv = _dims(mc)
    rank, nope, rot = mc["kv_lora_rank"], mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    L, slots, ctx = len(mc["layer_types"]), len(context_tokens), float(sum(context_tokens))
    absorb = 2.0 * H * rank * (nope + dv) * slots
    return {"flops": L * (absorb + 2.0 * H * (2 * rank + rot) * ctx),
            "bytes": L * ((ctx + slots) * latent_row_bytes(mc, kv_bytes)
                          + kv_b_params(mc) * weight_bytes)}


def decode_weight_bytes(mc: dict, experts_hit_per_layer: float, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step must stream: every layer's mixer, the
    dense feed-forward or the router, the shared expert and the experts that
    got a row, the output head."""
    n = 0.0
    for ffn in mc["ffn_types"]:
        n += mixer_params(mc)
        n += flops_moe.dense_ffn_params(mc) if ffn == "dense" else (
            flops_moe.router_params(mc) + flops_ling.shared_expert_params(mc)
            + experts_hit_per_layer * flops_moe.expert_params(mc))
    return (n + mc["vocab_size"] * mc["hidden_size"]) * weight_bytes
