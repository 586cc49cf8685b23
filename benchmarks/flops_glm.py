"""Parameters, operations and bytes of a ``glm_moe_dsa`` (GLM-5) configuration,
from shapes alone: latent attention with a low-rank query, the indexer, the
two rows a token caches, what one token step's selection has to read. Kept with
the benchmark so that no PR that claims a gain can change what a roofline share
is measured against. A multiply-add counts as 2 operations; ``mc`` is the
configuration file's ``model_config``; expert arithmetic that does not depend
on the mixer is ``flops_moe``'s.
"""

from __future__ import annotations

import flops_ling
import flops_moe


def _dims(mc: dict):
    return mc["hidden_size"], mc["num_heads"], mc.get("v_head_dim") or mc["head_dim"]


def indexer_params(mc: dict) -> int:
    """wq_b (from the compressed query), wk, weights_proj, the key norm's scale and bias."""
    D, Hi, di = mc["hidden_size"], mc["index_heads"], mc["index_head_dim"]
    return mc["q_lora_rank"] * Hi * di + D * di + D * Hi + 2 * di


def mixer_params(mc: dict) -> int:
    """q_a, its norm, q_b, kv_a (latent + rotated key), its norm, kv_b, o, the indexer."""
    D, H, dv = _dims(mc)
    rank, nope, rot, q_rank = (mc["kv_lora_rank"], mc["qk_nope_head_dim"], mc["qk_rope_head_dim"],
                               mc["q_lora_rank"])
    return (D * q_rank + q_rank + q_rank * H * (nope + rot) + D * (rank + rot) + rank
            + rank * H * (nope + dv) + H * dv * D + indexer_params(mc))


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
    return (mc["kv_lora_rank"] + mc["qk_rope_head_dim"]) * kv_bytes


def index_key_bytes(mc: dict, kv_bytes: int = 2) -> int:
    return mc["index_head_dim"] * kv_bytes


def cache_bytes_per_token(mc: dict, kv_bytes: int = 2) -> int:
    """What one token caches in every layer: the latent row and the index key."""
    return len(mc["layer_types"]) * (latent_row_bytes(mc, kv_bytes) + index_key_bytes(mc, kv_bytes))


def stored_bytes_per_token(mc: dict, kv_bytes: int = 2, lanes: int = 128) -> int:
    """What the program's two pools hold a token: the latent row is stored as
    whole lane tiles (576 values in 640), because single rows are gathered."""
    row = -(-(mc["kv_lora_rank"] + mc["qk_rope_head_dim"]) // lanes) * lanes
    return len(mc["layer_types"]) * (row + mc["index_head_dim"]) * kv_bytes


def dsa_decode_step(mc: dict, context_tokens: list, kv_bytes: int = 2) -> dict:
    """One token step's selection and attention in EVERY layer, for live slots
    that hold the given numbers of context tokens: each slot's index keys are
    read once (every one has to be scored) and its ``min(index_topk, context)``
    chosen latent rows once; the index products and the absorbed attention over
    the chosen rows are the operations. What any implementation has to do: no
    top-k, no table look-up, no second pass is counted."""
    _, H, _ = _dims(mc)
    rank, rot = mc["kv_lora_rank"], mc["qk_rope_head_dim"]
    Hi, di, topk, L = mc["index_heads"], mc["index_head_dim"], mc["index_topk"], len(mc["layer_types"])
    ctx = float(sum(context_tokens))
    chosen = float(sum(min(topk, c) for c in context_tokens))
    return {"flops": L * (2.0 * Hi * di * ctx + 2.0 * H * (2 * rank + rot) * chosen),
            "bytes": L * (ctx * index_key_bytes(mc, kv_bytes) + chosen * latent_row_bytes(mc, kv_bytes))}


def decode_weight_bytes(mc: dict, experts_hit_per_layer: float, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step must stream: every layer's mixer and
    indexer, the dense feed-forward or the router, the shared expert and the
    experts that got a row, the output head."""
    n = 0.0
    for ffn in mc["ffn_types"]:
        n += mixer_params(mc)
        n += flops_moe.dense_ffn_params(mc) if ffn == "dense" else (
            flops_moe.router_params(mc) + flops_ling.shared_expert_params(mc)
            + experts_hit_per_layer * flops_moe.expert_params(mc))
    return (n + mc["vocab_size"] * mc["hidden_size"]) * weight_bytes
