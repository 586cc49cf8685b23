"""Parameters, operations and bytes of a ``mimo_v2`` configuration, from shapes
alone: global and window attention apart, the router, the experts by rows
routed and by experts hit. Kept with the benchmark so that no PR that claims a
gain can change what a roofline share is measured against. A multiply-add
counts as 2 operations; ``mc`` is the configuration file's ``model_config``.
"""

from __future__ import annotations


def kv_heads(mc: dict, kind: str) -> int:
    return mc["window_num_kv_heads"] if kind == "window" else mc["num_kv_heads"]


def attention_params(mc: dict, kind: str) -> int:
    """q, k, v and o projections of one layer (+ one sink logit a head in a window layer)."""
    D, H, d, dv = mc["hidden_size"], mc["num_heads"], mc["head_dim"], mc["v_head_dim"]
    KV = kv_heads(mc, kind)
    n = D * H * d + D * KV * d + D * KV * dv + H * dv * D
    if kind == "window" and mc.get("window_sink"):
        n += H
    return n


def router_params(mc: dict) -> int:
    return mc["hidden_size"] * mc["experts_total"] + mc["experts_total"]


def expert_params(mc: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * mc["hidden_size"] * mc["expert_intermediate_size"]


def dense_ffn_params(mc: dict) -> int:
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def layer_params(mc: dict, kind: str, ffn: str) -> int:
    """One layer as this chip holds it (``experts_held`` of its experts), norms included."""
    n = attention_params(mc, kind) + 2 * mc["hidden_size"]
    if ffn == "dense":
        return n + dense_ffn_params(mc)
    return n + router_params(mc) + mc["experts_held"] * expert_params(mc)


def total_params(mc: dict) -> int:
    n = sum(layer_params(mc, k, f) for k, f in zip(mc["layer_types"], mc["ffn_types"]))
    return n + 2 * mc["vocab_size"] * mc["hidden_size"] + mc["hidden_size"]


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2) -> int:
    return sum(kv_heads(mc, k) * (mc["head_dim"] + mc["v_head_dim"])
               for k in mc["layer_types"]) * kv_bytes


def expert_layer_step(mc: dict, rows: float, experts_hit: float, weight_bytes: int = 2,
                      act_bytes: int = 2) -> dict:
    """The three grouped matmuls of ONE expert layer in one step: ``rows``
    (token, expert) rows routed to held experts, of which ``experts_hit`` got
    at least one. Each hit expert's weights are read once; each row is read
    (D), its gate and up written and read (F each, float32 out of the first
    two matmuls counted at ``act_bytes`` as the activation's type), its result
    written (D)."""
    D, F = mc["hidden_size"], mc["expert_intermediate_size"]
    return {"flops": 2.0 * 3 * D * F * rows,
            "bytes": float(experts_hit * expert_params(mc) * weight_bytes
                           + rows * (2 * D + 3 * F) * act_bytes)}


def attention_decode_step(mc: dict, kind: str, context_tokens: list, kv_bytes: int = 2) -> dict:
    """One decode step's attention in ONE layer of ``kind`` for a batch whose
    rows hold the given numbers of context tokens; a window layer reads at most
    its window of them."""
    H, d, dv = mc["num_heads"], mc["head_dim"], mc["v_head_dim"]
    if kind == "window":
        context_tokens = [min(c, mc["sliding_window"]) for c in context_tokens]
    ctx, rows = float(sum(context_tokens)), len(context_tokens)
    return {"flops": 2.0 * H * (d + dv) * ctx,
            "bytes": kv_heads(mc, kind) * (d + dv) * ctx * kv_bytes + rows * H * (d + dv) * 2}


def decode_weight_bytes(mc: dict, experts_hit_per_layer: float, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step must stream: every layer's attention,
    router and dense feed-forward, the experts that got a row, the output head."""
    n = 0.0
    for kind, ffn in zip(mc["layer_types"], mc["ffn_types"]):
        n += attention_params(mc, kind)
        n += dense_ffn_params(mc) if ffn == "dense" else (
            router_params(mc) + experts_hit_per_layer * expert_params(mc))
    return (n + mc["vocab_size"] * mc["hidden_size"]) * weight_bytes
