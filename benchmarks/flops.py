"""Operations and bytes that the algorithm needs, from shapes alone. Kept with
the benchmark so that no PR that claims a gain can change what a roofline share
or a utilization is measured against. A multiply-add counts as 2 operations.
"""

from __future__ import annotations


def layer_params(mc: dict) -> int:
    D, F = mc["hidden_size"], mc["intermediate_size"]
    hd = mc.get("head_dim") or D // mc["num_heads"]
    q, kv = mc["num_heads"] * hd, mc["num_kv_heads"] * hd
    n = D * q + 2 * D * kv + q * D + 3 * D * F + 2 * D
    if mc.get("attention_bias"):
        n += q + 2 * kv
    return n


def matmul_params(mc: dict) -> int:
    """Parameters that a token is multiplied by: every layer's projections and
    the output head (the embedding is a gather)."""
    D, F = mc["hidden_size"], mc["intermediate_size"]
    hd = mc.get("head_dim") or D // mc["num_heads"]
    q, kv = mc["num_heads"] * hd, mc["num_kv_heads"] * hd
    per_layer = D * q + 2 * D * kv + q * D + 3 * D * F
    return mc["num_layers"] * per_layer + D * mc["vocab_size"]


def total_params(mc: dict) -> int:
    n = mc["num_layers"] * layer_params(mc) + mc["vocab_size"] * mc["hidden_size"] + mc["hidden_size"]
    if not mc.get("tie_word_embeddings"):
        n += mc["hidden_size"] * mc["vocab_size"]
    return n


def attention_flops(mc: dict, q_tokens: int, kv_tokens: float) -> float:
    """QK^T and PV for ``q_tokens`` queries that each see ``kv_tokens`` keys, all layers."""
    hd = mc.get("head_dim") or mc["hidden_size"] // mc["num_heads"]
    return mc["num_layers"] * 2 * 2 * mc["num_heads"] * hd * q_tokens * kv_tokens


def train_flops_per_token_lora(mc: dict, seq_len: int) -> float:
    """Required operations to train one token with LoRA on a frozen base: the
    forward pass (2 per matmul parameter) and the backward pass through the
    activations (2 more); weight gradients of the base are never formed, and
    the adapters' own are negligible and left out. Causal attention sees on
    average half the row. Recomputation under remat is not required work."""
    n = matmul_params(mc)
    return 4.0 * n + 2.0 * attention_flops(mc, 1, seq_len / 2.0)


def paged_decode_attention(mc: dict, context_tokens: list, kv_bytes: int = 2) -> dict:
    """One decode step's attention in ONE layer for a batch whose rows hold the
    given numbers of context tokens: each row reads its K and V once."""
    hd = mc.get("head_dim") or mc["hidden_size"] // mc["num_heads"]
    ctx = float(sum(context_tokens))
    rows = len(context_tokens)
    return {
        "flops": 2 * 2 * mc["num_heads"] * hd * ctx,
        "bytes": 2 * mc["num_kv_heads"] * hd * ctx * kv_bytes
        + 2 * rows * mc["num_heads"] * hd * 2,
    }


def fused_sample(vocab: int, rows: int, passes: int, logit_bytes: int = 4) -> dict:
    """The sampling epilogue reads each row of logits ``passes`` times (1 for
    greedy: a max; 3 when it samples: max, normaliser, inverse CDF)."""
    return {"flops": float(5 * vocab * rows * passes),
            "bytes": float(vocab * rows * passes * logit_bytes)}


def roofline_seconds(work: dict, peaks: dict) -> dict:
    t_c, t_m = work["flops"] / peaks["bf16_flops"], work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}
