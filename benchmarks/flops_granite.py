"""Parameters, operations and bytes of a ``granitemoehybrid`` (Granite 4.0-H)
configuration, from shapes alone: the Mamba-2 and attention mixers, the
recurrent state a slot keeps, the KV a token caches, the weights one decode
step streams. Kept with the benchmark so that no PR that claims a gain can
change what a roofline share is measured against. A multiply-add counts as 2
operations; ``mc`` is the configuration file's ``model_config``.
"""

from __future__ import annotations

from reference.granite_v4 import ssm_dims  # (H, P, N, G, K) of a Mamba-2 mixer


def conv_channels(mc: dict) -> int:
    H, P, N, G, _ = ssm_dims(mc)
    return H * P + 2 * G * N


def ssm_params(mc: dict) -> int:
    """in_proj D x [z | x B C | dt], out_proj, the convolution with its bias,
    A_log, D and dt_bias per head, the gated norm's scale."""
    D = mc["hidden_size"]
    H, P, _, _, K = ssm_dims(mc)
    C = conv_channels(mc)
    return D * (H * P + C + H) + H * P * D + C * K + C + 3 * H + H * P


def attention_params(mc: dict) -> int:
    D, H, KV, d = mc["hidden_size"], mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    return D * H * d + 2 * D * KV * d + H * d * D


def mixer_params(mc: dict, kind: str) -> int:
    return {"ssm": ssm_params, "global": attention_params}[kind](mc)


def dense_ffn_params(mc: dict) -> int:
    return 3 * mc["hidden_size"] * mc["intermediate_size"]


def layer_params(mc: dict, kind: str) -> int:
    """One layer whole: mixer, SwiGLU, two norms."""
    return mixer_params(mc, kind) + dense_ffn_params(mc) + 2 * mc["hidden_size"]


def total_params(mc: dict) -> int:
    """Every layer, the final norm and the embedding ONCE (the head is tied to it)."""
    return (sum(layer_params(mc, k) for k in mc["layer_types"])
            + mc["vocab_size"] * mc["hidden_size"] + mc["hidden_size"])


def kv_bytes_per_token(mc: dict, kv_bytes: int = 2) -> int:
    """What one token caches: k and v of every attention layer's KV heads."""
    return mc["layer_types"].count("global") * 2 * mc["num_kv_heads"] * mc["head_dim"] * kv_bytes


def state_bytes_per_slot_layer(mc: dict, state_bytes: int = 4, conv_bytes: int = 2) -> int:
    """What ONE Mamba-2 layer keeps for one slot, whatever its context: the
    state per head and the last ``kernel - 1`` pre-convolution rows of [x | B | C]."""
    H, P, N, _, K = ssm_dims(mc)
    return H * P * N * state_bytes + (K - 1) * conv_channels(mc) * conv_bytes


def state_bytes_per_slot(mc: dict, state_bytes: int = 4, conv_bytes: int = 2) -> int:
    """What a slot keeps in ALL its Mamba-2 layers."""
    return mc["layer_types"].count("ssm") * state_bytes_per_slot_layer(mc, state_bytes, conv_bytes)


def ssm_state_step(mc: dict, live_slots: float, state_bytes: int = 4, conv_bytes: int = 2) -> dict:
    """One token step of EVERY Mamba-2 layer for ``live_slots`` slots: each
    slot's state and convolution rows are read once and written once, in the
    type they are stored in; per head the decay, the rank-one update and the
    read-out ``S C`` are 3 passes of ``P x N`` multiply-adds. Whatever
    implements the update has this to do."""
    H, P, N, _, _ = ssm_dims(mc)
    layers = mc["layer_types"].count("ssm")
    return {"flops": 2.0 * 3 * H * P * N * live_slots * layers,
            "bytes": 2.0 * state_bytes_per_slot(mc, state_bytes, conv_bytes) * live_slots}


def attention_decode_step(mc: dict, context_tokens: list, kv_bytes: int = 2) -> dict:
    """One decode step's attention in ONE attention layer: every q head meets
    each cached key and value of its KV head; a row is read once."""
    H, KV, d = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    ctx = float(sum(context_tokens))
    return {"flops": 2.0 * 2 * H * d * ctx,
            "bytes": 2 * KV * d * ctx * kv_bytes + len(context_tokens) * 2 * H * d * 2}


def decode_weight_bytes(mc: dict, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step must stream: every layer's mixer and
    feed-forward, and the tied embedding once, as the output head."""
    n = sum(mixer_params(mc, k) + dense_ffn_params(mc) for k in mc["layer_types"])
    return (n + mc["vocab_size"] * mc["hidden_size"]) * weight_bytes
