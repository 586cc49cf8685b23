"""Model step, serve: self time of the decode program's device ops under the scopes that stream weights (``dtx.qkv``,
``dtx.attn_out``, ``dtx.mlp``, ``dtx.moe_shared``, ``dtx.unembed``), per token step, in the agent-session cell."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_region_ms(obs, kimi_readers.WEIGHTS)
