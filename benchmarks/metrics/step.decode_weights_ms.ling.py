"""Model step, serve: self time of the decode program's device ops that stream dense weights (``dtx.qkv``,
``dtx.attn_out``, ``dtx.mlp``, ``dtx.moe_shared``, ``dtx.unembed``), per token step."""
import ling_readers


def read(obs):
    return ling_readers.decode_region_ms(obs, ling_readers.WEIGHTS)
