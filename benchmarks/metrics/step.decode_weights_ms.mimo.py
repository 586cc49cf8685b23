"""Model step, serve: self time of the decode program's device ops that stream dense weights (``dtx.qkv``,
``dtx.attn_out``, ``dtx.mlp``, ``dtx.unembed``), per token step."""
import moe_readers
import scope_readers


def read(obs):
    return moe_readers.decode_region_ms(obs, scope_readers.WEIGHTS)
