"""Model step, serve: self time of the decode program's device ops that stream dense weights (``dtx.qkv`` with the
Mamba layers' ``in_proj``, ``dtx.attn_out`` with their ``out_proj``, ``dtx.mlp``, ``dtx.unembed``: the tied embedding), per token step."""
import granite_readers


def read(obs):
    return granite_readers.decode_region_ms(obs, granite_readers.WEIGHTS)
