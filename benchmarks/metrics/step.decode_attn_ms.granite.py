"""Model step, serve: self time of the decode program's device ops under ``dtx.attn`` (scores and values over
each slot's gathered view, four attention layers without positions), per token step."""
import granite_readers


def read(obs):
    return granite_readers.decode_region_ms(obs, granite_readers.ATTN)
