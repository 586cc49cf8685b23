"""Model step, serve: self time of the decode program's device ops that move the KV pool (``dtx.kv_write``: the
scatter of the step's rows and the gather of each slot's view; ``dtx.layers`` alone: what the scans move), per token step."""
import granite_readers


def read(obs):
    return granite_readers.decode_region_ms(obs, granite_readers.KV_POOL)
