"""Model step, serve: self time of the decode program's device ops that move the KV pools (``dtx.kv_write``:
the scatter of the new token and the gather of the views; ``dtx.layers`` alone: what the scans move), per token step."""
import moe_readers
import scope_readers


def read(obs):
    return moe_readers.decode_region_ms(obs, scope_readers.KV_POOL)
