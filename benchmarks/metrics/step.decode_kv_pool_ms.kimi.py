"""Model step, serve: self time of the decode program's device ops under ``dtx.kv_write`` (the scatter of the token's
latent row and the gathered view of each slot's table) and under ``dtx.layers`` alone (what the layer scan itself moves), per token step."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_region_ms(obs, kimi_readers.KV_POOL)
