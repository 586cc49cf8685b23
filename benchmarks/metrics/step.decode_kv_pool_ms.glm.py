"""Model step, serve: self time of the decode program's device ops under ``dtx.kv_write`` (both scatters: the latent
row and the index key) and under ``dtx.layers`` alone (what the layer scan itself moves), per token step."""
import glm_readers


def read(obs):
    return glm_readers.decode_region_ms(obs, glm_readers.KV_POOL)
