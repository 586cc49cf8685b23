"""Model step, serve: self time of the decode program's device ops under ``dtx.attn`` (scores and values over each slot's
view of its latent rows) and ``dtx.mla_absorb`` (``kv_b_proj`` into the query and out of the output), per token step."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_region_ms(obs, kimi_readers.ATTN)
