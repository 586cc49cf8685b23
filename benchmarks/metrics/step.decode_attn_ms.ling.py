"""Model step, serve: self time of the decode program's device ops under ``dtx.attn`` (scores and values over
the gathered latent rows) and ``dtx.mla_absorb`` (the two absorbed products), per token step."""
import ling_readers


def read(obs):
    return ling_readers.decode_region_ms(obs, ling_readers.ATTN)
