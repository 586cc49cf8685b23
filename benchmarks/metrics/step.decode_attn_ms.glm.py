"""Model step, serve: self time of the decode program's device ops under ``dtx.dsa_gather`` (the chosen latent rows,
through the block table), ``dtx.attn`` (scores and values over them) and ``dtx.mla_absorb``, per token step."""
import glm_readers


def read(obs):
    return glm_readers.decode_region_ms(obs, glm_readers.ATTN)
