"""Token selection: device time per 256 prompt tokens that the prefill-chunk programs spend under ``dtx.dsa_index``,
``dtx.dsa_select`` (a top-k a row of the chunk, the mask), ``dtx.dsa_gather`` and ``dtx.attn`` (the masked view)."""
import glm_readers


def read(obs):
    return glm_readers.prefill_dsa_ms(obs, 256)
