"""Model step, serve: device time of the prefill-chunk programs per 256 prompt tokens, in the sparse-attention cell."""
import glm_readers


def read(obs):
    return glm_readers.prefill_chunk_ms(obs, 256)
