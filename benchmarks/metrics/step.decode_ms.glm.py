"""Model step, serve: device time of the decode program per token step, in the sparse-attention cell."""
import glm_readers


def read(obs):
    return glm_readers.decode_step_ms(obs)
