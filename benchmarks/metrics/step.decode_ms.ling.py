"""Model step, serve: device time of the decode program per token step, in the linear-attention cell."""
import ling_readers


def read(obs):
    return ling_readers.decode_step_ms(obs)
