"""Model step, serve: device time of the decode program per token step, in the sparse-expert cell."""
import moe_readers


def read(obs):
    return moe_readers.decode_step_ms(obs)
