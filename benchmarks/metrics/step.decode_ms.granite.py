"""Model step, serve: device time of the decode program per token step, in the state-space cell."""
import granite_readers


def read(obs):
    return granite_readers.decode_step_ms(obs)
