"""Model step, serve: device time of the decode program per token step, in the agent-session cell."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_step_ms(obs)
