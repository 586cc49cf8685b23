"""Model step, serve: device time of the prefill-chunk programs per 256 prompt tokens, in the agent-session cell."""
import kimi_readers


def read(obs):
    return kimi_readers.prefill_chunk_ms(obs, 256)
