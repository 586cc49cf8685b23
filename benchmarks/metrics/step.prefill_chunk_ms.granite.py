"""Model step, serve: device time of prefill per 256-token chunk, in the state-space cell."""
import granite_readers


def read(obs):
    return granite_readers.prefill_chunk_ms(obs, 256)
