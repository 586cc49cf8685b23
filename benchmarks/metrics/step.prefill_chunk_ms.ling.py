"""Model step, serve: device time of prefill per 256-token chunk, in the linear-attention cell."""
import ling_readers


def read(obs):
    return ling_readers.prefill_chunk_ms(obs, 256)
