"""Model step, serve: device time of prefill per 256-token chunk, in the sparse-expert cell."""
import moe_readers


def read(obs):
    return moe_readers.prefill_chunk_ms(obs, 256)
