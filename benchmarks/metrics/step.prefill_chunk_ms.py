"""Model step, serve: device time of prefill per 256-token chunk (all
``_prefill_chunk_impl`` executions of the traced window over the prompt tokens
the engine marked as prefilled in it, times 256)."""
import readers


def read(obs):
    return readers.prefill_chunk_ms(obs, 256)
