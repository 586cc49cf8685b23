"""Model step, serve: self time of the decode program's device ops that move the latent pool (``dtx.kv_write``:
the scatter of the new row and the gather of the view; ``dtx.layers`` alone: what the scans move), per token step."""
import ling_readers
import scope_readers


def read(obs):
    return ling_readers.decode_region_ms(obs, scope_readers.KV_POOL)
