"""Model step, serve: self time of the decode program's device ops under ``dtx.kv_write``, or under
``dtx.layers`` and no inner scope: the KV write, the gather of the view, and what the layer scan
moves of the pool; per token step (executions of ``_decode_impl`` in the window times its
scanned steps). The closed-loop batch cell's reading of it."""
import scope_readers


def read(obs):
    return scope_readers.decode_region_ms(obs, scope_readers.KV_POOL)
