"""Model step, serve: self time of the decode program's device ops under ``dtx.attn``: the paged
kernel, or attention over the gathered view; per token step (executions of ``_decode_impl`` in
the window times its scanned steps)."""
import scope_readers


def read(obs):
    return scope_readers.decode_region_ms(obs, ("dtx.attn",))
