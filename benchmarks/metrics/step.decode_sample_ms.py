"""Model step, serve: self time of the decode program's device ops under ``dtx.sample``, the
sampler of the decode step; per token step (executions of ``_decode_impl`` in the window times
its scanned steps)."""
import scope_readers


def read(obs):
    return scope_readers.decode_region_ms(obs, ("dtx.sample",))
