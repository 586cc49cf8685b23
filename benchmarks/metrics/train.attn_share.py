"""Model step, train: device-busy seconds in ops under ``dtx.attn`` (forward, recomputed and
transposed), over the busy seconds of the window's steps."""
import scope_readers


def read(obs):
    return scope_readers.busy_share(obs, lambda op: scope_readers.region_of(op) == "dtx.attn")
