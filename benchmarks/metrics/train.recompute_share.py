"""Model step, train: device-busy seconds in ops that ``jax.checkpoint`` recomputes in the backward
pass (``rematted_computation`` in the op's name stack), over the busy seconds of the window's
steps."""
import scope_readers


def read(obs):
    return scope_readers.busy_share(obs, lambda op: scope_readers.REMAT_MARK in op)
