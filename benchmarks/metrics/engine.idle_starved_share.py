"""Engine scheduler: share of the traced window's device-idle seconds whose innermost covering
scheduler span is ``dtx_engine_wait_empty``: the chip had nothing to run because nothing had been
asked of the engine (against ``dtx_engine_wait_blocked`` and the host at work). The open-loop cell's
reading of it."""
import cause_readers


def read(obs):
    return cause_readers.idle_starved_share(obs)
