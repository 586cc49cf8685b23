"""Engine scheduler: share of the traced window's device-idle seconds under ``dtx_engine_wait_empty``
(nothing had been asked of the engine). The closed-loop cells' reading of it: two clients a slot, so a
share above a few percent means the generator starves the engine."""
import cause_readers


def read(obs):
    return cause_readers.idle_starved_share(obs)
