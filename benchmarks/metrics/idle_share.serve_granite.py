"""Device: share of the traced window in which no operation ran on the chip. The configuration is at full depth:
this idle share is a deployment's own, no upper bound."""
import granite_readers


def read(obs):
    return granite_readers.idle_share(obs)
