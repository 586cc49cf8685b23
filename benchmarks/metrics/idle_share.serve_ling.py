"""Device: share of the traced window in which no operation ran on the chip. Depth is cut to 7 layers, so
the host's share is an upper bound on a deployment's."""
import ling_readers


def read(obs):
    return ling_readers.idle_share(obs)
