"""Device: share of the traced window in which no operation ran on the chip. Depth is cut to 5 layers of 61, so
the host's share is an upper bound on a deployment's."""
import kimi_readers


def read(obs):
    return kimi_readers.idle_share(obs)
