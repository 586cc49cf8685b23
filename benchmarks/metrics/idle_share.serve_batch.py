"""Device: share of the traced window in which no operation ran on the chip
(1 - union of the device's op intervals over the window). Depth is cut to 16
layers, so the host's share is larger here than in a 32-layer deployment."""
import readers


def read(obs):
    return readers.idle_share(obs)
