"""Model step, serve: self time of the decode program's device ops under no ``dtx.`` scope, over the program's
device time in the window, in the state-space cell."""
import granite_readers


def read(obs):
    return granite_readers.decode_unscoped_share(obs)
