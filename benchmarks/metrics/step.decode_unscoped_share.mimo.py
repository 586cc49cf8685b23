"""Model step, serve: self time of the decode program's device ops under no ``dtx.`` scope (and not a
``ragged-dot`` kernel), over the program's device time in the window."""
import moe_readers


def read(obs):
    return moe_readers.decode_unscoped_share(obs)
