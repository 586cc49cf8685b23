"""Model step, serve: self time of the decode program's device ops under no ``dtx.`` scope (and not a
``ragged-dot`` kernel), over the program's device time in the window, in the agent-session cell."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_unscoped_share(obs)
