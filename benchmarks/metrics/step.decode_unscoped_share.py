"""Model step, serve: self time of the decode program's device ops under no ``dtx.`` scope, over
the program's device time in the window. Over 15 % means that a scope is missing."""
import scope_readers


def read(obs):
    return scope_readers.decode_unscoped_share(obs)
