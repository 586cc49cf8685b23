"""Engine scheduler: occupied slots per decode dispatch, as a share of the
engine's slots. Dispatches are the ``dtx_engine_decode`` spans of the traced
window; a slot is occupied from its request's ``activate`` to its ``finish`` mark."""
import readers


def read(obs):
    return readers.decode_occupancy(obs)
