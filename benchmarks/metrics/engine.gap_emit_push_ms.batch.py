"""Engine scheduler: device-idle milliseconds under ``dtx_engine_emit_push`` (the ``chunk x slots``
loop that hands each token to its request) per ``dtx_engine_decode`` span in the traced window:
the part of emission that goes with the tokens of a chunk."""
import cause_readers


def read(obs):
    return cause_readers.emit_push_ms(obs)
