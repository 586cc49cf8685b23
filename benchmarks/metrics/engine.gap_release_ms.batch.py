"""Engine scheduler: device-idle milliseconds under ``dtx_engine_release`` (``_release_slot``: the
slot's bookkeeping, its row of the block table cleared on the device, its blocks freed) per
``dtx_engine_decode`` span in the traced window: the part of emission, and of any other path that
gives a slot up, that goes with the requests that end."""
import cause_readers


def read(obs):
    return cause_readers.gap_ms(obs, (cause_readers.RELEASE,))
