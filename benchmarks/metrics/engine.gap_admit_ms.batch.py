"""Engine scheduler: device-idle milliseconds of the traced window whose innermost covering
scheduler span is ``dtx_engine_admit``, its child ``dtx_engine_adapter_acquire`` included, per
``dtx_engine_decode`` span in the window. The closed-loop batch cell's reading of it."""
import tick_readers


def read(obs):
    return tick_readers.gap_ms(obs, tick_readers.ADMIT)
