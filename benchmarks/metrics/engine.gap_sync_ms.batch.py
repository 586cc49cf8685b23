"""Engine scheduler: device-idle milliseconds under ``dtx_engine_decode_sync`` (program end to host
wake-up) per ``dtx_engine_decode`` span in the traced window. The closed-loop cells' reading of it."""
import cause_readers


def read(obs):
    return cause_readers.gap_sync_ms(obs)
