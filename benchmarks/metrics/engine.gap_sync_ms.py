"""Engine scheduler: device-idle milliseconds under ``dtx_engine_decode_sync`` (from the end of the
decode program to the host's waking with the chunk's tokens) per ``dtx_engine_decode`` span in the
traced window. The open-loop cell's reading of it."""
import cause_readers


def read(obs):
    return cause_readers.gap_sync_ms(obs)
