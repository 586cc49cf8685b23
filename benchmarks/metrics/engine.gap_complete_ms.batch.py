"""Engine scheduler: device-idle milliseconds under ``dtx_engine_complete`` (``_complete``: the
latency histograms, the request's span into the trace ring, the client woken) per
``dtx_engine_decode`` span in the traced window."""
import cause_readers


def read(obs):
    return cause_readers.gap_ms(obs, (cause_readers.COMPLETE,))
