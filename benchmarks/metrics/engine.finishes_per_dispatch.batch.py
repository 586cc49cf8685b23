"""Engine scheduler: requests that ended per decode chunk: ``dtx_engine_complete`` spans over
``dtx_engine_decode`` spans inside the traced window. What ``engine.gap_release_ms.batch`` and
``engine.gap_complete_ms.batch`` are divided by to read as milliseconds a finish."""
import cause_readers


def read(obs):
    return cause_readers.finishes_per_dispatch(obs)
