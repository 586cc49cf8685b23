"""Model step, serve: device time of the decode program per token step
(median execution of ``_decode_impl`` in the device trace over its scanned steps)."""
import readers


def read(obs):
    return readers.decode_step_ms(obs)
