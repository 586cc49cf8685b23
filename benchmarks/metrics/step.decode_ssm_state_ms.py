"""State-space state: self time of the decode program's device ops under ``dtx.ssm_state`` (reset, decay,
update, read-out, write-back of the state leaves) and ``dtx.ssm_conv`` (the short convolution with its bias and
its rows, the split, ``dt`` and the decay), per token step."""
import granite_readers


def read(obs):
    return granite_readers.ssm_region_ms(obs, granite_readers.SSM_STATE)
