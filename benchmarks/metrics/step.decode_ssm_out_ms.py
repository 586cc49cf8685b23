"""State-space state: self time of the decode program's device ops under ``dtx.ssm_out`` (the gate ``y * silu(z)``
and the gated norm over 4,096 channels), per token step."""
import granite_readers


def read(obs):
    return granite_readers.ssm_region_ms(obs, granite_readers.SSM_OUT)
