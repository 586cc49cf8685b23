"""State-space state: device time per 256 prompt tokens that the prefill-chunk programs spend under the Mamba-2
layers' scopes (``dtx.ssm_conv``, ``dtx.ssm_state``: the SSD chunk form, one masked [256, 256] product a head,
``dtx.ssm_out``)."""
import granite_readers


def read(obs):
    return granite_readers.prefill_ssm_ms(obs, 256)
