"""Linear-attention state: device time per 256 prompt tokens that the prefill-chunk programs spend under the KDA
layers' scopes (``dtx.kda_conv``, ``dtx.kda_state``: the chunk form in 64-row sub-chunks, ``dtx.kda_out``)."""
import ling_readers


def read(obs):
    return ling_readers.prefill_kda_ms(obs, 256)
