"""Linear-attention state: self time of the decode program's device ops under ``dtx.kda_state`` (reset, decay,
delta update, read-out, write-back of the state leaves) and ``dtx.kda_conv`` (the short convolution and its
rows, q/k normalisation, the decay gate), per token step."""
import ling_readers


def read(obs):
    return ling_readers.kda_region_ms(obs, ling_readers.KDA_STATE)
