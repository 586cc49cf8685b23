"""Linear-attention state: self time of the decode program's device ops under ``dtx.kda_out`` (the head norm of
the read-out and the channel-wise output gate), per token step."""
import ling_readers


def read(obs):
    return ling_readers.kda_region_ms(obs, ling_readers.KDA_OUT)
