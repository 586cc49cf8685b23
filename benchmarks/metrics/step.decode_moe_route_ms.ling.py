"""Expert feed-forward: self time of the decode program's device ops under ``dtx.moe_route`` (router, group-limited
selection, sort) and ``dtx.moe_combine``, per token step, in the linear-attention cell."""
import ling_readers
import moe_readers


def read(obs):
    return ling_readers.decode_region_ms(obs, moe_readers.ROUTE)
