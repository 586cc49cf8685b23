"""Expert feed-forward: self time of the decode program's device ops under ``dtx.moe_route`` (norm, router
matmul, sigmoid, top-k, sort, the gather of rows) and ``dtx.moe_combine`` (back to pair order, weighting,
the sum), per token step."""
import moe_readers


def read(obs):
    return moe_readers.decode_region_ms(obs, moe_readers.ROUTE)
