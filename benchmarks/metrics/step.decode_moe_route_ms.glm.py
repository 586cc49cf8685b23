"""Expert feed-forward: self time of the decode program's device ops under ``dtx.moe_route`` and
``dtx.moe_combine``, per token step, in the sparse-attention cell."""
import glm_readers


def read(obs):
    return glm_readers.decode_region_ms(obs, glm_readers.moe_readers.ROUTE)
