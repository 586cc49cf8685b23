"""Expert feed-forward: self time of the decode program's device ops under ``dtx.moe_route`` and
``dtx.moe_combine``, per token step, in the agent-session cell."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_region_ms(obs, kimi_readers.moe_readers.ROUTE)
