"""Expert feed-forward: self time of the decode program's device ops under ``dtx.moe_experts`` (the three
grouped matmuls, XLA's ``ragged-dot`` kernels among them, and the activation), per token step."""
import moe_readers


def read(obs):
    return moe_readers.decode_region_ms(obs, (moe_readers.EXPERTS,))
