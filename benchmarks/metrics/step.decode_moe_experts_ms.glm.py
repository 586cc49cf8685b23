"""Expert feed-forward: self time of the decode program's grouped matmuls (``dtx.moe_experts``, and XLA's
``ragged-dot`` kernels), per token step, in the sparse-attention cell."""
import glm_readers


def read(obs):
    return glm_readers.decode_region_ms(obs, (glm_readers.moe_readers.EXPERTS,))
