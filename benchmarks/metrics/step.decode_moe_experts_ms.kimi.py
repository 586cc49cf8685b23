"""Expert feed-forward: self time of the decode program's grouped matmuls (``dtx.moe_experts``, and XLA's
``ragged-dot`` kernels), per token step, in the agent-session cell."""
import kimi_readers


def read(obs):
    return kimi_readers.decode_region_ms(obs, (kimi_readers.moe_readers.EXPERTS,))
