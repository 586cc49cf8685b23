"""Expert feed-forward: the grouped matmuls' share of their roofline in decode (memory-bound: the weights
of the experts that got a row). ``moe_readers.experts_roofline``."""
import moe_readers


def read(obs):
    return moe_readers.experts_roofline(obs)
