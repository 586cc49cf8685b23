"""Latent attention: a token step's latent attention (``dtx.attn``, ``dtx.mla_absorb``, ``dtx.kv_write``) as a share of its
roofline (memory-bound: ONE read of the live slots' latent rows, a row written a slot, ``kv_b_proj`` once, in every layer).
``kimi_readers.mla_decode_roofline``."""
import kimi_readers


def read(obs):
    return kimi_readers.mla_decode_roofline(obs)
