"""Latent attention: device time per 256 prompt tokens that the prefill-chunk programs spend under ``dtx.attn``,
``dtx.mla_absorb`` and ``dtx.kv_write`` (the scatter and the view of the slot's rows)."""
import kimi_readers


def read(obs):
    return kimi_readers.prefill_mla_ms(obs, 256)
