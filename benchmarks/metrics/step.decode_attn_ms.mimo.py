"""Model step, serve: self time of the decode program's device ops under ``dtx.attn`` (attention over the
gathered views, full width in global layers and window-wide in window layers), per token step."""
import moe_readers


def read(obs):
    return moe_readers.decode_region_ms(obs, ("dtx.attn",))
