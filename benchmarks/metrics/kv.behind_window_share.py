"""KV manager: share of the window layers' live pool held by blocks that no later query can see (they stay
allocated until the request ends), sampled about once a second over the measured window."""
import moe_readers


def read(obs):
    return moe_readers.kv_behind_window_share(obs)
