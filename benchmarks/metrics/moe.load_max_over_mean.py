"""Expert feed-forward: most rows on one held expert over the mean rows a held expert, per decode step and
expert layer (sums over the measured window)."""
import moe_readers


def read(obs):
    return moe_readers.load_max_over_mean(obs)
