"""Expert feed-forward: most rows on one held expert over the mean rows a held expert, per decode step and
expert layer (sums over the measured window), in the linear-attention cell."""
import ling_readers


def read(obs):
    return ling_readers.load_max_over_mean(obs)
