"""Expert feed-forward: (token, expert) rows a held expert gets per decode step and expert layer, from the
engine's counters over the measured window, in the linear-attention cell."""
import ling_readers


def read(obs):
    return ling_readers.rows_per_held_expert(obs)
