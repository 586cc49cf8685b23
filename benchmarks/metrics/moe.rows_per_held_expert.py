"""Expert feed-forward: (token, expert) rows a held expert gets per decode step and expert layer, from the
engine's counters over the measured window."""
import moe_readers


def read(obs):
    return moe_readers.rows_per_held_expert(obs)
