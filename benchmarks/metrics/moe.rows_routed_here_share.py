"""Expert feed-forward: of the real rows the expert layers routed in decode, the share with at least one
chosen expert held on this chip (under group-limited routing a row may keep none of the held group)."""
import ling_readers


def read(obs):
    return ling_readers.rows_routed_here_share(obs)
