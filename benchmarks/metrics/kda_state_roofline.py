"""Linear-attention state: the recurrent-state update's share of its roofline in decode (memory-bound: one read
and one write of the live slots' state and convolution rows in every KDA layer). ``ling_readers.kda_state_roofline``."""
import ling_readers


def read(obs):
    return ling_readers.kda_state_roofline(obs)
