"""State-space state: the recurrent-state update's share of its roofline in decode (memory-bound: one read and
one write of the live slots' state and convolution rows in every Mamba-2 layer). ``granite_readers.ssm_state_roofline``."""
import granite_readers


def read(obs):
    return granite_readers.ssm_state_roofline(obs)
