"""Token selection: a token step's selection and attention as a share of its roofline (memory-bound: one read of the
live slots' index keys and of their chosen latent rows in every layer). ``glm_readers.dsa_decode_roofline``."""
import glm_readers


def read(obs):
    return glm_readers.dsa_decode_roofline(obs)
