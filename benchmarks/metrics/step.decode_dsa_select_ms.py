"""Token selection: self time of the decode program's device ops under ``dtx.dsa_select`` (the exact top-k of a slot's
index scores, which picks are real), per token step."""
import glm_readers


def read(obs):
    return glm_readers.decode_region_ms(obs, glm_readers.DSA_SELECT)
