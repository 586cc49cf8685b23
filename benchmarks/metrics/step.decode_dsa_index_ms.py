"""Token selection: self time of the decode program's device ops under ``dtx.dsa_index`` (the indexer's projections,
the key's norm, the rotation, the index scores over every cached index key of a slot), per token step."""
import glm_readers


def read(obs):
    return glm_readers.decode_region_ms(obs, glm_readers.DSA_INDEX)
