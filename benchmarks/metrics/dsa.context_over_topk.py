"""Token selection: mean live context over mean tokens selected, of the traced window's decode rows (the program's
counters ``dsa_stats``, read off its decode spans): how many cached tokens a query sees for each one it reads."""
import glm_readers


def read(obs):
    return glm_readers.context_over_topk(obs)
