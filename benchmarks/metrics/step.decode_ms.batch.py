"""Model step, serve: as ``step.decode_ms``, in the closed-loop cells (it moves
``serve_tok_s`` there, not a tail)."""
import readers


def read(obs):
    return readers.decode_step_ms(obs)
