"""Prefix cache: prompt tokens that admissions mapped from shared blocks over prompt tokens admitted in the window
(the engine's counters ``prefix_stats``, read at the window's edges): what share of the history a turn did not prefill."""
import kimi_readers


def read(obs):
    return kimi_readers.shared_token_share(obs)
