"""Engine scheduler: |offset of the passes' ``t_perf`` to the trace's clock - the offset
``readers._to_trace_clock`` assumes|. The closed-loop cells' reading of it."""
import cause_readers


def read(obs):
    return cause_readers.clock_skew_ms(obs)
