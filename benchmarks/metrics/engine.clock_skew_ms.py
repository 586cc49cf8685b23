"""Engine scheduler: how far the anchor of ``readers._to_trace_clock`` (the benchmark's ``w0`` taken
for the start of the ``bench_window`` span) is from the offset the passes themselves give: the
median over ``dtx_engine_tick`` spans of (start on the trace's clock - the span's ``t_perf``).
The open-loop cell's reading of it."""
import cause_readers


def read(obs):
    return cause_readers.clock_skew_ms(obs)
