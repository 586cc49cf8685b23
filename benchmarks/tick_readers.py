"""The device's idle time, split by what the engine's scheduler was doing.

The scheduler (``serving/batched_engine.py`` ``_scheduler``) wraps every pass
of its loop in a ``dtx_engine_tick`` span and every phase of a pass in a span
of its own, all ``jax.profiler.TraceAnnotation``: they land in the profiler's
trace on the device's clock. ``trace_reduce.idle_gaps_by_host_span`` gives each
idle interval of the device to the innermost of these spans that covers its
middle; the readers here sum that by phase and put it over the number of
decode dispatches in the window, so a number reads as milliseconds of an idle
chip per scheduler tick that decoded.

A reader returns ``None`` where the trace holds none of the spans it sums (a
program from before the spans existed, a training cell).
"""

from __future__ import annotations

import readers
import trace_reduce

TICK = "dtx_engine_tick"
# the leaves of a tick: a span around one thing the scheduler does
LEAF_SPANS = (
    "dtx_engine_migrate", "dtx_engine_resume", "dtx_engine_admit",
    "dtx_engine_adapter_acquire", "dtx_engine_prefill_chunk", "dtx_engine_activate",
    "dtx_engine_grow", "dtx_engine_decode", "dtx_engine_decode_sync",
    "dtx_engine_emit", "dtx_engine_wait", "dtx_engine_spec_tree", "dtx_engine_spec_step",
)
EMIT = ("dtx_engine_emit",)
ADMIT = ("dtx_engine_admit", "dtx_engine_adapter_acquire")
DISPATCH = ("dtx_engine_decode", "dtx_engine_prefill_chunk")


def idle_by_span(obs) -> dict:
    """{span name: device-idle seconds of the traced window}, the tick itself
    standing for what no leaf covers inside a pass."""
    cached = getattr(obs, "_idle_by_span", None)
    if cached is None:
        lo, hi = obs.trace_clock
        cached = dict(trace_reduce.idle_gaps_by_host_span(
            obs.flat, lo, hi, LEAF_SPANS + (TICK,), k=len(LEAF_SPANS) + 2))
        obs._idle_by_span = cached
    return cached


def _spans_in_window(obs, names) -> int:
    lo, hi = obs.trace_clock
    return sum(1 for n, s, d in obs.flat["host"] if n in names and s + d >= lo and s <= hi)


def gap_ms(obs, names):
    """Idle milliseconds under the spans ``names`` per decode dispatch."""
    if obs.flat is None or not _spans_in_window(obs, names):
        return None
    ticks = len(readers.decode_dispatches(obs))
    if not ticks:
        return None
    idle = idle_by_span(obs)
    return sum(idle.get(n, 0.0) for n in names) * 1e3 / ticks


def gap_unnamed_share(obs):
    """Share of the window's idle seconds that no leaf span covers: between
    passes, in a pass outside every phase, or with no scheduler span at all."""
    if obs.flat is None or not readers.decode_dispatches(obs):
        return None
    idle = idle_by_span(obs)
    total = sum(idle.values())
    if total <= 0:
        return None
    named = sum(t for n, t in idle.items() if n in LEAF_SPANS)
    return 100.0 * (total - named) / total
