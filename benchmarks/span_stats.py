"""The keywords of the program's host spans.

A ``jax.profiler.TraceAnnotation(name, key=value)`` puts its keywords into the
event's stats and leaves the name bare (PERF.md §3). ``trace_reduce.flatten``
keeps ``(name, start, duration)`` and drops the stats, so the readers that need
a keyword (the tick's number and host clock, a wait's reason) read the trace
file a second time here, through the same ``ProfileData``, and keep only the
scheduler's spans.
"""

from __future__ import annotations

import scope_readers
import trace_reduce

PREFIX = "dtx_engine_"


def load(path: str) -> list:
    """[(name, start_s, dur_s, {keyword: value})] of the host plane's
    ``dtx_engine_*`` events, in the order of their starts."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(scope_readers._load_bytes(path))
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def spans(obs) -> list:
    """The run's scheduler spans with their keywords; ``[]`` where there is no
    trace file to read (an ``Observed`` without a trace, a file already gone)."""
    cached = getattr(obs, "_span_stats", None)
    if cached is None:
        cached = []
        if getattr(obs, "flat", None) is not None:
            try:
                cached = load(scope_readers.xplane_path(obs))
            except (OSError, ValueError):
                cached = []
        obs._span_stats = cached
    return cached
