"""What the device's idle time and a request's wait were for (ISSUE 37).

The scheduler's spans of PR 24 say which phase of a pass the host was in while
the device had nothing to run. Since PR 37 the program says more, all of it in
the profiler's own trace:

* inside ``dtx_engine_wait``: ``dtx_engine_wait_empty`` (nothing has been asked
  of the engine) or ``dtx_engine_wait_blocked`` with ``reason=``;
* inside ``dtx_engine_emit``: ``dtx_engine_emit_push`` (``tokens=``) around the
  push loop, and per finished request ``dtx_engine_release`` (``blocks=``) and
  ``dtx_engine_complete``;
* ``dtx_engine_tick`` carries ``tick=`` (the pass's number, which every mark of
  ``Request.timeline`` carries too) and ``t_perf=`` (``time.perf_counter()`` at
  its start), which ties the timelines' clock to the trace's without an anchor
  of the benchmark's own;
* a request's ``admit`` mark carries ``waited_ticks`` and ``waited_for``.

Idle seconds are the device's idle intervals (``trace_reduce.gaps``) cut along
the scheduler's spans: each part goes to the innermost span that was open on
the scheduler's thread at that time. ``tick_readers`` gives an interval WHOLE
to the span over its middle, which serves spans as long as the intervals (a
leaf of a pass) and fails the new names: one interval runs from the end of a
decode program over the whole of emission, the next pass's admissions and its
dispatch, some 30 ms in cell 4, and its middle falls in one ``release`` of one
to three milliseconds (my chip runs, PR 37: under that rule ``release`` read
23.08 ms a dispatch, the whole of ``engine.gap_emit_ms.batch``, ``emit_push``
and ``complete`` 0.0). By overlap the parts of one interval add up to it, and
"idle under ``decode_sync``" is what its name says: from the end of the
program to the host's waking. A reader returns ``None`` on a run without a
trace and on a program that has none of the new names.
"""

from __future__ import annotations

import numpy as np

import readers
import span_stats
import tick_readers
import trace_reduce
from common import log

WAIT_EMPTY = "dtx_engine_wait_empty"
WAIT_BLOCKED = "dtx_engine_wait_blocked"
EMIT_PUSH = "dtx_engine_emit_push"
RELEASE = "dtx_engine_release"
COMPLETE = "dtx_engine_complete"
SYNC = "dtx_engine_decode_sync"
# the names PR 37 added inside the leaves of a pass
CAUSE_SPANS = (WAIT_EMPTY, WAIT_BLOCKED, EMIT_PUSH, RELEASE, COMPLETE)
ALL_SPANS = frozenset(tick_readers.LEAF_SPANS + CAUSE_SPANS + (tick_readers.TICK,))


def holds(obs, names=CAUSE_SPANS) -> bool:
    """The run has a trace and the trace at least one span of ``names``: by
    default, the program that was traced opens the new spans."""
    if getattr(obs, "flat", None) is None:
        return False
    seen = getattr(obs, "_host_names", None)
    if seen is None:
        seen = obs._host_names = {n for n, _, _ in obs.flat["host"]}
    return not seen.isdisjoint(names)


def innermost(spans) -> list:
    """[(t0, t1, name)] in order of time: which of ``spans`` (``(name, start,
    end)``, properly nested, one thread's) was the innermost open one."""
    out, stack, t = [], [], 0.0

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][2] <= limit:
            name, _, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, start, end in sorted(spans, key=lambda sp: (sp[1], sp[1] - sp[2])):
        close_until(start)
        if stack and start > t:
            out.append((t, start, stack[-1][0]))
        stack.append((name, start, end))
        t = max(t, start)
    close_until(float("inf"))
    return out


def idle_by_cause(obs) -> dict:
    """{span name: device-idle seconds of the traced window while that span was
    the scheduler's innermost open one}, over the leaves of a pass, the new
    names inside them and the pass itself; ``"(no host span)"`` between passes."""
    cached = getattr(obs, "_idle_by_cause", None)
    if cached is not None:
        return cached
    lo, hi = obs.trace_clock
    segments = innermost([(n, s, s + d) for n, s, d in obs.flat["host"] if n in ALL_SPANS])
    idle = trace_reduce.gaps(trace_reduce._first_device(obs.flat)["ops"], lo, hi)
    cached, i = {}, 0
    for g0, g1 in idle:
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        j, covered = i, 0.0
        while j < len(segments) and segments[j][0] < g1:
            part = min(g1, segments[j][1]) - max(g0, segments[j][0])
            if part > 0:
                cached[segments[j][2]] = cached.get(segments[j][2], 0.0) + part
                covered += part
            j += 1
        if g1 - g0 > covered:
            cached["(no host span)"] = cached.get("(no host span)", 0.0) + (g1 - g0) - covered
    obs._idle_by_cause = cached
    return cached


def gap_ms(obs, names, requires=CAUSE_SPANS):
    """Idle milliseconds under the spans ``names`` per decode dispatch, where the
    trace holds a span of ``requires``; 0.0 where the program has the new names
    and the window none of ``names`` (a cell that never starves has no
    ``wait_empty``)."""
    if not holds(obs, requires):
        return None
    dispatches = len(readers.decode_dispatches(obs))
    if not dispatches:
        return None
    idle = idle_by_cause(obs)
    return sum(idle.get(n, 0.0) for n in names) * 1e3 / dispatches


def emit_push_ms(obs):
    """``gap_ms`` under ``dtx_engine_emit_push``; the log line gives the whole
    table, so that one traced run says what emission and the sleeps were made of."""
    value = gap_ms(obs, (EMIT_PUSH,))
    if value is not None:
        n = len(readers.decode_dispatches(obs))
        table = {k[len("dtx_engine_"):] if k.startswith("dtx_engine_") else k: round(v * 1e3 / n, 3)
                 for k, v in sorted(idle_by_cause(obs).items(), key=lambda kv: -kv[1])}
        log(f"[metric] idle ms a decode dispatch by the scheduler's innermost span "
            f"({n} dispatches): {table}")
    return value


def gap_sync_ms(obs):
    """Idle under ``dtx_engine_decode_sync``: from the end of the decode program
    to the host's waking with its tokens. The span is PR 24's, so a program from
    before PR 37 is read too."""
    return gap_ms(obs, (SYNC,), requires=(SYNC,))


def idle_starved_share(obs):
    """Share of the window's idle seconds spent under ``dtx_engine_wait_empty``:
    the chip idle because nothing had been asked of the engine."""
    if not holds(obs):
        return None
    idle = idle_by_cause(obs)
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * idle.get(WAIT_EMPTY, 0.0) / total


def finishes_per_dispatch(obs):
    """``dtx_engine_complete`` spans over ``dtx_engine_decode`` spans inside the
    traced window: requests that ended per decode chunk."""
    if not holds(obs):
        return None
    dispatches = len(readers.decode_dispatches(obs))
    if not dispatches:
        return None
    lo, hi = obs.trace_clock
    done = sum(1 for n, s, d in obs.flat["host"] if n == COMPLETE and lo <= s and s + d <= hi)
    return done / dispatches


def ticks(obs) -> list:
    """[(start on the trace's clock, t_perf, tick number)] of the passes whose
    span carries both keywords."""
    out = []
    for name, start, _, stats in span_stats.spans(obs):
        if name == tick_readers.TICK and "t_perf" in stats and "tick" in stats:
            out.append((start, float(stats["t_perf"]), int(stats["tick"])))
    return out


def perf_to_trace_offset(obs):
    """Seconds to add to a ``time.perf_counter()`` stamp of the program to get
    the trace's clock: the median over the passes of (span start - ``t_perf``).
    The stamp is read just before the span opens, so each difference is the
    offset plus some microseconds."""
    stamped = ticks(obs)
    if not stamped:
        return None
    return float(np.median([start - t_perf for start, t_perf, _ in stamped]))


def clock_skew_ms(obs):
    """How far ``readers._to_trace_clock`` is off: it takes the benchmark's
    ``w0`` to be the start of the ``bench_window`` span, and the profiler starts
    between the two stamps. |the passes' offset - the offset it assumes|."""
    offset = perf_to_trace_offset(obs)
    if offset is None or getattr(obs, "trace_window", None) is None:
        return None
    assumed = obs.trace_clock[0] - obs.trace_window[0]
    return abs(offset - assumed) * 1e3


def admitted_in_window(obs) -> list:
    """The ``admit`` marks (detail dicts) stamped in a pass whose span lies in
    the traced window. Marks and passes meet by the pass's number: no clock."""
    lo, hi = obs.trace_clock
    inside = {tick for start, _, tick in ticks(obs) if lo <= start <= hi}
    if not inside:
        return []
    return [d for r in obs.records if r.req is not None
            for _, e, d in r.req.timeline
            if e == "admit" and d.get("tick") in inside and "waited_for" in d]


def admit_waited_share(obs):
    """Requests admitted in the traced window that some pass of the scheduler
    had seen and left waiting (``waited_for`` other than ``tick``), over all
    admitted there; the count by cause goes to the log."""
    if getattr(obs, "flat", None) is None:
        return None
    admits = admitted_in_window(obs)
    if not admits:
        return None
    by_cause = {}
    for d in admits:
        by_cause[d["waited_for"]] = by_cause.get(d["waited_for"], 0) + 1
    waited = [d["waited_ticks"] for d in admits if d["waited_for"] != "tick"]
    log(f"[metric] engine.admit_waited_share: {len(admits)} admitted in the traced window, "
        f"by cause {dict(sorted(by_cause.items()))}; passes waited (not `tick`): "
        f"median {float(np.median(waited)) if waited else 0.0:.0f}, "
        f"most {max(waited, default=0)}")
    return 100.0 * len(waited) / len(admits)
