"""Arithmetic that several per-layer metric readers share. A reader
(``metrics/<name>.py``) is a few lines over these; it returns ``None`` when
the run holds nothing for it to read, and the harness then leaves the metric
out of the line."""

from __future__ import annotations

import numpy as np

import flops
import trace_reduce

DECODE_PROGRAM = "_decode_impl"
PREFILL_PROGRAM = "_prefill_chunk_impl"
DECODE_SPAN = "dtx_engine_decode"


def _to_trace_clock(obs, t_perf: float) -> float:
    """Benchmark clock -> trace clock: both tick alike, the window span anchors them."""
    return obs.trace_clock[0] + (t_perf - obs.trace_window[0])


def idle_share(obs):
    if obs.flat is None:
        return None
    lo, hi = obs.trace_clock
    b = trace_reduce.busy_idle(obs.flat, lo, hi)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def decode_step_ms(obs):
    """Device time of the decode program per token step (it scans ``chunk`` steps)."""
    if obs.flat is None:
        return None
    lo, hi = obs.trace_clock
    times = trace_reduce.program_times(obs.flat, DECODE_PROGRAM, lo, hi)
    if not times:
        return None
    return float(np.median(times)) * 1e3 / obs.engine_info["chunk"]


def _marks(rec, event):
    return [(t, d) for t, e, d in rec.req.timeline if e == event]


def live_requests(obs):
    """(activate, finish, record) on the benchmark's clock for every request that decoded."""
    out = []
    for r in obs.records:
        act, fin = _marks(r, "activate"), _marks(r, "finish")
        if act and fin:
            out.append((act[0][0], fin[-1][0], r))
    return out


def decode_dispatches(obs):
    """Start times (trace clock) of the engine's decode spans inside the traced window."""
    lo, hi = obs.trace_clock
    return sorted(s for n, s, d in obs.flat["host"] if n == DECODE_SPAN and lo <= s and s + d <= hi)


def rows_at(obs, live, t_trace):
    """Requests decoding at trace time t, each with its context length then."""
    rows = []
    for a, f, r in live:
        ta, tf = _to_trace_clock(obs, a), _to_trace_clock(obs, f)
        if ta <= t_trace < tf:
            frac = (t_trace - ta) / max(tf - ta, 1e-9)
            rows.append((r, len(r.spec["prompt"]) + frac * r.n_tokens))
    return rows


def decode_occupancy(obs):
    if obs.flat is None:
        return None
    live, starts = live_requests(obs), decode_dispatches(obs)
    if not starts:
        return None
    occ = [len(rows_at(obs, live, t)) for t in starts]
    return 100.0 * float(np.mean(occ)) / obs.engine_info["slots"]


def queue_wait_p95_ms(obs):
    w0, w1 = obs.window
    waits = []
    for r in obs.records:
        admit = _marks(r, "admit")
        if admit and w0 <= r.due < w1:
            waits.append((admit[0][0] - r.req.t_submit) * 1e3)
    return float(np.percentile(waits, 95)) if waits else None


def prefill_chunk_ms(obs, chunk_tokens: int = 256):
    """Device time of the prefill-chunk programs per ``chunk_tokens`` prompt
    tokens (padded to the bucket, as the engine runs them). The chunk programs
    of 64 to 256 tokens share one name in the trace, so the time is taken over
    all of them and scaled by the tokens the engine marked in the same span."""
    if obs.flat is None:
        return None
    lo, hi = obs.trace_clock
    times = trace_reduce.program_times(obs.flat, PREFILL_PROGRAM, lo, hi)
    tokens = 0
    for r in obs.records:
        for t, d in _marks(r, "prefill"):
            if lo <= _to_trace_clock(obs, t) <= hi:
                tokens += int(d.get("tokens", 0))
    if not times or not tokens:
        return None
    return sum(times) * 1e3 / tokens * chunk_tokens


def kernel_roofline(obs, match, work_of_dispatch):
    """Share of its roofline that a kernel reached over the traced window: the
    least seconds the chip could take for the work of every decode dispatch
    (``work_of_dispatch(rows)`` gives one token step's operations and bytes),
    over the kernel's measured device seconds."""
    if obs.flat is None:
        return None
    lo, hi = obs.trace_clock
    measured = sum(trace_reduce.op_times(obs.flat, match, lo, hi))
    if measured <= 0:
        return None
    live = live_requests(obs)
    least = 0.0
    for t in decode_dispatches(obs):
        work = work_of_dispatch(rows_at(obs, live, t))
        least += obs.engine_info["chunk"] * flops.roofline_seconds(work, obs.peaks)["seconds"]
    return 100.0 * least / measured
