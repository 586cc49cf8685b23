"""Open loop: requests are sent on a schedule drawn from the seed whether or
not earlier ones have finished, as independent users send them. Each is timed
from when it was DUE, so a stall of the generator or the engine shows as
latency. Parameters: ``traffic/<name>.json`` (rate, lengths, adapter skew,
sampling, the share of greedy requests that the output check needs)."""

from __future__ import annotations

import math
import time

import draws


def plan(cell, seed: int, seconds: float, adapters: list, vocab: int) -> dict:
    t = cell.traffic
    span = float(t.get("lead_in_s", 0.0)) + seconds
    n = int(math.ceil(float(t["rate_rps"]) * span))
    return {"requests": draws.request_set(n, t, adapters, vocab, seed),
            "due": draws.arrival_times(n, float(t["rate_rps"]), int(t["schedule_seed"]))}


def drive(plan, submit, lead_in_s, seconds, at_window_start, tick, records):
    t0 = time.perf_counter() + 0.05
    w0 = t0 + lead_in_s
    started = False
    for spec, rel in zip(plan["requests"], plan["due"]):
        due = t0 + float(rel)
        if due >= w0 + seconds:
            break
        if not started and due >= w0:
            _sleep_until(w0)
            at_window_start(w0)
            started = True
        _sleep_until(due)
        tick(time.perf_counter())
        submit(spec, due)
    if not started:
        _sleep_until(w0)
        at_window_start(w0)
    while time.perf_counter() < w0 + seconds:
        tick(time.perf_counter())
        time.sleep(min(0.05, max(0.0, w0 + seconds - time.perf_counter())))
    tick(time.perf_counter())


def _sleep_until(t):
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(d)


def metrics(red: dict, seconds: float) -> dict:
    import numpy as np

    out = {}
    if red["ttft_ms"]:
        out["ttft_p95_ms"] = float(np.percentile(red["ttft_ms"], 95))
        out["ttft_p50_ms"] = float(np.percentile(red["ttft_ms"], 50))
    if red["tpot_ms"]:
        out["tpot_p95_ms"] = float(np.percentile(red["tpot_ms"], 95))
        out["tpot_p50_ms"] = float(np.percentile(red["tpot_ms"], 50))
    out["serve_tok_s"] = red["tokens_finished_in_window"] / seconds
    return out
