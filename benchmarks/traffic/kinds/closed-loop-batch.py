"""Closed loop: ``clients`` callers, each sending its next request when the
last returns, as a batch job that waits for replies does. A slow system gets
less load, so the number judged is output tokens completed per second.
Parameters: ``traffic/<name>.json`` (clients, lengths, adapters, sampling)."""

from __future__ import annotations

import threading
import time

import draws


def plan(cell, seed: int, seconds: float, adapters: list, vocab: int) -> dict:
    t = cell.traffic
    n = int(t["requests"])
    return {"requests": draws.request_set(n, t, adapters, vocab, seed),
            "clients": int(t["clients"])}


def drive(plan, submit, lead_in_s, seconds, at_window_start, tick, records):
    reqs, n_clients = plan["requests"], plan["clients"]
    t0 = time.perf_counter() + 0.05
    w0 = t0 + lead_in_s
    stop = w0 + seconds
    nxt = {"i": 0}
    mu = threading.Lock()

    def client():
        while True:
            with mu:
                i = nxt["i"]
                nxt["i"] += 1
            now = time.perf_counter()
            if now >= stop:
                return
            # past the end of the set the same requests come round again
            rec = submit(reqs[i % len(reqs)], now)
            rec.req.done.wait()

    while time.perf_counter() < t0:
        time.sleep(0.001)
    clients = [threading.Thread(target=client, daemon=True) for _ in range(n_clients)]
    for th in clients:
        th.start()
    while time.perf_counter() < w0:
        time.sleep(min(0.01, max(0.0, w0 - time.perf_counter())))
    at_window_start(w0)
    while time.perf_counter() < stop:
        tick(time.perf_counter())
        time.sleep(min(0.05, max(0.0, stop - time.perf_counter())))
    tick(time.perf_counter())
    for th in clients:
        th.join(timeout=120)


def metrics(red: dict, seconds: float) -> dict:
    import numpy as np

    out = {"serve_tok_s": red["tokens_finished_in_window"] / seconds}
    if red["ttft_ms"]:
        out["ttft_p50_ms"] = float(np.percentile(red["ttft_ms"], 50))
    if red["tpot_ms"]:
        out["tpot_p50_ms"] = float(np.percentile(red["tpot_ms"], 50))
    return out
