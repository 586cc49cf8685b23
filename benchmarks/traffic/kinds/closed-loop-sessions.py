"""Closed loop of agent SESSIONS, for a configuration that brings its own
weights and reference and serves under a prefix cache.

``clients`` callers; a caller runs one session at a time: a first turn (system
prompt, task, files), then further turns whose prompt is the WHOLE history so
far: the last prompt, the answer the engine served for it, and a tool result.
It sends a turn when the last returned and opens its next session when one
ends. Every turn after a session's first is a strict extension of the prompt
before it, so an engine with a prefix cache maps the history's blocks and
prefills the suffix only; first turns are cold. The number judged is output
tokens completed per second (``closed-loop-batch``'s ``metrics``).

Parameters, ``traffic/<name>.json``: ``clients``, ``sessions`` (the set, replayed
past its end), ``first_prompt_tokens`` / ``tool_tokens`` / ``output_tokens``
(Pareto, as ``draws.pareto_quantiles``), ``further_turns`` ``{min, max}``
(uniform), ``max_context`` (a session ends where its next prompt, as the engine
will lay it out, plus its output would pass it), adapters and sampling as the
other kinds have them, one adapter a session. Sizes and their order are the
``schedule_seed``'s, token ids and weights the run's ``--seed``'s (``draws.py``
says why).

The engine build, the reference check and the reduction are
``closed-loop-arch``'s and ``serving.py``'s, by import; this kind brings its own
``plan``, ``drive`` and ``warm_up``, a window that reads the engine's
``prefix_stats`` at both edges, and a check sample that holds turns of both
paths: the longest request of all, the longest cold turn and the longest turn
admitted through shared blocks, then a seeded draw of the rest.
"""

from __future__ import annotations

import itertools
import threading
import time
import types

import numpy as np

import draws
import spec as spec_mod

_arch = spec_mod.load_module("traffic", "kinds", "closed-loop-arch.py")
metrics = _arch.metrics
BUCKET = 64  # the engine lays a prompt, and a suffix, out in whole buckets (utils/decoding.py)


def _up(n: int) -> int:
    return -(-n // BUCKET) * BUCKET


def _uniform_set(n: int, lo: int, hi: int) -> np.ndarray:
    """n whole numbers spread evenly over lo..hi (a fixed set, as the quantiles are)."""
    return lo + (np.arange(n) * (hi - lo + 1)) // n


def plan(cell, seed: int, seconds: float, adapters: list, vocab: int) -> dict:
    t = cell.traffic
    n = int(t["sessions"])
    order = draws.rng_for(int(t["schedule_seed"]), 1)
    firsts = order.permutation(draws.pareto_quantiles(n, t["first_prompt_tokens"]))
    further = order.permutation(_uniform_set(n, int(t["further_turns"]["min"]),
                                             int(t["further_turns"]["max"])))
    n_turns = int(further.sum())
    tools = order.permutation(draws.pareto_quantiles(n_turns, t["tool_tokens"]))
    outs = order.permutation(draws.pareto_quantiles(n_turns + n, t["output_tokens"]))
    names = draws.zipf_counts(n, adapters, float(t.get("adapter_zipf_s", 0.0)),
                              float(t.get("base_share", 1.0)))
    names = [names[i] for i in order.permutation(n)]
    limit = int(t["max_context"])
    rng = draws.rng_for(seed, 1)
    sessions, at_tool, at_out = [], 0, 0
    for i in range(n):
        first_out = int(outs[at_out])
        at_out += 1
        turns, cursor = [], _up(int(firsts[i]))  # where the engine's row stands after a prompt
        last_out = first_out
        for _ in range(int(further[i])):
            tool, out = int(tools[at_tool]), int(outs[at_out])
            at_tool, at_out = at_tool + 1, at_out + 1
            # the suffix a turn prefills: the last answer and the tool result, in whole buckets
            nxt = cursor + _up(last_out + tool)
            if nxt + out > limit:
                continue  # this session is over; the draws go on, so the set stays fixed
            turns.append({"tool": rng.integers(10, vocab, size=tool).tolist(), "max_new_tokens": out})
            cursor, last_out = nxt, out
        sessions.append({"id": i, "adapter": names[i],
                         "first": rng.integers(10, vocab, size=int(firsts[i])).tolist(),
                         "max_new_tokens": first_out, "turns": turns,
                         "seed": int(rng.integers(0, 2**31 - 1))})
    return {"sessions": sessions, "clients": int(t["clients"]),
            "temperature": float(t.get("temperature", 0.0)), "top_p": float(t.get("top_p", 1.0))}


def _turn(plan, session, turn: int, prompt: list, max_new: int) -> dict:
    return {"id": (session["id"], turn), "session": session["id"], "turn": turn, "prompt": prompt,
            "max_new_tokens": max_new, "adapter": session["adapter"],
            "temperature": plan["temperature"], "top_p": plan["top_p"], "seed": session["seed"]}


def run_session(plan, session, submit, stop: float) -> None:
    """One session through ``submit``, a turn at a time, until it ends, a turn
    fails or ``stop`` (the benchmark's clock) has passed."""
    history, max_new = list(session["first"]), session["max_new_tokens"]
    for turn in range(len(session["turns"]) + 1):
        now = time.perf_counter()
        if now >= stop:
            return
        rec = submit(_turn(plan, session, turn, history, max_new), now)
        rec.req.done.wait()
        if rec.req.error is not None or turn == len(session["turns"]):
            return
        nxt = session["turns"][turn]
        history = history + list(rec.req.tokens) + nxt["tool"]
        max_new = nxt["max_new_tokens"]


def drive(plan, submit, lead_in_s, seconds, at_window_start, tick, records):
    sessions, n_clients = plan["sessions"], plan["clients"]
    t0 = time.perf_counter() + 0.05
    w0 = t0 + lead_in_s
    stop = w0 + seconds
    order = itertools.count()  # the next session to open, whichever client asks

    def client():
        while time.perf_counter() < stop:
            # past the end of the set the same sessions come round again
            run_session(plan, sessions[next(order) % len(sessions)], submit, stop)

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


def warm_up(engine, the_plan, vocab: int, seed: int) -> int:
    """One short session an adapter's worth of shapes: a cold prompt whose
    chunks are the longest chunk and the shortest, extensions whose suffixes
    are the two chunk lengths between, the same prompt again (an exact hit),
    each with outputs over one decode dispatch and across a block's edge, so
    that every program the window's admissions, chunks, token steps, growth and
    releases run has been compiled. No prompt is long: a program's shape does
    not depend on how many blocks a slot holds. Returns how many requests ran."""
    import serving

    rng = draws.rng_for(seed, 3)
    c, bs = engine.prefill_chunk, max(engine.block_size, 1)
    out = engine.chunk + bs + 1
    sizes = sorted(set(range(2 * BUCKET, c, BUCKET))) or [BUCKET]  # the chunk lengths between
    adapters = sorted({s["adapter"] for s in the_plan["sessions"]})
    session = {"id": -1, "adapter": adapters[-1], "seed": int(rng.integers(0, 2**31 - 1)),
               "first": rng.integers(10, vocab, size=c + BUCKET - 3).tolist(),
               "max_new_tokens": out,
               "turns": [{"tool": rng.integers(10, vocab, size=n - out - 5).tolist(),
                          "max_new_tokens": out} for n in sizes]}
    records, threads = [], []
    submit = lambda spec, due: serving.submit_and_watch(engine, spec, due, records, threads)  # noqa: E731
    run_session(the_plan, session, submit, float("inf"))
    again = submit(_turn(the_plan, session, 0, session["first"], out), time.perf_counter())
    again.req.done.wait()
    for th in threads:
        th.join()
    bad = [r.error for r in records if r.error]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")
    return len(records)


def _admit_mode(rec) -> str:
    modes = [d.get("mode", "") for _, e, d in rec.req.timeline if e == "admit"]
    return modes[-1] if modes else ""


def check_sample(records: list, want: int, seed: int) -> list:
    """``want`` finished greedy requests for the reference: the longest of all,
    the longest cold turn and the longest turn admitted through shared blocks
    (both paths are compared, whatever the draw), then a seeded draw of the rest."""
    length = lambda r: len(r.spec["prompt"]) + r.n_tokens  # noqa: E731
    greedy = [r for r in records if r.error is None and r.n_tokens > 0
              and r.spec["temperature"] <= 0.0]
    picked = []
    for group in (greedy, [r for r in greedy if not _admit_mode(r).startswith("cow")],
                  [r for r in greedy if _admit_mode(r).startswith("cow")]):
        rest = [r for r in group if r not in picked]
        if rest and len(picked) < want:
            picked.append(max(rest, key=length))
    rest = [r for r in greedy if r not in picked]
    order = draws.rng_for(seed, 4).permutation(len(rest))
    return picked + [rest[i] for i in order[: max(0, want - len(picked))]]


def check_served(cell, params, lora, names, records: list, seed: int, precision="f32"):
    """``closed-loop-arch``'s comparison over this kind's sample."""
    sample = check_sample(records, int(cell.workload["check"]["requests"]), seed)
    out = _arch.check_served(cell, params, lora, names, sample, seed, precision)
    out["paths"] = sorted({_admit_mode(r) for r in sample})
    return out


def prepare(ctx):
    from common import log

    t0 = time.perf_counter()
    engine, lora, names = _arch.build_engine(ctx.cell, ctx.seed)
    vocab = engine.cfg.vocab_size
    the_plan = plan(ctx.cell, ctx.seed, ctx.seconds, names, vocab)
    t1 = time.perf_counter()
    n_warm = warm_up(engine, the_plan, vocab, ctx.seed)
    log(f"[bench] engine built in {t1 - t0:.1f} s, {n_warm} warm-up requests in "
        f"{time.perf_counter() - t1:.1f} s; decode_path={engine.decode_path} "
        f"epilogue={engine.sampling_epilogue}")
    return engine, lora, names, the_plan


def _counters(engine) -> dict:
    return {"moe": dict(engine.moe_stats), "prefix": dict(getattr(engine, "prefix_stats", None) or {}),
            "preempt": dict(engine.preempt_stats)}


def _window(ctx, engine, the_plan):
    """``serving.window`` under this kind's generator, which reads the engine's
    expert, prefix-cache and preemption counters at both ends of the window."""
    import serving

    seen = {}

    def counted_drive(plan_, submit, lead, seconds, at_window_start, tick, records):
        def start(t):
            seen[0] = _counters(engine)
            at_window_start(t)

        drive(plan_, submit, lead, seconds, start, tick, records)
        seen[1] = _counters(engine)

    out = serving.window(ctx, engine, types.SimpleNamespace(drive=counted_drive), the_plan)
    delta = {key: {k: seen[1][key][k] - seen[0][key].get(k, 0) for k in seen[1][key]}
             for key in seen.get(1, {})}
    return out, delta


def run(ctx) -> dict:
    import serving
    from common import Observed, log, peak_memory_bytes

    cell = ctx.cell
    engine, lora, names, the_plan = prepare(ctx)
    (records, w0, w1, trace_end), delta = _window(ctx, engine, the_plan)
    compiles = ctx.compiles_in_window()
    mem = peak_memory_bytes()
    red = serving.reduce_records(records, w0, w1)
    in_window = [r for r in records if w0 <= r.due < w1]
    modes = [_admit_mode(r) for r in in_window]
    later = [m for r, m in zip(in_window, modes) if r.spec["turn"] > 0]
    paths = {"turns": len(in_window), "later_turns": len(later),
             "later_turns_extended": sum(m == "cow_extend" for m in later),
             "waited_for_blocks": sum(
                 d.get("waited_for") == "blocks" for r in in_window
                 for _, e, d in r.req.timeline if e == "admit")}
    obs = Observed(cell=cell, records=records, window=(w0, w1), engine_info={
        "decode_path": engine.decode_path, "epilogue": engine.sampling_epilogue,
        "slots": engine.slots, "chunk": engine.chunk, "block_size": engine.block_size,
        "sampling_stats": dict(engine.sampling_stats), "moe_stats": delta.get("moe", {}),
        "prefix_stats": delta.get("prefix", {}), "preempt_stats": delta.get("preempt", {}),
        "admit_paths": paths}, reduced=red)
    wave = [r for r in records if r.spec["turn"] == 0 and r.spec["session"] < the_plan["clients"]]
    if wave and all(r.first for r in wave):
        log(f"[bench] the first wave ({len(wave)} cold turns at once) had its first tokens "
            f"{max(r.first for r in wave) - min(r.sent for r in wave):.1f} s after its start; lead-in "
            f"{cell.traffic.get('lead_in_s')} s")
    log(f"[bench] in the window: prefix cache {delta.get('prefix')}; preemptions "
        f"{delta.get('preempt')}; admissions {paths}; experts {delta.get('moe')}")
    if trace_end is not None:
        obs.trace_window = (w0, trace_end)

    # free the engine's state before the reference runs: the peak stays the program's
    params = serving.release(engine)
    t0 = time.perf_counter()
    chk = check_served(cell, params, lora, names, [r for r in records if r.done is not None], ctx.seed)
    log(f"[bench] reference check took {time.perf_counter() - t0:.1f} s")
    limits = cell.workload["check"]["limits"]
    checks = [("compiles_in_window", compiles, 0, "max"),
              ("failed_requests", red["failed"], 0, "max"),
              ("served_tokens_compared", chk["served_tokens"], 1 if ctx.on_cpu else 100, "min"),
              # both paths served what the reference puts first, or the sample says which it lacked
              ("paths_compared", len(chk.get("paths", ())), 2, "min")]
    for key in ("gap_max", "gap_mean"):
        if key in limits:
            checks.append((key, chk[key] if chk[key] is not None else float("inf"),
                           limits[key], "max"))
    obs.check = chk
    return {"attempted": red["attempted"], "failed": red["failed"], "reduced": red,
            "checks": checks, "memory_peak_bytes": mem, "observed": obs,
            "kind_metrics": metrics(red, ctx.seconds)}


def readings(ctx, control: bool = True) -> dict:
    """For setting limits (``calibrate.py``): one short window at the cell's own
    load, then the numbers of the sound program and of the int8 control on the
    same sample of served requests."""
    import jax
    import serving

    engine, lora, names, the_plan = prepare(ctx)
    (records, w0, w1, _), _ = _window(ctx, engine, the_plan)
    params = serving.release(engine)
    done = [r for r in records if r.done is not None]
    out = {"sound": check_served(ctx.cell, params, lora, names, done, ctx.seed),
           "failed": serving.reduce_records(records, w0, w1)["failed"]}
    if control:
        out["control"] = check_served(ctx.cell, params, lora, names, done, ctx.seed,
                                      precision="int8")
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    return out
