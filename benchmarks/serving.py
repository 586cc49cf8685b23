"""The part of a serving run that every kind of serving traffic shares: build
the engine in this process, give it the seed's weights and adapters, warm the
shapes this traffic uses, hand the engine to the kind's generator for the
window, then decide ``correct`` against the plain reference.

A kind (``traffic/kinds/<kind>.py``) supplies ``plan(cell, seed, seconds, ...)``
(the requests and when or by whom they are sent) and ``drive(engine, plan,
submit)`` (the generator); everything else is here.
"""

from __future__ import annotations

import shutil
import tempfile
import threading
import time

import jax
import numpy as np

import draws
import weights
from common import Observed, log, peak_memory_bytes, tracing

WINDOW_SPAN = "bench_window"
TRACE_SECONDS = 4.0


class Record:
    """One request as the benchmark saw it, on the benchmark's clock."""

    __slots__ = ("spec", "due", "sent", "first", "done", "n_tokens", "error", "req")

    def __init__(self, spec, due):
        self.spec, self.due = spec, due
        self.sent = self.first = self.done = None
        self.n_tokens, self.error, self.req = 0, None, None


def submit_and_watch(engine, spec: dict, due: float, records: list, threads: list) -> Record:
    """Send one request now; a watcher thread stamps its first token and its
    end on the benchmark's own clock."""
    rec = Record(spec, due)
    rec.sent = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_submit"):
        rec.req = engine.submit(
            spec["prompt"], max_new_tokens=spec["max_new_tokens"],
            temperature=spec["temperature"], top_p=spec["top_p"], seed=spec["seed"],
            adapter=spec["adapter"])
    records.append(rec)

    def watch():
        tok = rec.req.stream.get()
        if tok is not None:
            rec.first = time.perf_counter()
            rec.req.done.wait()
        rec.done = time.perf_counter()
        rec.n_tokens = len(rec.req.tokens)
        rec.error = rec.req.error

    th = threading.Thread(target=watch, daemon=True)
    th.start()
    threads.append(th)
    return rec


def adapter_names(n: int) -> list:
    return [f"ad{i}" for i in range(n)]


def build_engine(cell, seed: int):
    """BatchedEngine on ``preset:<config>``, with the seed's weights and the
    seed's adapters. Returns (engine, params, lora_draws, names)."""
    import jax
    import spec as spec_mod
    from datatunerx_tpu.serving.batched_engine import BatchedEngine
    from datatunerx_tpu.training.checkpoint import CheckpointManager

    spec_mod.register_preset(cell)
    mc = cell.model_fields
    ad = cell.workload.get("adapters") or {"count": 0}
    names = adapter_names(int(ad["count"]))
    lora = None
    work = tempfile.mkdtemp(prefix="bench_adapters_")
    try:
        paths = {}
        if names:
            # the adapters are the benchmark's draws, written where the engine
            # reads adapters from: its normal loading path, checkpoint by name
            lora = weights.draw_lora(mc, seed, count=len(names), rank=int(ad["rank"]),
                                     targets=ad["targets"], b_std=0.05)
            host = jax.device_get(lora)
            for i, name in enumerate(names):
                path = f"{work}/{name}"
                mngr = CheckpointManager(path)
                mngr.maybe_save(
                    {"lora": {"layers": {t: {"a": host[t]["a"][i], "b": host[t]["b"][i]}
                                         for t in host}}}, step=1, force=True)
                mngr.close()
                paths[name] = path
        engine = BatchedEngine(f"preset:{cell.config_name}", adapters=paths or None,
                               **cell.workload["engine"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    # the engine drew weights of its own (seed 0, zero biases) to get here;
    # free them and serve the seed's
    old, engine.params = engine.params, None
    for leaf in jax.tree_util.tree_leaves(old):
        leaf.delete()
    del old
    engine.params = weights.draw_params(mc, seed)
    jax.block_until_ready(engine.params)
    return engine, lora, names


def shapes_of(engine, spec: dict):
    """(prefill chunk lengths, KV blocks reserved) this request makes the engine
    use, by the engine's own prompt arithmetic."""
    from datatunerx_tpu.utils.decoding import prepare_prompt

    _, _, _, plen, _, max_new, _ = prepare_prompt(
        spec["prompt"], engine.tokenizer.eos_token_id, engine.max_seq_len, spec["max_new_tokens"])
    c = engine.prefill_chunk
    chunks = {c} if plen >= c else set()
    if plen % c:
        chunks.add(plen % c)
    blocks = -(-(plen + max_new) // engine.block_size) if engine.paged else 0
    return chunks, blocks, plen


def warm_up(engine, requests: list, vocab: int, seed: int) -> int:
    """Run one short request for every prefill-chunk length and every block-table
    size the traffic's requests make the engine use (it compiles a small program
    per size), in every sampling mode the traffic uses. The warm-up requests are
    made for their shapes, with as few output tokens as give the same sizes.
    Returns how many ran."""
    need_chunks, need_blocks = set(), set()
    for spec in requests:
        chunks, blocks, _ = shapes_of(engine, spec)
        need_chunks |= chunks
        need_blocks.add(blocks)
    longest = max(len(s["prompt"]) for s in requests)
    bs = max(engine.block_size, 1)
    shapes = []
    for c in sorted(need_chunks):
        shapes.append((c, 1))
    for n in sorted(need_blocks):
        # the longest bucketed prompt that leaves room for a token in n blocks
        plen = max(64, min((bs * (n - 1)) // 64 * 64, -(-longest // 64) * 64))
        shapes.append((plen, max(1, bs * (n - 1) - plen + 1)))
    rng = draws.rng_for(seed, 3)
    modes = sorted({s["temperature"] for s in requests})
    adapters = sorted({s["adapter"] for s in requests})

    def make(plen, max_new, i, temperature):
        return {"prompt": rng.integers(10, vocab, size=plen).tolist(), "max_new_tokens": max_new,
                "adapter": adapters[i % len(adapters)], "temperature": temperature,
                "top_p": requests[0]["top_p"], "seed": int(rng.integers(0, 2**31 - 1))}

    n = 0
    # each mode alone first (an all-greedy batch is a program of its own), then
    # the shapes, mixed as the traffic mixes them
    for temperature in modes:
        recs, threads = [], []
        submit_and_watch(engine, make(64, 9, 0, temperature), time.perf_counter(), recs, threads)
        for th in threads:
            th.join()
        n += 1
    recs, threads = [], []
    for i, (plen, max_new) in enumerate(shapes):
        submit_and_watch(engine, make(plen, max_new, i, modes[i % len(modes)]),
                         time.perf_counter(), recs, threads)
    for th in threads:
        th.join()
    got_chunks, got_blocks = set(), set()
    for r in recs:
        chunks, blocks, _ = shapes_of(engine, r.spec)
        got_chunks |= chunks
        got_blocks.add(blocks)
    if not (need_chunks <= got_chunks and need_blocks <= got_blocks):
        raise RuntimeError(f"warm-up missed shapes: chunks {sorted(need_chunks - got_chunks)} "
                           f"blocks {sorted(need_blocks - got_blocks)}")
    bad = [r.error for r in recs if r.error]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")
    return n + len(shapes)


def reduce_records(records: list, w0: float, w1: float) -> dict:
    """End-to-end numbers over ALL requests that were due in the window."""
    due = [r for r in records if w0 <= r.due < w1]
    ok = [r for r in due if r.error is None and r.first is not None]
    failed = len(due) - len(ok)
    ttft = [(r.first - r.due) * 1e3 for r in ok]
    if failed and ttft:
        ttft += [max(ttft)] * failed  # a failed request counts as the worst
    tpot = [(r.done - r.first) / (r.n_tokens - 1) * 1e3 for r in ok if r.n_tokens > 1]
    finished = [r for r in records if r.error is None and r.done is not None and w0 <= r.done < w1]
    return {
        "attempted": len(due), "failed": failed,
        "ttft_ms": ttft, "tpot_ms": tpot,
        "late_ms": [(r.sent - r.due) * 1e3 for r in due],
        "generator_late_ms_max": max(((r.sent - r.due) * 1e3 for r in due), default=0.0),
        "tokens_finished_in_window": sum(r.n_tokens for r in finished),
        "requests_finished_in_window": len(finished),
    }


def check_served(cell, engine_params, lora, names, records: list, seed: int, precision="f32"):
    """The widest and the mean gap by which a served greedy token's logit lies
    below the reference's best, over a seeded sample of finished greedy
    requests with the longest in it. One reference pass per request, over its
    prompt and the tokens served for it. With ``precision='int8'`` the control:
    at the same positions, the gap of the token the lower precision puts first."""
    import jax.numpy as jnp
    from reference import decoder

    mc = cell.model_fields
    ad = cell.workload.get("adapters") or {}
    scale = float(ad.get("alpha", 0.0)) / float(ad.get("rank", 1)) if names else 0.0
    greedy = [r for r in records if r.error is None and r.n_tokens > 0
              and r.spec["temperature"] <= 0.0]
    if not greedy:
        return {"served_tokens": 0, "gap_max": None, "gap_mean": None, "requests": 0}
    want = int(cell.workload["check"]["requests"])
    longest = max(greedy, key=lambda r: len(r.spec["prompt"]) + r.n_tokens)
    rest = [r for r in greedy if r is not longest]
    order = draws.rng_for(seed, 4).permutation(len(rest))
    sample = [longest] + [rest[i] for i in order[: max(0, want - 1)]]
    gaps = []
    max_out = max(s["max_new_tokens"] for s in (r.spec for r in greedy))
    n_rows = -(-max_out // 64) * 64  # one compiled shape for every request's rows
    for r in sample:
        tokens = list(r.spec["prompt"]) + list(r.req.tokens)
        n_prompt, n_out = len(r.spec["prompt"]), r.n_tokens
        rows = list(range(n_prompt - 1, n_prompt - 1 + n_out))
        rows += [rows[-1]] * (n_rows - n_out)
        pad = -len(tokens) % 128  # few compiled lengths; causal, so a tail of padding is inert
        padded = tokens + [0] * pad
        ll = None
        if r.spec["adapter"]:
            i = names.index(r.spec["adapter"])
            ll = {t: {"a": lora[t]["a"][i], "b": lora[t]["b"][i]} for t in lora}
        ref = decoder.sequence_logits(engine_params, mc, padded, rows, ll, scale, valid_len=len(tokens))
        best = jnp.max(ref, axis=-1)
        if precision == "f32":
            served = jnp.asarray(list(r.req.tokens) + [0] * (n_rows - n_out), jnp.int32)
        else:
            low = decoder.sequence_logits(engine_params, mc, padded, rows, ll, scale,
                                          valid_len=len(tokens), precision=precision)
            served = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - got, np.float64)[:n_out])
    gaps = np.concatenate(gaps)
    return {"served_tokens": int(gaps.size), "requests": len(sample),
            "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "flip_share": float((gaps > 0).mean())}


def prepare(ctx, kind):
    """Engine built, given the seed's weights and adapters, and warmed for the
    shapes of the kind's plan. Returns (engine, lora, names, plan)."""
    cell = ctx.cell
    t0 = time.perf_counter()
    engine, lora, names = build_engine(cell, ctx.seed)
    vocab = engine.cfg.vocab_size
    plan = kind.plan(cell, ctx.seed, ctx.seconds, names, vocab)
    t1 = time.perf_counter()
    n_warm = warm_up(engine, plan["requests"], vocab, ctx.seed)
    log(f"[bench] engine built in {t1 - t0:.1f} s, {n_warm} warm-up requests in "
        f"{time.perf_counter() - t1:.1f} s; decode_path={engine.decode_path} "
        f"epilogue={engine.sampling_epilogue}")
    return engine, lora, names, plan


def window(ctx, engine, kind, plan):
    """The lead-in and the measured window. Returns (records, w0, w1, trace_end)."""
    lead = float(ctx.cell.traffic.get("lead_in_s", 0.0))
    records, threads = [], []
    trace_s = min(ctx.seconds, TRACE_SECONDS) if ctx.trace else 0.0
    state = {}

    def at_window_start(t_start):
        # called by the generator when the lead-in is over
        state["w0"] = t_start
        ctx.mark_window_start()
        if ctx.trace:
            state["trace"] = tracing(ctx.trace_dir, WINDOW_SPAN)
            state["trace"].__enter__()
            state["trace_until"] = t_start + trace_s

    def tick(now):
        if "trace" in state and now >= state["trace_until"]:
            state.pop("trace").__exit__(None, None, None)
            state["trace_end"] = now

    submit = lambda spec, due: submit_and_watch(engine, spec, due, records, threads)  # noqa: E731
    kind.drive(plan, submit, lead, ctx.seconds, at_window_start, tick, records)
    if "trace" in state:
        state.pop("trace").__exit__(None, None, None)
        state["trace_end"] = time.perf_counter()
    for th in threads:  # requests still in flight run to their end
        th.join(timeout=120)
    return records, state["w0"], state["w0"] + ctx.seconds, state.get("trace_end")


def release(engine):
    """Stop the engine and free its KV pool; returns the weights it served."""
    params = engine.params
    engine.close()
    for leaf in jax.tree_util.tree_leaves(engine._cache):
        leaf.delete()
    engine.params = None
    return params


def run(ctx, kind) -> dict:
    """One serving run. ``kind`` is the traffic kind's module."""
    cell = ctx.cell
    engine, lora, names, plan = prepare(ctx, kind)
    records, w0, w1, trace_end = window(ctx, engine, kind, plan)
    compiles = ctx.compiles_in_window()
    mem = peak_memory_bytes()
    red = reduce_records(records, w0, w1)
    obs = Observed(cell=cell, records=records, window=(w0, w1), engine_info={
        "decode_path": engine.decode_path, "epilogue": engine.sampling_epilogue,
        "slots": engine.slots, "chunk": engine.chunk, "block_size": engine.block_size,
        "sampling_stats": dict(engine.sampling_stats)}, reduced=red)
    if trace_end is not None:
        obs.trace_window = (w0, trace_end)

    # free the engine's state before the reference runs: the peak stays the program's
    params = release(engine)
    t0 = time.perf_counter()
    chk = check_served(cell, params, lora, names, [r for r in records if r.done is not None], ctx.seed)
    log(f"[bench] reference check took {time.perf_counter() - t0:.1f} s")
    limits = cell.workload["check"]["limits"]
    checks = [("compiles_in_window", compiles, 0, "max"),
              ("failed_requests", red["failed"], 0, "max"),
              ("served_tokens_compared", chk["served_tokens"], 1 if ctx.on_cpu else 100, "min")]
    for key in ("gap_max", "gap_mean"):
        if key in limits:
            checks.append((key, chk[key] if chk[key] is not None else float("inf"),
                           limits[key], "max"))
    obs.check = chk
    return {"attempted": red["attempted"], "failed": red["failed"], "reduced": red,
            "checks": checks, "memory_peak_bytes": mem, "observed": obs,
            "kind_metrics": kind.metrics(red, ctx.seconds)}


def readings(ctx, kind, control: bool = True) -> dict:
    """For setting limits (``calibrate.py``): one short window at the cell's own
    load, then the numbers of the sound program and of the int8 control on the
    same sample of served requests."""
    engine, lora, names, plan = prepare(ctx, kind)
    records, w0, w1, _ = window(ctx, engine, kind, plan)
    params = release(engine)
    done = [r for r in records if r.done is not None]
    out = {"sound": check_served(ctx.cell, params, lora, names, done, ctx.seed),
           "failed": reduce_records(records, w0, w1)["failed"]}
    if control:
        out["control"] = check_served(ctx.cell, params, lora, names, done, ctx.seed,
                                      precision="int8")
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    return out
