"""Closed loop, for a configuration that brings its own weights and reference.

The load is ``closed-loop-batch``'s, taken from that file: ``clients`` callers,
each sending its next request when the last returns; the number judged is
output tokens completed per second. What differs is whose model it is:
``serving.py`` draws llama-family weights (``weights.py``) and checks against
``reference/decoder.py`` by name, so a configuration of another architecture
goes through this kind, which has ``run`` and ``readings`` of its own (the hook
``benchmarks/README.md`` gives) and reuses everything else of ``serving.py`` by
import: ``warm_up``, ``window``, ``reduce_records``, ``release``.

Worked example, how ``mimo-v2.5-l7-ep16`` was added without an edit to a file
that was there:

1. ``configs/<name>.json``: the published ``config.json`` keys on top,
   ``reduced`` and ``assumed``, the ``deployment`` the cut stands for,
   ``model_config`` (the program's ``ModelConfig`` fields, which
   ``spec.register_preset`` registers as ``preset:<name>``), and two names:
   ``weights_module`` (a file under ``benchmarks/`` with ``draw_params(mc, seed)``
   and ``draw_lora(mc, seed, count=, rank=, targets=, b_std=)`` in the program's
   tree layout) and ``reference_module`` (a file with ``sequence_logits(params,
   mc, tokens, rows, lora, lora_scale, valid_len=, precision=)``, importing
   nothing of the program, with its int8 control).
2. ``traffic/<mix>.json`` with ``"kind": "closed-loop-arch"``, ``workloads/<cell>.json``
   (engine settings, adapters, ``check.limits`` from ``calibrate.py`` readings).
3. ``metrics/<name>.py`` per new per-layer metric, ``BENCHMARK.json`` entries.

``correct`` is decided as ``serving.check_served`` decides it: over a seeded
sample of the greedy requests the run finished, with the longest in it, the
widest and the mean gap by which a served token's logit lies below the
reference's best (``gap_max``, ``gap_mean``); compilations in the window 0;
failed requests 0.
"""

from __future__ import annotations

import functools
import shutil
import tempfile
import time
import types

import numpy as np

import spec as spec_mod

_batch = spec_mod.load_module("traffic", "kinds", "closed-loop-batch.py")
plan, drive, metrics = _batch.plan, _batch.drive, _batch.metrics


@functools.lru_cache(maxsize=None)
def _module(path: str):
    return spec_mod.load_module(*path.split("/"))  # once: its jitted functions keep their compiles


def _modules(cell):
    """(weights module, reference module) that the configuration file names."""
    return _module(cell.config["weights_module"]), _module(cell.config["reference_module"])


def _one_adapter(lora, i):
    """Adapter ``i`` of the drawn stack, in the tree layout one adapter has."""
    import jax

    return jax.tree_util.tree_map(lambda a: a[i], lora)


def build_engine(cell, seed: int):
    """As ``serving.build_engine``, with the configuration's own draws: a
    BatchedEngine on ``preset:<config>``, given the seed's weights and the
    seed's adapters through its normal loading path."""
    import jax
    import serving
    from datatunerx_tpu.serving.batched_engine import BatchedEngine
    from datatunerx_tpu.training.checkpoint import CheckpointManager

    weights, _ = _modules(cell)
    spec_mod.register_preset(cell)
    mc = cell.model_fields
    ad = cell.workload.get("adapters") or {"count": 0}
    names = serving.adapter_names(int(ad["count"]))
    lora = None
    work = tempfile.mkdtemp(prefix="bench_adapters_")
    try:
        paths = {}
        if names:
            lora = weights.draw_lora(mc, seed, count=len(names), rank=int(ad["rank"]),
                                     targets=ad["targets"], b_std=0.05)
            host = jax.device_get(lora)
            for i, name in enumerate(names):
                mngr = CheckpointManager(f"{work}/{name}")
                mngr.maybe_save({"lora": {"layers": _one_adapter(host, i)}}, step=1, force=True)
                mngr.close()
                paths[name] = f"{work}/{name}"
        engine = BatchedEngine(f"preset:{cell.config_name}", adapters=paths or None,
                               **cell.workload["engine"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    # the engine drew weights of its own to get here; free them and serve the seed's
    old, engine.params = engine.params, None
    for leaf in jax.tree_util.tree_leaves(old):
        leaf.delete()
    del old
    engine.params = weights.draw_params(mc, seed)
    jax.block_until_ready(engine.params)
    return engine, lora, names


def prepare(ctx):
    import serving
    from common import log

    t0 = time.perf_counter()
    engine, lora, names = build_engine(ctx.cell, ctx.seed)
    vocab = engine.cfg.vocab_size
    the_plan = plan(ctx.cell, ctx.seed, ctx.seconds, names, vocab)
    t1 = time.perf_counter()
    n_warm = serving.warm_up(engine, the_plan["requests"], vocab, ctx.seed)
    log(f"[bench] engine built in {t1 - t0:.1f} s, {n_warm} warm-up requests in "
        f"{time.perf_counter() - t1:.1f} s; decode_path={engine.decode_path} "
        f"epilogue={engine.sampling_epilogue}")
    return engine, lora, names, the_plan


def check_served(cell, params, lora, names, records: list, seed: int, precision="f32"):
    """``serving.check_served`` with the configuration's own reference."""
    import draws
    import jax.numpy as jnp

    _, reference = _modules(cell)
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
    max_out = max(r.spec["max_new_tokens"] for r in greedy)
    n_rows = -(-max_out // 64) * 64  # one compiled shape for every request's rows
    for r in sample:
        tokens = list(r.spec["prompt"]) + list(r.req.tokens)
        n_prompt, n_out = len(r.spec["prompt"]), r.n_tokens
        rows = list(range(n_prompt - 1, n_prompt - 1 + n_out))
        rows += [rows[-1]] * (n_rows - n_out)
        padded = tokens + [0] * (-len(tokens) % 256)  # few compiled lengths; causal, so a tail of padding is inert
        ll = _one_adapter(lora, names.index(r.spec["adapter"])) if r.spec["adapter"] else None
        ref = reference.sequence_logits(params, mc, padded, rows, ll, scale, valid_len=len(tokens))
        best = jnp.max(ref, axis=-1)
        if precision == "f32":
            served = jnp.asarray(list(r.req.tokens) + [0] * (n_rows - n_out), jnp.int32)
        else:
            low = reference.sequence_logits(params, mc, padded, rows, ll, scale,
                                            valid_len=len(tokens), precision=precision)
            served = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - got, np.float64)[:n_out])
    gaps = np.concatenate(gaps)
    return {"served_tokens": int(gaps.size), "requests": len(sample),
            "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
            "flip_share": float((gaps > 0).mean())}


def _window(ctx, engine, the_plan):
    """``serving.window`` under this kind's generator, which also reads the
    engine's expert counters at both ends of the window and samples, about
    once a second, what share of the window layers' live pool lies behind
    every query's window."""
    import serving

    seen = {"shares": [], "next": 0.0}

    def sampled_drive(plan_, submit, lead, seconds, at_window_start, tick, records):
        def start(t):
            seen["moe0"] = dict(engine.moe_stats)
            at_window_start(t)

        def tock(now):
            tick(now)
            if now >= seen["next"]:
                seen["next"] = now + 1.0
                w = engine.kv_window_stats()
                if w and w["live_bytes"]:
                    seen["shares"].append(100.0 * w["behind_bytes"] / w["live_bytes"])

        drive(plan_, submit, lead, seconds, start, tock, records)
        seen["moe1"] = dict(engine.moe_stats)

    out = serving.window(ctx, engine, types.SimpleNamespace(drive=sampled_drive), the_plan)
    moe = {k: seen["moe1"][k] - seen["moe0"][k] for k in seen.get("moe1", {})}
    share = float(np.mean(seen["shares"])) if seen["shares"] else None
    return out, moe, share


def run(ctx) -> dict:
    import serving
    from common import Observed, log, peak_memory_bytes

    cell = ctx.cell
    engine, lora, names, the_plan = prepare(ctx)
    (records, w0, w1, trace_end), moe, behind = _window(ctx, engine, the_plan)
    compiles = ctx.compiles_in_window()
    mem = peak_memory_bytes()
    red = serving.reduce_records(records, w0, w1)
    obs = Observed(cell=cell, records=records, window=(w0, w1), engine_info={
        "decode_path": engine.decode_path, "epilogue": engine.sampling_epilogue,
        "slots": engine.slots, "chunk": engine.chunk, "block_size": engine.block_size,
        "sampling_stats": dict(engine.sampling_stats), "moe_stats": moe,
        "kv_behind_window_share": behind}, reduced=red)
    log(f"[bench] expert counters in the window: {moe}; behind-window share {behind}")
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
              ("served_tokens_compared", chk["served_tokens"], 1 if ctx.on_cpu else 100, "min")]
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
    (records, w0, w1, _), _, _ = _window(ctx, engine, the_plan)
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
