"""Headline benchmark: LoRA SFT tokens/sec/chip.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Orchestration:
- Pre-flight probes the default device in a subprocess and reports each
  phase as it completes, so a failure names the phase it died in.
- The bare command measures on the chip or FAILS: with no TPU it exits
  non-zero and prints no line. ``DTX_BENCH_FORCE_CPU=1`` asks for the CPU by
  name — a correctness-and-counts smoke at the ``debug`` preset whose line
  is marked ``"cpu_fallback": true`` with ``"vs_baseline": null`` so it can
  never read as a device result. (CI runs every mode this way.)
- On TPU the headline is Llama-2-7B QLoRA tokens/sec/chip
  (scripts/bench_7b.py, the BASELINE.json metric) with the tinyllama-1.1b
  line embedded as ``"secondary"``.
- Each measurement runs in its own subprocess: the parent never touches
  JAX, so one process at a time holds the chip.

``vs_baseline``: the reference publishes no numbers, so the denominator is
this project's own earliest recorded measurement; neither constant below
has a driver record behind it (ROADMAP S1 replaces this file's modes with
the ledger-backed benchmark).
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

DEADLINE_S = float(os.environ.get("DTX_BENCH_TIMEOUT_S", "480"))
PREFLIGHT_TIMEOUT_S = float(os.environ.get("DTX_BENCH_PREFLIGHT_S", "60"))
PREFLIGHT_TRIES = int(os.environ.get("DTX_BENCH_PREFLIGHT_TRIES", "4"))
PREFLIGHT_SLEEP_S = float(os.environ.get("DTX_BENCH_PREFLIGHT_SLEEP_S", "15"))

# Earlier rounds' own tokens/sec/chip figures (no driver record — see the
# module docstring).
ROUND1_TINYLLAMA_TOKS = 12996.0  # round 1, xla attention, B8xT1024
ROUND2_7B_TOKS = 709.0           # round 2, nf4 XLA dequant path, B4xT1024


# --------------------------------------------------------------- child mode

def _child_backend() -> bool:
    """Backend policy for a measurement child (utils/runtime.py): the CPU
    only when ``DTX_BENCH_FORCE_CPU`` asked for it by name, otherwise a CPU
    backend is an error — a child that lost the chip must not print a
    ``debug``-preset line. Returns True on a TPU."""
    import jax

    if os.environ.get("DTX_BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    from datatunerx_tpu.utils import runtime

    runtime.configure_compile_cache()
    return runtime.require_backend()["platform"] == "tpu"


def child_tinyllama():
    """Measure tinyllama-1.1b LoRA SFT tokens/sec on the default backend and
    print one JSON line. Run in a subprocess by the orchestrator."""
    import jax

    on_tpu = _child_backend()
    import jax.numpy as jnp

    from datatunerx_tpu.models import get_config, init_params
    from datatunerx_tpu.training import TrainConfig, Trainer
    from datatunerx_tpu.training.loss import IGNORE_INDEX

    if on_tpu:
        model, B, T, steps = "tinyllama-1.1b", 8, 1024, 20
        B = int(os.environ.get("DTX_BENCH_BATCH", B))
    else:  # CPU smoke so the artifact always carries a line
        model, B, T, steps = "debug", 8, 128, 5

    # the Pallas flash kernel is the TPU default (chip_smoke.py proves it
    # against its oracle on the chip). CPU smoke keeps xla (flash off-TPU
    # would dispatch interpret mode: slow, no signal).
    attention = os.environ.get("DTX_BENCH_ATTENTION",
                               "flash" if on_tpu else "xla")
    remat = os.environ.get("DTX_BENCH_REMAT", "dots")
    cfg = get_config(model, remat=remat, attention_impl=attention)
    tr = Trainer(
        cfg,
        TrainConfig(
            finetuning_type="lora", lora_rank=8, lora_alpha=32.0,
            lora_dropout=0.05, lora_targets=("q_proj", "v_proj"),
            learning_rate=2e-4, scheduler="cosine", optimizer="adamw",
            total_steps=1000, compute_dtype=jnp.bfloat16,
        ),
    )
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    state = tr.init_state(params, jax.random.PRNGKey(1))

    rng = jax.random.PRNGKey(2)
    toks = jax.random.randint(rng, (B, T), 0, cfg.vocab_size, jnp.int32)
    labels = jnp.where(
        jnp.arange(T)[None, :] < T // 8, IGNORE_INDEX, toks
    )  # prompt-masked SFT batch shape
    batch = {"input_ids": toks, "labels": labels}

    # warmup / compile
    state, m = tr.train_step(state, batch)
    jax.block_until_ready(m["loss"])

    # DTX_BENCH_PIPELINE=1: feed the steps through the pipelined input path
    # (data/prefetch.py — host batch build in a background thread + batch N+1
    # placed while step N runs), the same machinery tuning/train.py uses. The
    # default path keeps the static-batch measurement for round-over-round
    # continuity; the pipelined line carries the pipeline wait stats so input
    # stalls are visible next to the throughput number.
    pipelined = bool(os.environ.get("DTX_BENCH_PIPELINE"))
    pipe_stats = None
    if pipelined:
        import numpy as np

        from datatunerx_tpu.data.prefetch import PipelineStats, prefetch_batches
        from datatunerx_tpu.parallel.sharding import place_batch
        from datatunerx_tpu.training.loss import IGNORE_INDEX as _II

        host_rng = np.random.default_rng(3)

        def host_batches():
            for _ in range(steps):
                t = host_rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
                lab = np.where(np.arange(T)[None, :] < T // 8, _II, t)
                yield {"input_ids": t, "labels": lab.astype(np.int32)}

        pipe_stats = PipelineStats()
        batches, host_pf = prefetch_batches(
            host_batches,
            place_fn=lambda b: place_batch(b, tr.mesh),
            depth=int(os.environ.get("DTX_BENCH_PREFETCH_DEPTH", "2")),
            stats=pipe_stats,
        )
        t0 = time.perf_counter()
        try:
            for b in batches:
                state, m = tr.train_step(state, b)
        finally:
            host_pf.close()
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = tr.train_step(state, batch)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0

    toks_per_sec = B * T * steps / dt
    vs = toks_per_sec / ROUND1_TINYLLAMA_TOKS if on_tpu else None
    tag = (f",{attention}" if attention != "xla" else "") + (
        f",remat={remat}" if remat != "dots" else "")
    tag += f",B{B}" if B != 8 else ""
    tag += ",pipelined" if pipelined else ""
    line = {
        "metric": f"lora_sft_tokens_per_sec_per_chip[{model},B{B}xT{T}{tag}]",
        "value": round(toks_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs, 3) if vs is not None else None,
        # explicit provenance so a CPU-only round can never be read as TPU
        # signal: the MEASURED platform, straight from the device that ran
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
    }
    if pipe_stats is not None:
        line["pipeline"] = {k: round(v, 3)
                            for k, v in pipe_stats.snapshot().items()}
    print(json.dumps(line))


def _pct(xs, q):
    """Nearest-sample percentile over an (un)sorted list — the one
    implementation every bench mode's p50/p95/p99 shares."""
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0


def child_serve(preflight=None):
    """DTX_BENCH_SERVE=1: continuous-batching serve bench. A mixed long/short
    chat workload runs through one BatchedEngine (paged KV cache + chunked
    prefill by default; DTX_BENCH_SERVE_PAGED=0 compares the dense cache) and
    the line carries the three serving north-stars: aggregate tokens/s, TTFT
    (time to first streamed token, where chunked prefill + the prefill token
    budget bite), and TPOT (inter-token time, where a long admission stalling
    decode would show). CPU numbers are smoke-only, like the pipeline bench.
    """
    import jax

    on_tpu = _child_backend()
    import threading

    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    if on_tpu:
        model, max_seq, short_new, long_new = "tinyllama-1.1b", 1024, 48, 32
        n_short, n_long = 12, 4
    else:  # CPU smoke: tiny model, tiny workload, same code path
        model, max_seq, short_new, long_new = "debug", 256, 12, 8
        n_short, n_long = 6, 2
    slots = int(os.environ.get("DTX_BENCH_SERVE_SLOTS", "4"))
    paged = os.environ.get("DTX_BENCH_SERVE_PAGED", "1") != "0"
    block = int(os.environ.get("DTX_BENCH_BLOCK_SIZE", "16"))
    budget = int(os.environ.get("DTX_BENCH_PREFILL_BUDGET", "256"))
    # decode path: auto = Pallas in-place kernel on TPU, XLA gather
    # elsewhere; "on" forces the kernel (interpret-mode on CPU — slower,
    # smoke-only) so the kernel-vs-gather contract runs on every platform
    kernel_mode = os.environ.get("DTX_BENCH_SERVE_KERNEL", "auto")
    # adapter-churn mode: M synthetic tenant adapters rotate through a
    # P-slot pool with M > P, so the run exercises load-on-miss + LRU
    # eviction under mixed traffic and reports adapter hit rate + load
    # latency next to tokens/s (the capacity story of the dynamic plane)
    n_adapters = int(os.environ.get("DTX_BENCH_SERVE_ADAPTERS", "0"))
    adapter_pool = int(os.environ.get(
        "DTX_BENCH_ADAPTER_POOL", str(max(1, n_adapters // 2))))
    adapter_names = []
    adapter_ckpts = {}
    tmpdir = None
    if n_adapters > 0:
        import tempfile

        from datatunerx_tpu.serving.adapters import make_adapter_sweep

        tmpdir = tempfile.mkdtemp(prefix="dtx-bench-adapters-")
        adapter_ckpts = make_adapter_sweep(tmpdir, f"preset:{model}",
                                           n_adapters)
        adapter_names = sorted(adapter_ckpts)
    decode_chunk = int(os.environ.get("DTX_BENCH_DECODE_CHUNK", "8"))
    engine_kw = dict(
        template="vanilla", max_seq_len=max_seq, slots=slots,
        decode_chunk=decode_chunk,
        adapters=adapter_ckpts or None,
        adapter_pool=adapter_pool if n_adapters else 0,
        kv_block_size=block if paged else 0,
        prefill_token_budget=budget if paged else 0,
    )
    eng = BatchedEngine(f"preset:{model}",
                        paged_kernel=kernel_mode if paged else "auto",
                        **engine_kw)
    decode_parity_checked = False
    try:
        tok = eng.tokenizer
        short_ids = tok.encode("a quick question about the weather today")
        long_ids = tok.encode("background context " * (max_seq // 4))
        eng.generate(short_ids, max_new_tokens=2)  # compile prefill+decode
        eng.generate(long_ids, max_new_tokens=2)

        if eng.paged_kernel:
            # a fast-but-wrong number must be unreportable: before the
            # clock starts, the kernel engine's outputs are asserted
            # token-identical (greedy AND fixed-seed sampled) against a
            # gather-oracle twin sharing every other knob
            oracle = BatchedEngine(f"preset:{model}", paged_kernel="off",
                                   **engine_kw)
            try:
                for ids in (short_ids, long_ids[: max_seq // 4]):
                    for kw in ({}, {"temperature": 0.8, "top_p": 0.9,
                                    "seed": 11}):
                        want = oracle.generate(ids, max_new_tokens=8, **kw)
                        got = eng.generate(ids, max_new_tokens=8, **kw)
                        assert got == want, (
                            "paged kernel diverged from the gather oracle "
                            f"(kw={kw}): {got} != {want}")
            finally:
                oracle.close()
            decode_parity_checked = True

        lock = threading.Lock()
        per_req = []  # (t_submit, [token arrival times])

        def consume(req, t0):
            stamps = []
            while True:
                t = req.stream.get()
                if t is None:
                    break
                stamps.append(time.perf_counter())
            with lock:
                per_req.append((t0, stamps, req.error))

        threads = []
        wall0 = time.perf_counter()
        # interleave: every 3rd request is a long prompt, arriving while
        # short decodes are in flight — the head-of-line-blocking shape
        workload = []
        li = si = 0
        while li < n_long or si < n_short:
            if si < n_short:
                workload.append((short_ids, short_new)); si += 1
            if si % 2 == 0 and li < n_long:
                workload.append((long_ids, long_new)); li += 1
        for i, (ids, max_new) in enumerate(workload):
            t0 = time.perf_counter()
            # churn mode: requests cycle the adapter population (every 4th
            # stays on base) so residency is constantly contested
            adapter = (adapter_names[i % len(adapter_names)]
                       if adapter_names and i % 4 else "")
            req = eng.submit(ids, max_new_tokens=max_new, adapter=adapter)
            th = threading.Thread(target=consume, args=(req, t0), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - wall0
    finally:
        eng.close()

    errors = [e for _, _, e in per_req if e]
    ttfts = sorted((s[0] - t0) for t0, s, e in per_req if s and not e)
    tpots = sorted((s[-1] - s[0]) / (len(s) - 1)
                   for _, s, e in per_req if len(s) > 1 and not e)
    total_tokens = sum(len(s) for _, s, _ in per_req)
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    pct = _pct
    decode_path = eng.decode_path
    tag = (f"{model},slots{slots}," +
           (f"paged,bs{block},budget{budget}" if paged else "dense") +
           (",kernel" if decode_path == "pallas" else "") +
           (f",adapters{n_adapters}/pool{adapter_pool}"
            if n_adapters else ""))
    line = {
        "metric": f"serve_tokens_per_sec[{tag}]",
        "value": round(total_tokens / wall, 1) if wall > 0 else 0.0,
        "unit": "tokens/s",
        "vs_baseline": None,  # no prior serve-bench round to compare against
        # explicit provenance so a CPU-only round can never be read as TPU
        # signal: the MEASURED platform, straight from the device that ran
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
        # decode-path provenance next to platform/cpu_fallback: which
        # attention read served this number (pallas kernel / XLA gather /
        # dense), and whether the kernel run passed its pre-clock
        # token-parity gate against the gather oracle
        "paged_kernel": decode_path == "pallas",
        "decode_path": decode_path,
        "serve": {
            "requests": len(per_req),
            "errors": len(errors),
            "tokens": total_tokens,
            "decode_parity_checked": decode_parity_checked,
            "ttft_ms_mean": round(mean(ttfts) * 1e3, 1),
            "ttft_ms_p50": round(pct(ttfts, 0.5) * 1e3, 1),
            "ttft_ms_p95": round(pct(ttfts, 0.95) * 1e3, 1),
            "ttft_ms_p99": round(pct(ttfts, 0.99) * 1e3, 1),
            "tpot_ms_mean": round(mean(tpots) * 1e3, 2),
            "tpot_ms_p50": round(pct(tpots, 0.5) * 1e3, 2),
            "tpot_ms_p95": round(pct(tpots, 0.95) * 1e3, 2),
            "tpot_ms_p99": round(pct(tpots, 0.99) * 1e3, 2),
            "prefill_stats": dict(eng.prefill_stats),
        },
    }
    occ = eng.adapter_occupancy() if n_adapters else None
    if occ is not None:
        lookups = occ["hits"] + occ["misses"]
        load_ms = sorted(occ.get("load_ms") or [])
        line["serve"]["adapters"] = {
            "count": n_adapters,
            "pool_slots": occ["slots"],
            "hit_rate": round(occ["hits"] / lookups, 3) if lookups else None,
            "loads": occ["loads"],
            "evictions": occ["evictions"],
            "load_ms_p50": round(pct(load_ms, 0.5), 1),
            "load_ms_p95": round(pct(load_ms, 0.95), 1),
        }
    if preflight is not None:
        line["preflight"] = preflight
    print(json.dumps(line), flush=True)


def child_serve_capacity(preflight=None):
    """DTX_BENCH_SERVE_CAPACITY=1: KV-overcommit capacity twin bench. The
    same reservation-heavy mixed workload (short prompts with generous
    ``max_new`` budgets — the shape where eager reserve strands the most
    blocks — interleaved with longer prompts) runs on TWIN engines over
    ONE block budget: eager reserve (``kv_overcommit off``, today's
    ceil((prompt+max_new)/bs) admission) vs overcommit (lazy reserve +
    on-demand growth + youngest-first preemption). The scoreboard is MAX
    CONCURRENT IN-FLIGHT SESSIONS at token parity, plus blocks-per-session
    p50/p95, preemption/resume counts, and tokens/s.

    Before the clock starts, the overcommit twin's outputs are asserted
    token-identical (greedy AND fixed-seed sampled) against the eager twin
    — preemption/growth must be invisible in the tokens, or the capacity
    number is unreportable. The run also asserts the acceptance bar: the
    overcommit twin admits >= 1.5x the eager twin's peak concurrent
    sessions on the same pool, with zero errors (no preemption deadlock).
    CPU numbers are smoke-only, like the serve bench."""
    import jax

    on_tpu = _child_backend()
    import threading

    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    if on_tpu:
        model, max_seq, short_new, long_new = "tinyllama-1.1b", 1024, 192, 32
        n_short, n_long = 10, 3
    else:
        model, max_seq, short_new, long_new = "debug", 256, 64, 16
        n_short, n_long = 6, 2
    slots = int(os.environ.get("DTX_BENCH_SERVE_SLOTS", "4"))
    block = int(os.environ.get("DTX_BENCH_BLOCK_SIZE", "16"))
    # a pool sized so EAGER reserve is the binding constraint: roughly two
    # short sessions' eager reserve, while lazy reserve fits all `slots`
    blocks = int(os.environ.get(
        "DTX_BENCH_KV_BLOCKS",
        str(2 * (-(-(64 + short_new) // block)) + 4 if not on_tpu else
            2 * (-(-(256 + short_new) // block)) + 4)))
    engine_kw = dict(
        template="vanilla", max_seq_len=max_seq, slots=slots,
        decode_chunk=int(os.environ.get("DTX_BENCH_DECODE_CHUNK", "8")),
        kv_block_size=block, kv_blocks=blocks)
    pct = _pct

    def run_workload(eng):
        tok = eng.tokenizer
        short_ids = tok.encode("a quick question about the weather today")
        long_ids = tok.encode("background context " * (max_seq // 8))
        lock = threading.Lock()
        per_req = []

        def consume(req, t0):
            stamps = []
            while True:
                t = req.stream.get()
                if t is None:
                    break
                stamps.append(time.perf_counter())
            with lock:
                per_req.append((t0, stamps, req.error))

        workload = []
        li = si = 0
        while li < n_long or si < n_short:
            if si < n_short:
                workload.append((short_ids, short_new)); si += 1
            # longs arrive after the first slot-filling wave of shorts, so
            # the peak-concurrency comparison measures RESERVE pessimism
            # (the thing overcommit removes), not long-prompt FIFO waits
            if (si % 5 == 0 or si >= n_short) and li < n_long:
                workload.append((long_ids, long_new)); li += 1
        threads = []
        wall0 = time.perf_counter()
        for ids, max_new in workload:
            t0 = time.perf_counter()
            req = eng.submit(ids, max_new_tokens=max_new)
            th = threading.Thread(target=consume, args=(req, t0), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - wall0
        # the LIVENESS gate proper: a deadlocked session would hang its
        # consumer past the join timeout and silently vanish from per_req —
        # every submitted request must have terminated, or the capacity
        # number is unreportable
        assert len(per_req) == len(workload) and \
            not any(th.is_alive() for th in threads), (
            f"{len(workload) - len(per_req)} session(s) never terminated "
            "— preemption deadlock")
        tokens = sum(len(s) for _, s, _ in per_req)
        errors = [e for _, _, e in per_req if e]
        sess_blocks = sorted(eng.kv_stats["session_blocks"])
        preempts = dict(eng.preempt_stats)
        return {
            "requests": len(per_req), "errors": len(errors),
            "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1) if wall > 0 else 0.0,
            "peak_sessions": eng.kv_stats["peak_sessions"],
            "blocks_per_session_p50": pct(sess_blocks, 0.5),
            "blocks_per_session_p95": pct(sess_blocks, 0.95),
            "preemptions": preempts.get("exported", 0)
            + preempts.get("requeued_prefill", 0),
            "resumes": preempts.get("resumed", 0),
            "overcommit_peak_ratio": None,
        }

    eager = BatchedEngine(f"preset:{model}", kv_overcommit="off",
                          **engine_kw)
    over = BatchedEngine(f"preset:{model}", kv_overcommit="on",
                         **engine_kw)
    try:
        tok = eager.tokenizer
        probes = [tok.encode("a quick question about the weather today"),
                  tok.encode("tell me something entirely different")]
        # pre-clock token-parity gate: growth + preemption must be
        # invisible in the tokens before any capacity number is reportable
        for ids in probes:
            for kw in ({}, {"temperature": 0.8, "top_p": 0.9, "seed": 11}):
                want = eager.generate(ids, max_new_tokens=12, **kw)
                got = over.generate(ids, max_new_tokens=12, **kw)
                assert got == want, (
                    f"overcommit diverged from the eager twin (kw={kw}): "
                    f"{got} != {want}")
        eager_stats = run_workload(eager)
        over_stats = run_workload(over)
    finally:
        eager.close()
        over.close()

    assert over_stats["errors"] == 0 and eager_stats["errors"] == 0, (
        "capacity workload dropped sessions (preemption deadlock?): "
        f"{over_stats} vs {eager_stats}")
    ratio = (over_stats["peak_sessions"]
             / max(1, eager_stats["peak_sessions"]))
    over_stats["overcommit_peak_ratio"] = round(ratio, 2)
    assert ratio >= 1.5, (
        "overcommit admitted no more concurrent sessions than eager "
        f"reserve on the same pool: {over_stats['peak_sessions']} vs "
        f"{eager_stats['peak_sessions']} (ratio {ratio:.2f} < 1.5)")
    tag = f"{model},slots{slots},bs{block},blocks{blocks}"
    line = {
        "metric": f"serve_capacity_sessions[{tag}]",
        "value": over_stats["peak_sessions"],
        "unit": "sessions",
        "vs_baseline": None,
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
        "decode_path": over.decode_path,
        "capacity": {
            "parity_checked": True,
            "kv_blocks": blocks, "block_size": block, "slots": slots,
            "peak_ratio": round(ratio, 2),
            "overcommit": over_stats,
            "eager": eager_stats,
        },
    }
    if preflight is not None:
        line["preflight"] = preflight
    print(json.dumps(line), flush=True)


def child_serve_spec(preflight=None):
    """DTX_BENCH_SERVE_SPEC=1: speculative-decoding serve bench. The same
    mixed greedy workload runs on TWIN engines — spec-on (take:N
    self-speculative draft) vs spec-off — over the same model, twice:

    - **aligned**: the target's post-draft layers' output projections are
      scaled toward zero (residual passthrough), so the truncated draft is
      a faithful approximation of the target — the trained-draft regime
      where speculation pays. The line reports acceptance rate, mean
      accepted length, and the TPOT p50/p95 delta vs the spec-off twin.
    - **adversarial**: raw random deep layers — the draft is noise and
      acceptance collapses. The run asserts the adaptive-k controller
      demonstrably DISABLES speculation (plain pending-form fallback) so
      TPOT cannot regress vs spec-off.

    A TREE sub-run rides along (``--spec_tree WxD`` at depth D == chain k,
    so the draft cost is identical): a contested mediocre-draft regime
    compares tree vs chain accept-length p50 (the tree must not lose — its
    branch 0 IS the chain path) and TPOT ratios, and an adversarial tree
    run asserts the controller stands tree speculation down too.

    Before the clock starts, every spec-on engine's greedy outputs are
    asserted token-identical to the spec-off twin (the PR 13 kernel-gate
    pattern): a fast-but-wrong number must be unreportable. The JSON line
    carries ``spec_mode``/``spec_draft``/``spec_tree``/``decode_path``
    provenance next to ``platform``/``cpu_fallback``. CPU numbers are
    smoke-only.
    """
    import dataclasses

    import jax

    on_tpu = _child_backend()
    import threading

    from datatunerx_tpu.models.config import PRESETS
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    layers = int(os.environ.get("DTX_BENCH_SPEC_LAYERS", "6"))
    take = int(os.environ.get("DTX_BENCH_SPEC_TAKE", "1"))
    k = int(os.environ.get("DTX_BENCH_SPEC_K", "4"))
    slots = int(os.environ.get("DTX_BENCH_SERVE_SLOTS", "4"))
    block = int(os.environ.get("DTX_BENCH_BLOCK_SIZE", "16"))
    max_seq, short_new, long_new = 256, 24, 16
    n_short, n_long = 6, 2
    if "bench-spec" not in PRESETS:
        PRESETS["bench-spec"] = dataclasses.replace(
            PRESETS["debug"], name="bench-spec", num_layers=layers)
    engine_kw = dict(
        template="vanilla", max_seq_len=max_seq, slots=slots,
        decode_chunk=int(os.environ.get("DTX_BENCH_DECODE_CHUNK", "8")),
        kv_block_size=block)

    def align_params(params, alpha):
        """Scale post-draft layers' OUTPUT projections toward zero: the
        residual stream passes through them near-unchanged, so take:N
        approximates the full target while the target still pays every
        layer's compute. Layers < take are untouched, so the draft (sliced
        at engine construction) stays numerically identical to the
        target's early layers. alpha sets how faithful the draft is:
        1e-3 ~ trained-draft regime, ~0.3 a mediocre draft whose chain
        proposals diverge early (the regime tree drafts exist for)."""
        layers_t = dict(params["layers"])
        for name in ("o_proj", "down_proj"):
            sub = dict(layers_t[name])
            sub["kernel"] = sub["kernel"].at[take:].multiply(alpha)
            layers_t[name] = sub
        out = dict(params)
        out["layers"] = layers_t
        return out

    pct = _pct

    def run_workload(eng):
        tok = eng.tokenizer
        short_ids = tok.encode("a quick question about the weather today")
        long_ids = tok.encode("background context " * (max_seq // 8))
        lock = threading.Lock()
        per_req = []

        def consume(req, t0):
            stamps = []
            while True:
                t = req.stream.get()
                if t is None:
                    break
                stamps.append(time.perf_counter())
            with lock:
                per_req.append((t0, stamps, req.error))

        workload = []
        li = si = 0
        while li < n_long or si < n_short:
            if si < n_short:
                workload.append((short_ids, short_new)); si += 1
            if si % 2 == 0 and li < n_long:
                workload.append((long_ids, long_new)); li += 1
        threads = []
        wall0 = time.perf_counter()
        for ids, max_new in workload:
            t0 = time.perf_counter()
            req = eng.submit(ids, max_new_tokens=max_new)
            th = threading.Thread(target=consume, args=(req, t0), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - wall0
        tokens = sum(len(s) for _, s, _ in per_req)
        errors = [e for _, _, e in per_req if e]
        tpots = [(s[-1] - s[0]) / (len(s) - 1)
                 for _, s, e in per_req if len(s) > 1 and not e]
        return {
            "requests": len(per_req), "errors": len(errors),
            "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1) if wall > 0 else 0.0,
            "tpot_ms_p50": round(pct(tpots, 0.5) * 1e3, 2),
            "tpot_ms_p95": round(pct(tpots, 0.95) * 1e3, 2),
        }

    def run_pair(alpha, tree=None, mode="auto", epilogue="auto",
                 learned=True):
        from datatunerx_tpu.obs.metrics import (
            Registry,
            spec_accept_len_histogram,
        )

        reg = Registry()
        off = BatchedEngine("preset:bench-spec",
                            sampling_epilogue=epilogue, **engine_kw)
        on = BatchedEngine("preset:bench-spec", spec_draft=f"take:{take}",
                           spec_k=k, spec_mode=mode, spec_tree=tree,
                           spec_tree_learned=learned,
                           sampling_epilogue=epilogue,
                           registry=reg, **engine_kw)
        try:
            if alpha is not None:
                off.params = align_params(off.params, alpha)
                on.params = align_params(on.params, alpha)
            tok = off.tokenizer
            probes = [tok.encode("a quick question about the weather today"),
                      tok.encode("tell me something entirely different")]
            # pre-clock token-parity gate (greedy): the spec engine's
            # output must be IDENTICAL to the non-spec twin before any
            # number it produces is reportable
            for ids in probes:
                want = off.generate(ids, max_new_tokens=12)
                got = on.generate(ids, max_new_tokens=12)
                assert got == want, (
                    f"spec-on diverged from spec-off twin: {got} != {want}")
            off_stats = run_workload(off)
            on_stats = run_workload(on)
            info = on.spec_info() or {}
            proposed = info.get("proposed", 0)
            accepted = info.get("accepted", 0)
            row_steps = info.get("row_steps", 0)
            h_len = spec_accept_len_histogram(reg)
            out = {
                "parity_checked": True,
                "accept_rate": (round(accepted / proposed, 3)
                                if proposed else None),
                # true mean accepted length per verify event — robust to
                # the controller shrinking k mid-run (proposed tracks the
                # ACTUAL per-step k, so accepted*k/proposed would inflate)
                "mean_accept_len": (round(accepted / row_steps, 2)
                                    if row_steps else None),
                # per-row accepted-length p50 from the same histogram the
                # server exports — the tree-vs-chain comparison statistic
                "accept_len_p50": (round(h_len.percentile(0.5), 2)
                                   if h_len.count else None),
                "spec_steps": info.get("spec_steps", 0),
                "plain_steps": info.get("plain_steps", 0),
                "controller_active": bool(info.get("active")),
                "disabled_events": info.get("disabled_events", 0),
                "on": on_stats, "off": off_stats,
                "tpot_p50_ratio": (
                    round(on_stats["tpot_ms_p50"] / off_stats["tpot_ms_p50"],
                          3) if off_stats["tpot_ms_p50"] else None),
            }
            out["sampling_epilogue"] = on.sampling_epilogue
            out["epilogue_impl"] = on._epilogue_impl
            out["fused_steps"] = on.sampling_stats["fused_steps"]
            if tree is not None:
                out["tree_steps"] = info.get("tree_steps", 0)
                out["tree"] = info.get("tree")
            return out, on.decode_path
        finally:
            off.close()
            on.close()

    aligned, decode_path = run_pair(alpha=1e-3)
    adversarial, _ = run_pair(alpha=None)
    # the adaptive controller's contract: on the adversarial workload
    # speculation must demonstrably stand down (plain fallback carries the
    # traffic), so its TPOT cannot drift from the spec-off twin's
    assert adversarial["plain_steps"] >= adversarial["spec_steps"], (
        "adaptive-k controller failed to disable spec on the adversarial "
        f"workload: {adversarial}")
    adversarial["controller_disabled"] = True

    # ---- tree-draft sub-run: same draft cost (depth D == chain k draft
    # forwards), contested regime (mediocre draft, spec pinned on so both
    # shapes keep drafting). Greedy tree branch 0 IS the chain path, so per
    # row tree acceptance dominates chain acceptance structurally — the
    # accept-length lift is the tree's whole value proposition.
    tree_spec_s = os.environ.get("DTX_BENCH_SPEC_TREE", f"2x{k}")
    contested_alpha = float(os.environ.get("DTX_BENCH_SPEC_ALPHA", "0.12"))
    chain_c, _ = run_pair(alpha=contested_alpha, mode="on")
    # learned=False pins the fixed WxD rectangle controller — the
    # chain-vs-tree statistic keeps its pre-learned-shapes meaning
    tree_c, _ = run_pair(alpha=contested_alpha, tree=tree_spec_s, mode="on",
                         learned=False)
    # adversarial run keeps the LEARNED controller (default) — standing
    # down must hold for the controller that actually ships
    tree_adv, _ = run_pair(alpha=None, tree=tree_spec_s)
    # never-slower carries over to trees: adversarial drafts stand down
    assert tree_adv["plain_steps"] >= tree_adv["spec_steps"], (
        "adaptive controller failed to disable TREE spec on the "
        f"adversarial workload: {tree_adv}")
    tree_adv["controller_disabled"] = True
    if (tree_c["accept_len_p50"] is not None
            and chain_c["accept_len_p50"] is not None):
        # 0.5 slack: p50 is bucketed and concurrent submits batch rows
        # slightly differently between the twin runs
        assert tree_c["accept_len_p50"] >= chain_c["accept_len_p50"] - 0.5, (
            "tree drafts failed to lift accept_len p50 over the chain at "
            f"equal draft cost: tree={tree_c['accept_len_p50']} "
            f"chain={chain_c['accept_len_p50']}")
    tree_block = {
        "spec_tree": tree_spec_s,
        "contested_alpha": contested_alpha,
        "chain_contested": chain_c,
        "contested": tree_c,
        "adversarial": tree_adv,
        "accept_len_p50_lift": (
            round(tree_c["accept_len_p50"] - chain_c["accept_len_p50"], 2)
            if (tree_c["accept_len_p50"] is not None
                and chain_c["accept_len_p50"] is not None) else None),
        # TPOT p50 ratio vs the spec-off twin: tree should sit at or below
        # the chain's ratio (reported, not asserted — CPU timing is noise)
        "tpot_ratio_le_chain": (
            tree_c["tpot_p50_ratio"] <= chain_c["tpot_p50_ratio"]
            if (tree_c["tpot_p50_ratio"] is not None
                and chain_c["tpot_p50_ratio"] is not None) else None),
    }

    # ---- learned-vs-fixed tree sub-run (PR 20): the SAME contested twin,
    # learned per-depth widths (AdaptiveTree) vs the fixed WxD rectangle.
    # The learned controller prunes dead branches (draft FLOPs the fixed
    # rectangle burns for nothing), so tokens/s must not regress. 0.85
    # slack: CPU smoke timing is noisy; TPU runs separate cleanly.
    tree_l, _ = run_pair(alpha=contested_alpha, tree=tree_spec_s, mode="on",
                         learned=True)
    l_tps = tree_l["on"]["tokens_per_sec"]
    f_tps = tree_c["on"]["tokens_per_sec"]
    assert not f_tps or l_tps >= 0.85 * f_tps, (
        "learned tree shapes regressed tokens/s vs the fixed rectangle: "
        f"learned={l_tps} fixed={f_tps}")
    tree_block["learned"] = tree_l
    tree_block["fixed"] = tree_c
    tree_block["learned_tps_ratio"] = (round(l_tps / f_tps, 3)
                                       if f_tps else None)
    tree_block["learned_ge_fixed"] = bool(not f_tps or l_tps >= f_tps)
    learned_widths = (tree_l.get("tree") or {}).get("widths")

    # ---- fused-epilogue sub-run (PR 20): the aligned twin again, spec-on
    # engine forced through the fused sampling epilogue vs explicitly off.
    # run_pair's pre-clock parity gate doubles as the engine-level
    # fused-vs-legacy token-exactness proof; the greedy fused path skips
    # the legacy sampler's full-vocab sort, so TPOT must not regress
    # (1.2 noise guard on CPU smoke; the ≤1.0 verdict is reported).
    ep_on, _ = run_pair(alpha=1e-3, epilogue="on")
    ep_off, _ = run_pair(alpha=1e-3, epilogue="off")
    assert ep_on["fused_steps"] > 0, (
        "epilogue-on run never took the fused path: "
        f"{ep_on['epilogue_impl']}")
    assert ep_off["fused_steps"] == 0, "epilogue-off run took the fused path"
    ep_ratio = (round(ep_on["on"]["tpot_ms_p50"] /
                      ep_off["on"]["tpot_ms_p50"], 3)
                if ep_off["on"]["tpot_ms_p50"] else None)
    assert ep_ratio is None or ep_ratio <= 1.2, (
        "fused sampling epilogue regressed TPOT p50 vs the legacy sampler: "
        f"ratio={ep_ratio}")
    epilogue_block = {
        "impl": ep_on["epilogue_impl"],
        "on": ep_on["on"], "off": ep_off["on"],
        "fused_steps": ep_on["fused_steps"],
        "tpot_p50_ratio": ep_ratio,
        "tpot_le_off": ep_ratio is not None and ep_ratio <= 1.0,
    }
    tag = (f"bench-spec,L{layers},take{take},k{k},tree{tree_spec_s},"
           f"slots{slots},bs{block}")
    line = {
        "metric": f"serve_spec_tokens_per_sec[{tag}]",
        "value": aligned["on"]["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": None,
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
        # provenance: which decode path (spec verify runs the multi-token
        # gather path even under --paged_kernel; the Pallas kernel is the
        # T=1 non-spec fast path) and which draft produced these numbers
        "decode_path": decode_path,
        "spec_mode": "auto",
        "spec_draft": f"take:{take}",
        "spec_tree": tree_spec_s,
        # PR 20 provenance: which sampler path produced these numbers and
        # the tree shape the learned controller settled on
        "sampling_epilogue": epilogue_block["impl"],
        "tree_shape": (",".join(str(w) for w in learned_widths)
                       if learned_widths else tree_spec_s),
        "spec": {"k": k, "target_layers": layers, "draft_layers": take,
                 "aligned": aligned, "adversarial": adversarial,
                 "tree": tree_block, "epilogue": epilogue_block},
    }
    if preflight is not None:
        line["preflight"] = preflight
    print(json.dumps(line), flush=True)


def child_replay(preflight=None):
    """DTX_BENCH_REPLAY=1: the trace-driven load-replay + chaos harness
    (datatunerx_tpu/loadgen/) against a 2-replica in-process fleet of REAL
    BatchedEngines behind a real Gateway, with a drain fired MID-STREAM
    (the chaos action waits for in-flight work) — judged by the SLO
    epilogue. Runs TWICE: with the KV session handoff on (drained
    sessions migrate; the run asserts ZERO dropped sessions and ZERO
    re-prefills via the engines' prefill-counter delta) and with it off
    plus an export-kill (today's reap-deadline behavior: sessions die
    mid-stream and fail over cold, re-prefilling — the counted baseline
    the handoff removes). The line carries both runs' numbers and the SLO
    verdict with any violated objective NAMED, which
    scripts/bench_job_summary.py lifts into the GH job summary. CPU
    numbers are smoke-only, like the serve bench."""
    import jax

    on_tpu = _child_backend()

    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway
    from datatunerx_tpu.loadgen.chaos import ChaosInjector
    from datatunerx_tpu.loadgen.replay import (
        LocalClient,
        ReplayRunner,
        drain_when_busy,
        slo_epilogue,
    )
    from datatunerx_tpu.loadgen.workload import WorkloadModel, summarize
    from datatunerx_tpu.obs.slo import SLOEvaluator, default_slos
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    model = "tinyllama-1.1b" if on_tpu else "debug"
    max_seq = 1024 if on_tpu else 256
    n_requests = int(os.environ.get("DTX_BENCH_REPLAY_REQUESTS",
                                    "24" if on_tpu else "12"))
    rps = float(os.environ.get("DTX_BENCH_REPLAY_RPS", "8"))

    def one_run(handoff: bool):
        engines = [
            BatchedEngine(f"preset:{model}", template="vanilla",
                          max_seq_len=max_seq, slots=2, decode_chunk=4)
            for _ in range(2)  # shared program memo: second engine is cheap
        ]
        pool = ReplicaPool([InProcessReplica(f"replica-{i}", e)
                            for i, e in enumerate(engines)])
        gw = Gateway(pool, model_name=f"preset:{model}",
                     session_handoff=handoff)
        try:
            # tiny prompts: the replay measures the HARNESS + scheduler
            # under churn, not model quality; compile before the clock
            engines[0].generate(engines[0].tokenizer.encode("warm up"),
                                max_new_tokens=2)
            admits0 = sum(sum(e.prefill_stats.values()) for e in engines)
            wl = WorkloadModel(requests=n_requests, sessions=3, rps=rps,
                               seed=7, prompt_chars=40,
                               prompt_cap_chars=200,
                               output_tokens=24, output_cap_tokens=48)
            events = wl.generate()
            mid = events[-1]["t"] * 0.5

            def _drain(op):
                out = drain_when_busy(gw, op["replica"])
                if not handoff:
                    # today's reap-deadline kill: in-flight sessions on
                    # the drained replica die mid-stream and fail over
                    # on the cold (re-prefill) path. Loop briefly — a
                    # session still in its prefill isn't exportable yet.
                    killed, deadline = 0, time.monotonic() + 2.0
                    while killed == 0 and time.monotonic() < deadline:
                        killed = len(
                            engines[1].export_sessions()["sessions"])
                        if killed == 0:
                            time.sleep(0.02)
                    out["killed"] = killed
                return out

            chaos = ChaosInjector(
                [{"t": round(mid, 3), "op": "drain",
                  "replica": "replica-1"}],
                {"drain": _drain})
            runner = ReplayRunner(LocalClient(gw), max_inflight=8)
            evaluator = SLOEvaluator(runner.registry,
                                     default_slos("loadgen"))
            t0 = time.perf_counter()
            report = runner.run(events, chaos=chaos)
            wall = time.perf_counter() - t0
            verdict = slo_epilogue(evaluator, since_t=0.0,
                                   out=lambda s: print(s, file=sys.stderr))
            admissions = (sum(sum(e.prefill_stats.values())
                              for e in engines) - admits0)
            # each request cold-admits exactly once; anything beyond is a
            # session that re-prefilled after the drain
            re_prefills = max(0, admissions - report["requests"])
            return {
                "workload": summarize(events),
                "requests": report["requests"],
                "errors": report["errors"],
                "codes": report["codes"],
                "ttft_ms_p50": report["ttft_ms_p50"],
                "ttft_ms_p95": report["ttft_ms_p95"],
                "ttft_ms_p99": report["ttft_ms_p99"],
                "latency_ms_p99": report["latency_ms_p99"],
                "chaos": report.get("chaos", []),
                "handoff": gw.handoff_stats(),
                "admissions": admissions,
                "re_prefills": re_prefills,
                "slo_pass": verdict["pass"],
                "slo_violations": verdict["violations"],
                "wall_s": wall,
            }
        finally:
            gw.close()

    hot = one_run(handoff=True)
    # the drain-mid-stream acceptance assertions: handoff on = nothing
    # dropped, nothing re-prefilled
    assert hot["errors"] == 0, \
        f"handoff-on replay dropped sessions: {hot['codes']}"
    assert hot["re_prefills"] == 0, \
        f"handoff-on replay re-prefilled {hot['re_prefills']} session(s)"
    cold = one_run(handoff=False)

    line = {
        "metric": f"replay_requests_per_sec[{model},2replicas,drain]",
        "value": (round(hot["requests"] / hot["wall_s"], 2)
                  if hot["wall_s"] > 0 else 0.0),
        "unit": "req/s",
        "vs_baseline": None,
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
        "replay": {k: v for k, v in hot.items() if k != "wall_s"},
        "replay_cold": {
            "errors": cold["errors"],
            "codes": cold["codes"],
            "re_prefills": cold["re_prefills"],
            "handoff": cold["handoff"],
            "slo_pass": cold["slo_pass"],
        },
    }
    if preflight is not None:
        line["preflight"] = preflight
    print(json.dumps(line), flush=True)


def child_disagg(preflight=None):
    """DTX_BENCH_DISAGG=1: disaggregated-serving twin bench. The same
    mixed workload — short interactive requests plus long prompts sharing
    one long document preamble — runs against TWIN in-process fleets of
    REAL BatchedEngines at EQUAL chips:

    - **uniform**: two mixed replicas, role-blind least-busy routing
      (PR 15 behavior; no fleet plane).
    - **disagg**: one prefill specialist + one decode replica, the
      router's prompt-token threshold steering longs at the specialist,
      the fleet-shared prefix tier on, and (by default) the fleet
      handoff plane re-homing decode-ready sessions onto the decode
      replica mid-run.

    Before the clock starts, a token-parity gate (greedy AND fixed-seed
    sampled, engine-level; plus one greedy probe through each gateway)
    asserts the disagg twin's outputs byte-identical to the uniform twin
    — role routing, prefix sharing and handoff must be invisible in the
    tokens or the numbers are unreportable. The run then asserts the
    disaggregation claim at equal chips: TTFT p95 no worse AND tokens/s
    no worse than uniform, with zero errors on both twins. The win is
    structural — longs pay their shared-prefix prefill ONCE on the
    specialist instead of once per replica, and shorts on the decode
    replica never queue behind a long prefill. CPU numbers are
    smoke-only, like the serve bench."""
    import jax

    on_tpu = _child_backend()
    import threading

    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    model = "tinyllama-1.1b" if on_tpu else "debug"
    max_seq = 1024 if on_tpu else 256
    n_short = int(os.environ.get("DTX_BENCH_DISAGG_SHORT",
                                 "10" if on_tpu else "8"))
    n_long = int(os.environ.get("DTX_BENCH_DISAGG_LONG", "4"))
    short_new = 24 if on_tpu else 12
    long_new = 32 if on_tpu else 12
    handoff_on = os.environ.get("DTX_BENCH_DISAGG_HANDOFF", "1") != "0"

    def build(disagg: bool, threshold: int):
        from datatunerx_tpu.gateway.admission import AdmissionController

        engines = [
            BatchedEngine(f"preset:{model}", template="vanilla",
                          max_seq_len=max_seq, slots=2, decode_chunk=4,
                          # local prefix cache ON for BOTH twins (fair):
                          # the comparison is prefix LOCALITY via role
                          # routing, not cache-on vs cache-off
                          prefix_cache=4)
            for _ in range(2)  # shared program memo: 2nd engine is cheap
        ]
        roles = ["prefill", "decode"] if disagg else ["mixed", "mixed"]
        pool = ReplicaPool([
            InProcessReplica(f"replica-{i}", e, role=roles[i])
            for i, e in enumerate(engines)])
        # tokenizer-exact admission (both twins): the routing threshold
        # then compares true token counts, not the chars/4 heuristic
        tok = engines[0].tokenizer
        adm = AdmissionController(
            count_tokens=lambda s: len(tok.encode(s)))
        gw = Gateway(pool, model_name=f"preset:{model}", admission=adm,
                     prefill_threshold=threshold if disagg else 0,
                     fleet_prefix_bytes=(8 << 20) if disagg else 0,
                     fleet_handoff=disagg and handoff_on)
        return gw, engines

    def run_twin(gw):
        lock = threading.Lock()
        per_req = []

        def one(req, idx):
            t0 = time.perf_counter()
            ttft = None
            toks = 0
            err = None
            try:
                for _ in gw.chat_stream(dict(req),
                                        trace_id=f"disagg-{idx}"):
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    toks += 1
            except Exception as e:  # noqa: BLE001 — an error IS the data
                err = f"{type(e).__name__}: {e}"
            if ttft is None:
                # tiny presets can hit EOS before the first delta — the
                # queue+prefill wait is still the number being measured,
                # so fall back to end-to-end completion time
                ttft = time.perf_counter() - t0
            with lock:
                per_req.append((ttft, toks, err))

        # longs first (they are the work that must not block shorts),
        # shorts right behind — everything in flight together
        workload = long_reqs + short_reqs
        threads = []
        wall0 = time.perf_counter()
        for i, req in enumerate(workload):
            th = threading.Thread(target=one, args=(req, i), daemon=True)
            th.start()
            threads.append(th)
            time.sleep(0.01)
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - wall0
        assert len(per_req) == len(workload) and \
            not any(th.is_alive() for th in threads), \
            "disagg workload: session(s) never terminated"
        ttfts = sorted(t * 1e3 for t, _, _ in per_req if t is not None)
        # LOGICAL tokens — each request's prompt plus its decoded deltas.
        # Identical prompt work is credited to both twins, so tokens/s is
        # a pure wall-clock comparison at equal work; the disagg twin's
        # skipped re-prefills (prefix extends on the specialist) show up
        # as the shorter wall, not as a smaller numerator
        tokens = prompt_toks_total + sum(n for _, n, _ in per_req)
        errors = [e for _, _, e in per_req if e]
        return {
            "requests": len(per_req), "errors": len(errors),
            "error_detail": errors[:3],
            "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1) if wall > 0 else 0.0,
            "ttft_ms_p50": _pct(ttfts, 0.5),
            "ttft_ms_p95": _pct(ttfts, 0.95),
            "wall_s": round(wall, 3),
        }

    probe_req = {"messages": [{"role": "user", "content": "parity probe"}],
                 "max_tokens": 8}
    gw_u, eng_u = build(disagg=False, threshold=0)
    # size the shared preamble in MEASURED tokens (the debug preset's
    # tokenizer is near char-level): it must fit max_seq with decode
    # room, or the engine truncates it and the prefix is never shared.
    # The preamble rides in the USER turn — the vanilla template renders
    # only the final query, so a system turn would be dropped on the
    # floor and the longs would not actually be long
    tok = eng_u[0].tokenizer
    base = "clause and subclause policy detail. "
    # bucket math bounds the preamble: prepare_prompt pads plen to
    # DECODE_BUCKET (64) multiples and a prefix EXTEND appends a further
    # padded suffix bucket, so the warm entry's cursor + 64 must still
    # leave decode room under max_seq — 0.35*max_seq keeps the CPU
    # preset's warm plen at 128 of 256 (extend cursor 192, room 64)
    target = int(max_seq * (0.6 if on_tpu else 0.35))
    preamble = "You are a meticulous assistant. "
    while len(tok.encode(preamble + base)) < target:
        preamble += base
    long_reqs = [{"messages": [
        {"role": "user", "content": f"{preamble}\nsummarize item {i}."}],
        "max_tokens": long_new} for i in range(n_long)]
    short_reqs = [{"messages": [
        {"role": "user", "content": f"quick question {i}?"}],
        "max_tokens": short_new} for i in range(n_short)]
    # the prefix-cache win is only real if the warm prompt's tokens are a
    # STRICT prefix of every long's tokens (longest_prefix is a trie walk
    # over whole cached keys) — assert it, or a tokenizer merging across
    # the preamble/suffix boundary silently degrades extends to full
    # prefills and the bench measures nothing
    pre_ids = list(tok.encode(preamble))
    for r in long_reqs:
        ids = list(tok.encode(r["messages"][0]["content"]))
        assert len(ids) > len(pre_ids) and ids[:len(pre_ids)] == pre_ids, \
            "warm preamble does not token-prefix the long prompts"
    prompt_toks_total = sum(
        len(tok.encode(m["content"]))
        for r in long_reqs + short_reqs for m in r["messages"])
    threshold = int(os.environ.get(
        "DTX_BENCH_DISAGG_THRESHOLD", str(target // 2)))
    gw_d, eng_d = build(disagg=True, threshold=threshold)
    try:
        # pre-clock token-parity gate (engine level, greedy + seeded
        # sampled): the twins must be the same model before the clock
        # may compare them
        ids = eng_u[0].tokenizer.encode("a quick question about weather")
        for kw in ({}, {"temperature": 0.8, "top_p": 0.9, "seed": 11}):
            want = eng_u[0].generate(ids, max_new_tokens=12, **kw)
            got = eng_d[0].generate(ids, max_new_tokens=12, **kw)
            assert got == want, (
                f"disagg twin diverged from uniform (kw={kw}): "
                f"{got} != {want}")
        # gateway-level greedy probe: role routing must not change tokens
        want = gw_u.chat(dict(probe_req), trace_id="parity-u")
        got = gw_d.chat(dict(probe_req), trace_id="parity-d")
        assert got == want, (
            f"gateway routing changed tokens: {got!r} != {want!r}")
        if gw_d.fleet is not None:
            gw_d.fleet.start(0.05)
        # steady-state warm phase (both twins, pre-clock): the BARE
        # preamble has been seen before the measured burst, and its
        # cached entry strict-prefixes every long — the clocked
        # comparison is prefix LOCALITY (disagg: every long lands where
        # the prefix is hot and pays a suffix-only extend; uniform:
        # role-blind spread re-prefills the preamble per replica), not
        # first-ever-prefill cost
        warm = {"messages": [{"role": "user", "content": preamble}],
                "max_tokens": 4}
        gw_u.chat(dict(warm), trace_id="warm-u")
        gw_d.chat(dict(warm), trace_id="warm-d")
        uniform = run_twin(gw_u)
        disagg = run_twin(gw_d)
        fleet_stats = gw_d.fleet.stats() if gw_d.fleet is not None else {}
        role_routes = dict(getattr(gw_d.router, "role_routes", {}))
    finally:
        gw_u.close()
        gw_d.close()

    assert uniform["errors"] == 0 and disagg["errors"] == 0, (
        "disagg twin bench dropped requests: "
        f"uniform={uniform['error_detail']} "
        f"disagg={disagg['error_detail']}")
    assert disagg["ttft_ms_p95"] <= uniform["ttft_ms_p95"], (
        "disaggregation did NOT hold TTFT p95 at equal chips: "
        f"{disagg['ttft_ms_p95']}ms vs uniform {uniform['ttft_ms_p95']}ms")
    assert disagg["tokens_per_sec"] >= uniform["tokens_per_sec"], (
        "disaggregation did NOT hold tokens/s at equal chips: "
        f"{disagg['tokens_per_sec']} vs uniform "
        f"{uniform['tokens_per_sec']}")
    tag = f"{model},2replicas,thr{threshold}"
    line = {
        "metric": f"serve_disagg_tokens_per_sec[{tag}]",
        "value": disagg["tokens_per_sec"],
        "unit": "tok/s",
        "vs_baseline": round(disagg["tokens_per_sec"]
                             / max(uniform["tokens_per_sec"], 1e-9), 3),
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
        "disagg": {
            "parity_checked": True,
            "handoff_enabled": handoff_on,
            "threshold_tokens": threshold,
            "workload": {"long": n_long, "short": n_short},
            "uniform": uniform,
            "disaggregated": disagg,
            "fleet": fleet_stats,
            "role_routes": role_routes,
        },
    }
    if preflight is not None:
        line["preflight"] = preflight
    print(json.dumps(line), flush=True)


def child_tenant(preflight=None):
    """DTX_BENCH_TENANT=1: multi-tenant QoS twin bench. The same mixed
    two-tenant workload — a pinned interactive tenant (plat, one adapter,
    a TTFT objective) sharing the fleet with a 3x-heavier bulk tenant
    (batch, two adapters churning the pool, a KV-block quota) — runs
    against TWIN in-process fleets of REAL BatchedEngines at equal chips:

    - **off**: no tenant directory, no host tier (PR 16 behavior): the
      tenant tags ride the requests but price nothing, every adapter
      fights the same LRU, and every evict→reload pays the orbax read.
    - **on**: the tenancy plane (datatunerx_tpu/tenancy/): plat's adapter
      pinned against eviction, batch priced against its block quota at
      admission, and the host-RAM adapter tier catching evicted weights
      so reloads skip orbax.

    One replica per twin ON PURPOSE: with two replicas the router's
    residency-affinity would park each bulk adapter on its own replica
    and the pool would never churn — the single 2-slot pool (pinned
    adapter + 1 contested slot under 2 bulk adapters) makes the
    evict→reload cycle the bench exists to price deterministic. The line
    reports the pinned tenant's TTFT p95 on both twins plus the host
    tier's hit rate, and asserts: zero 5xx on both twins; the pinned
    adapter still resident after the churn; the churn actually evicted;
    and every re-load after the first came from host RAM (each adapter
    paid orbax AT MOST ONCE). CPU numbers are smoke-only, like the serve
    bench."""
    import tempfile

    import jax

    on_tpu = _child_backend()

    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway
    from datatunerx_tpu.loadgen.replay import LocalClient, ReplayRunner
    from datatunerx_tpu.loadgen.workload import WorkloadModel, summarize
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    model = "tinyllama-1.1b" if on_tpu else "debug"
    max_seq = 1024 if on_tpu else 256
    n_requests = int(os.environ.get("DTX_BENCH_TENANT_REQUESTS",
                                    "24" if on_tpu else "12"))
    rps = float(os.environ.get("DTX_BENCH_TENANT_RPS", "3"))

    tmp = tempfile.mkdtemp(prefix="dtx-tenant-bench-")
    cks = {name: make_adapter_checkpoint(
               os.path.join(tmp, name), f"preset:{model}",
               seed=i + 3, rank=4)
           for i, name in enumerate(("plat-a", "batch-a", "batch-b"))}
    tenants_cfg = {
        "plat": {"tier": "pinned", "adapters": ["plat-a"], "share": 4.0,
                 "ttft_p95_ms": 2000.0},
        "batch": {"tier": "bulk", "adapters": ["batch-a", "batch-b"],
                  "share": 1.0, "kv_block_quota": 24},
    }
    mix = {"plat": {"adapters": ["plat-a"], "weight": 1.0},
           "batch": {"adapters": ["batch-a", "batch-b"], "weight": 3.0}}

    def tenant_p95(tstats: dict, name: str):
        """→ (p95_ms, source): a tenant's TTFT p95, falling back to its
        latency p95 when no request streamed a delta — the tiny debug
        model can sample EOS as the first token, which leaves every
        ttft_ms None and would report a meaningless 0.0. Real models on
        TPU stream, so there the headline is true TTFT."""
        t = tstats.get(name) or {}
        if t.get("ttft_ms_p95"):
            return t["ttft_ms_p95"], "ttft"
        return t.get("latency_ms_p95") or 0.0, "latency"

    def one_run(qos: bool):
        # a roomy block pool: the bench prices the TENANT quota, not the
        # fleet-wide block gate (dense-parity default would shed everyone)
        eng = BatchedEngine(
            f"preset:{model}", template="vanilla", max_seq_len=max_seq,
            slots=2, decode_chunk=4, adapters=cks, adapter_pool=2,
            adapter_rank_max=8, kv_block_size=16, kv_blocks=256,
            tenants=tenants_cfg if qos else None,
            host_adapter_cache_mb=64.0 if qos else 0.0)
        pool = ReplicaPool([InProcessReplica("replica-0", eng)])
        gw = Gateway(pool, model_name=f"preset:{model}",
                     tenants=tenants_cfg if qos else None)
        try:
            # compile + warm OUTSIDE the clock, identically on both
            # twins: the base decode step, every adapter's first pool
            # insert, and one LoRA-apply step each pay one-time jit
            # compiles that would otherwise all land on whichever twin
            # runs first and swamp its latencies. plat-a loads LAST so
            # the pinned adapter starts resident on both twins.
            eng.generate(eng.tokenizer.encode("warm up"), max_new_tokens=2)
            for name in ("batch-a", "batch-b", "plat-a"):
                eng.load_adapter(name, cks[name], preload=True)
                eng.chat([{"role": "user", "content": "warm"}],
                         max_new_tokens=2, adapter=name)
            wl = WorkloadModel(requests=n_requests, sessions=3, rps=rps,
                               seed=11, prompt_chars=30,
                               prompt_cap_chars=120, output_tokens=8,
                               output_cap_tokens=16, base_every=0,
                               tenants=mix)
            # ...and one full UNTIMED replay of the exact workload: the
            # per-adapter warm chats are single-slot and short-prompt, so
            # the measured pass would still pay first-compiles for the
            # long multi-turn prefill buckets and two-slot concurrency —
            # ~1.5s each on CPU, all billed to whichever twin runs first
            ReplayRunner(LocalClient(gw), max_inflight=8).run(wl.generate())
            events = wl.generate()
            runner = ReplayRunner(LocalClient(gw), max_inflight=8)
            t0 = time.perf_counter()
            report = runner.run(events)
            wall = time.perf_counter() - t0
            occ = eng.adapter_occupancy() or {}
            host = (eng.adapter_registry.host_tier_stats()
                    if eng.adapter_registry is not None else None)
            hits = (host or {}).get("host_hits", 0)
            orbax = (host or {}).get("orbax_loads", 0)
            tstats = report.get("tenants") or {}
            plat_p95, plat_src = tenant_p95(tstats, "plat")
            batch_p95, _ = tenant_p95(tstats, "batch")
            return {
                "workload": summarize(events),
                "requests": report["requests"],
                "errors": report["errors"],
                "codes": report["codes"],
                "tenants": tstats,
                "plat_ttft_ms_p95": plat_p95,
                "plat_p95_source": plat_src,
                "batch_ttft_ms_p95": batch_p95,
                "pool_evictions": occ.get("evictions", 0),
                "pinned_resident_at_end":
                    "plat-a" in (occ.get("resident_adapters") or []),
                "host_tier": host,
                "host_hit_rate": (round(hits / max(hits + orbax, 1), 3)
                                  if host is not None else None),
                "wall_s": wall,
            }
        finally:
            gw.close()

    qos_on = one_run(qos=True)
    qos_off = one_run(qos=False)
    assert qos_on["errors"] == 0 and qos_off["errors"] == 0, (
        "tenant twin bench dropped requests: "
        f"on={qos_on['codes']} off={qos_off['codes']}")
    for run, label in ((qos_on, "on"), (qos_off, "off")):
        plat = (run["tenants"].get("plat") or {})
        assert plat.get("ok", 0) >= 1, (
            f"pinned tenant served nothing on the qos-{label} twin "
            f"({plat}) — its TTFT p95 is meaningless")
    on_plat = qos_on["tenants"].get("plat") or {}
    assert not on_plat.get("shed"), (
        "the tenancy twin shed pinned-tenant traffic: "
        f"{on_plat} — quota pricing leaked onto the wrong tenant")
    assert qos_on["pool_evictions"] >= 1, (
        "bulk adapter churn never evicted — the host-tier hit rate "
        "measures nothing")
    assert qos_on["pinned_resident_at_end"], (
        "the pinned tenant's adapter was evicted despite the pin tier")
    host = qos_on["host_tier"] or {}
    assert host.get("host_hits", 0) >= 1, (
        f"no evict→reload came from the host tier: {host}")
    assert host.get("orbax_loads", 0) <= len(cks), (
        "an adapter paid the orbax read twice despite the host tier: "
        f"{host}")

    tag = f"{model},1replica,pool2,3adapters"
    on_p95 = qos_on["plat_ttft_ms_p95"] or 0.0
    off_p95 = qos_off["plat_ttft_ms_p95"] or 0.0
    assert on_p95 > 0 and off_p95 > 0, (
        "pinned-tenant p95 degenerated to 0 despite the latency "
        f"fallback: on={qos_on['tenants']} off={qos_off['tenants']}")
    line = {
        "metric": f"tenant_pinned_ttft_p95_ms[{tag}]",
        "value": on_p95,
        "unit": "ms",
        "vs_baseline": round(on_p95 / max(off_p95, 1e-9), 3),
        "platform": jax.devices()[0].platform,
        "cpu_fallback": not on_tpu,
        "tenant": {
            "workload": qos_on["workload"],
            "host_hit_rate": qos_on["host_hit_rate"],
            "p95_source": qos_on["plat_p95_source"],
            "qos_on": {k: v for k, v in qos_on.items()
                       if k not in ("wall_s", "workload")},
            "qos_off": {k: v for k, v in qos_off.items()
                        if k not in ("wall_s", "workload")},
        },
    }
    if preflight is not None:
        line["preflight"] = preflight
    print(json.dumps(line), flush=True)


# ------------------------------------------------------------- orchestrator

# The probe reports each phase AS IT COMPLETES (one JSON line, flushed), so
# when the device hangs the parent can read the partial stdout of the
# killed child and name the phase that hung — backend init, the first XLA
# compile, the first execution, or the first PALLAS (Mosaic) compile+run.
# If the plain-XLA phases pass but pallas_execute hangs, the Mosaic pipeline
# (which the paged-decode kernel rides) is the suspect — not the backend.
PREFLIGHT_PHASES = ("backend_init", "first_compile", "first_execute",
                    "pallas_execute")

_PREFLIGHT_CODE = """\
import json, os, time
t0 = time.perf_counter()
import jax
if os.environ.get("DTX_BENCH_FORCE_CPU"):
    jax.config.update("jax_platforms", "cpu")
dev = jax.devices()[0]
t1 = time.perf_counter()
print(json.dumps({"phase": "backend_init", "ms": round((t1 - t0) * 1e3, 1),
                  "platform": dev.platform}), flush=True)
import jax.numpy as jnp
x = jnp.ones((256, 256), jnp.float32)
compiled = jax.jit(lambda a: a @ a).lower(x).compile()
t2 = time.perf_counter()
print(json.dumps({"phase": "first_compile",
                  "ms": round((t2 - t1) * 1e3, 1)}), flush=True)
out = float(compiled(x)[0, 0])
t3 = time.perf_counter()
print(json.dumps({"phase": "first_execute", "ms": round((t3 - t2) * 1e3, 1),
                  "result": out}), flush=True)
# tiny Pallas kernel through the real Mosaic pipeline on TPU (interpret
# emulation elsewhere) — self-contained so the probe needs no repo import;
# engineered to reproduce the matmul phases' 256.0 check value
from jax.experimental import pallas as pl
def _k(a_ref, o_ref):
    o_ref[:] = a_ref[:] + a_ref[:]
a = jnp.full((128, 128), 128.0, jnp.float32)
pk = pl.pallas_call(_k, out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
                    interpret=dev.platform != "tpu")
out = float(pk(a)[0, 0])
t4 = time.perf_counter()
print(json.dumps({"phase": "pallas_execute", "ms": round((t4 - t3) * 1e3, 1),
                  "result": out}), flush=True)
"""


def _preflight_probe():
    """Probe the default device in a SUBPROCESS with per-phase timing,
    retrying over a window.

    A device can fail by hanging (not erroring), and a process that has
    initialized a hung platform cannot recover — so each probe is isolated,
    and a chip still held by an exiting process gets a few retries.

    Returns a report dict written into the bench JSON: ``ok``, ``attempts``,
    ``phases_ms`` (per completed phase), ``platform``, and — on failure —
    ``timed_out_phase`` / ``failed_phase`` naming where the probe died.
    """
    report = {"ok": False, "attempts": 0, "phases_ms": {}, "platform": None,
              "timed_out_phase": None, "failed_phase": None}
    for attempt in range(PREFLIGHT_TRIES):
        report["attempts"] = attempt + 1
        timed_out = False
        try:
            p = subprocess.run(
                [sys.executable, "-c", _PREFLIGHT_CODE],
                timeout=PREFLIGHT_TIMEOUT_S, capture_output=True, text=True,
            )
            stdout = p.stdout or ""
        except subprocess.TimeoutExpired as e:
            timed_out = True
            stdout = e.stdout or b""
            if isinstance(stdout, bytes):
                stdout = stdout.decode("utf-8", "replace")
        phases, result = {}, None
        for ln in stdout.splitlines():
            try:
                obj = json.loads(ln)
            except ValueError:
                continue
            if isinstance(obj, dict) and "phase" in obj:
                phases[obj["phase"]] = obj.get("ms")
                report["platform"] = obj.get("platform",
                                             report["platform"])
                result = obj.get("result", result)
        report["phases_ms"] = phases
        if all(ph in phases for ph in PREFLIGHT_PHASES) and result == 256.0:
            report.update(ok=True, timed_out_phase=None, failed_phase=None)
            return report
        # the phase the child died in: the first that never reported done
        hung = next((ph for ph in PREFLIGHT_PHASES if ph not in phases),
                    PREFLIGHT_PHASES[-1])
        report["timed_out_phase" if timed_out else "failed_phase"] = hung
        done = [ph for ph in PREFLIGHT_PHASES if ph in phases]
        print(f"[bench] pre-flight attempt {attempt + 1}/{PREFLIGHT_TRIES}: "
              f"device {'hung' if timed_out else 'errored'} in phase "
              f"'{hung}' (completed: {', '.join(done) or 'none'})",
              file=sys.stderr)
        if attempt + 1 < PREFLIGHT_TRIES:
            time.sleep(PREFLIGHT_SLEEP_S)
    return report


def _run_child(argv, timeout_s, env_extra=None):
    """Run a bench child; return its parsed last JSON stdout line or None."""
    env = dict(os.environ)
    env.update(env_extra or {})
    try:
        p = subprocess.run(
            argv, timeout=timeout_s, capture_output=True, text=True,
            env=env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        print(f"[bench] child {argv[1]} timed out after {timeout_s:.0f}s",
              file=sys.stderr)
        return None
    sys.stderr.write(p.stderr[-2000:])
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
            if isinstance(obj, dict) and "metric" in obj:
                return obj
        except ValueError:
            continue
    print(f"[bench] child {argv[1]} exited rc={p.returncode} with no "
          f"JSON line", file=sys.stderr)
    return None


def main():
    t_start = time.monotonic()

    def remaining():
        return DEADLINE_S - (time.monotonic() - t_start)

    # the probe runs even forced-CPU (it probes the CPU backend then):
    # every bench line carries per-phase pre-flight timing
    preflight = _preflight_probe()

    if os.environ.get("DTX_BENCH_FORCE_CPU"):
        # the CPU, asked for by name: a correctness-and-counts smoke whose
        # line is marked so it can never read as a device result
        line = _run_child(
            [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
            timeout_s=max(remaining() - 10, 60),
        )
        if line is None:
            print("[bench] CPU smoke child failed", file=sys.stderr)
            return 1
        line["cpu_fallback"] = True
        line["vs_baseline"] = None
        line["preflight"] = preflight
        print(json.dumps(line), flush=True)
        return 0

    if not (preflight["ok"] and preflight.get("platform") == "tpu"):
        print("[bench] no TPU: pre-flight "
              f"{json.dumps(preflight, sort_keys=True)} — refusing to print "
              "a CPU line (set DTX_BENCH_FORCE_CPU=1 for the marked CPU "
              "smoke)", file=sys.stderr)
        return 1

    # --- TPU path: tinyllama (continuity) then 7B QLoRA (the north star) ---
    tiny = _run_child(
        [sys.executable, os.path.join(REPO, "bench.py"), "--child"],
        timeout_s=min(max(remaining() * 0.45, 120), 300),
    )

    seven = None
    if remaining() > 150:
        seven = _run_child(
            [sys.executable, os.path.join(REPO, "scripts", "bench_7b.py"),
             "--steps", os.environ.get("DTX_BENCH_7B_STEPS", "10")],
            timeout_s=remaining() - 20,
        )
        if seven is not None:
            # vs_baseline for the artifact = speedup over round-2's recorded
            # 709 tok/s/chip (bench_7b.py itself reports MFU there)
            seven = dict(seven)
            seven["mfu"] = seven.get("vs_baseline")
            seven["vs_baseline"] = round(
                float(seven["value"]) / ROUND2_7B_TOKS, 3)
    else:
        print("[bench] skipping 7B line: insufficient budget left "
              f"({remaining():.0f}s)", file=sys.stderr)

    headline = seven or tiny
    if headline is None:
        print("[bench] the device passed pre-flight but no measurement "
              "child produced a line", file=sys.stderr)
        return 1
    out = dict(headline)
    if headline is seven and tiny is not None:
        out["secondary"] = tiny
    out["preflight"] = preflight
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if os.environ.get("DTX_BENCH_REPLAY"):
        # replay mode: loadgen harness against an in-process fleet, with
        # the same per-phase pre-flight diagnosis on its line
        child_replay(preflight=_preflight_probe())
    elif os.environ.get("DTX_BENCH_DISAGG"):
        # disaggregated-serving twin bench (uniform vs role-split fleet
        # at equal chips) with the same per-phase pre-flight diagnosis
        child_disagg(preflight=_preflight_probe())
    elif os.environ.get("DTX_BENCH_TENANT"):
        # multi-tenant QoS twin bench (tenancy plane on vs off over the
        # same two-tenant mix) with the same pre-flight diagnosis
        child_tenant(preflight=_preflight_probe())
    elif os.environ.get("DTX_BENCH_SERVE_CAPACITY"):
        # KV-overcommit capacity twin bench (eager reserve vs overcommit
        # over one block budget) with the same pre-flight diagnosis
        child_serve_capacity(preflight=_preflight_probe())
    elif os.environ.get("DTX_BENCH_SERVE_SPEC"):
        # speculative-decoding twin-engine serve bench (spec-on vs spec-off,
        # aligned + adversarial) with the same pre-flight diagnosis
        child_serve_spec(preflight=_preflight_probe())
    elif os.environ.get("DTX_BENCH_SERVE"):
        # serve mode is its own entry (no orchestrator): probe first so the
        # serve line carries the same per-phase pre-flight diagnosis
        child_serve(preflight=_preflight_probe())
    elif "--child" in sys.argv:
        child_tinyllama()
    else:
        sys.exit(main())
