"""Training traffic: instruction/response documents of heavy-tailed length,
written to a CSV from the seed, read back through the program's own loader
(tokenise, pack into full rows, prefetch, place) and trained on by the
program's own step. Parameters: ``traffic/<name>.json`` (lengths, rows and
tokens per step, prefetch depth); the trainer's settings are the cell's.

Set-up builds ONE object, the compiled step with its state, drives it through
its first steps on the feed's first batches, and hands that same step, state
and feed to the window. The plain reference follows those first steps after
the window has closed.
"""

from __future__ import annotations

import csv
import itertools
import os
import shutil
import tempfile
import time

import numpy as np

import draws
import flops
import weights
from common import Observed, log, peak_memory_bytes, tracing

WINDOW_SPAN = "bench_window"
TRACE_STEPS = 3
IGNORE = -100


def template_overhead(template: str) -> int:
    """Tokens the template and the specials add to a document (byte tokenizer:
    one token a byte). ``llama2`` adds 545, its default system prompt, which
    leaves one document to a row; ``vanilla`` adds 2."""
    from datatunerx_tpu.data.preprocess import encode_supervised_example
    from datatunerx_tpu.data.templates import get_template
    from datatunerx_tpu.utils.simple_tokenizer import SimpleTokenizer

    tok = SimpleTokenizer()
    ids, _ = encode_supervised_example(get_template(template, tok), tok, "qq", "aa",
                                       history=None, system=None, cutoff_len=1 << 20)
    return len(ids) - 4


def write_csv(path: str, traffic: dict, seed: int) -> int:
    """Documents whose TOKEN counts (one per byte, plus the template's few) are
    the fixed quantiles of the length distribution; the seed fills them."""
    spec = traffic["doc_tokens"]
    overhead = template_overhead(traffic.get("template", "vanilla"))
    need = int(traffic["steps_of_data"]) * int(traffic["rows_per_step"]) * int(traffic["block_size"])
    probe = draws.pareto_quantiles(4096, spec).mean()
    n = int(need / probe * 1.1) + 1
    rng = draws.rng_for(seed, 5)
    lengths = rng.permutation(draws.pareto_quantiles(n, spec))
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz      etaoin", np.uint8)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["instruction", "response"])
        for total in lengths:
            body = max(4, int(total) - overhead)
            text = alphabet[rng.integers(0, len(alphabet), size=body)].tobytes().decode()
            cut = max(1, body // 3)
            w.writerow([text[:cut], text[cut:]])
    return n


def build_feed(cell, seed: int, mesh, stats, first_batches: list, token_counts: list):
    """The program's input pipeline over the seed's CSV: an endless stream of
    placed batches, epoch after epoch, and the host prefetcher to close."""
    from datatunerx_tpu.data.loader import BatchIterator, CsvDataset
    from datatunerx_tpu.data.prefetch import prefetch_batches
    from datatunerx_tpu.parallel.sharding import place_batch
    from datatunerx_tpu.utils.simple_tokenizer import SimpleTokenizer

    t = cell.traffic
    work = tempfile.mkdtemp(prefix="bench_docs_")
    try:
        path = os.path.join(work, "train.csv")
        n_docs = write_csv(path, t, seed)
        tok = SimpleTokenizer()
        examples = CsvDataset(path).encode(t.get("template", "llama2"), tok,
                                           cutoff_len=int(t["block_size"]))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    it = BatchIterator(examples, global_batch=int(t["rows_per_step"]),
                       block_size=int(t["block_size"]), pad_id=tok.pad_token_id or 0,
                       seed=int(t["schedule_seed"]), pack=True)  # the seed fills the rows, not orders them
    if it.steps_per_epoch() < 4:
        raise RuntimeError(f"{n_docs} documents pack into fewer than 4 steps")

    def source():
        for epoch in itertools.count():
            for batch in it.epoch(epoch):
                if len(first_batches) < n_first:
                    first_batches.append({k: np.array(v) for k, v in batch.items()})
                token_counts.append(int(np.sum(batch["attention_mask"] != 0)))
                yield batch

    n_first = int(cell.workload["check"]["steps"])
    batches, host_pf = prefetch_batches(
        source, place_fn=lambda b: place_batch(b, mesh), depth=int(t["prefetch_depth"]),
        stats=stats)
    return batches, host_pf


def find_adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever the chain put it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            mu = find_adam_mu(part)
            if mu is not None:
                return mu
    return None


def leaf_norms(tree) -> dict:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
            for p, x in flat}


def diff_rel(got, ref) -> float:
    """||got - ref|| / ||ref|| over all leaves together: the direction of the
    gradient, which unbiased rounding noise moves and a norm does not show."""
    import jax

    num = den = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        num += float(np.sum((g - r) ** 2))
        den += float(np.sum(r ** 2))
    return float(np.sqrt(num / max(den, 1e-300)))


def worst_leaf_gap(got: dict, ref: dict) -> float:
    """Largest |norm_program - norm_reference| over leaves, against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (a LoRA A-gradient is exactly zero while B is zero)."""
    med = float(np.median(list(ref.values())))
    return max(abs(got[k] - ref[k]) / max(ref[k], med, 1e-30) for k in ref)


def reference_steps(cell, params, lora0, batches: list, precision="f32"):
    """Follow the first steps in the plain reference: each step's loss, the
    first clipped gradient's leaf norms, the leaf norms of the parameters'
    change after the last step."""
    import jax
    import jax.numpy as jnp
    from reference import decoder

    tr = cell.workload["train"]
    mc = cell.model_fields
    scale = float(tr["lora_alpha"]) / float(tr["lora_rank"])
    lora0 = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), lora0)
    p = lora0
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first = [], None
    for step, batch in enumerate(batches):
        loss, g = decoder.loss_and_grads(params, mc, p, batch, scale, precision, IGNORE)
        g = decoder.clip_by_global_norm(g, float(tr["max_grad_norm"]))
        if first is None:
            first, first_tree = leaf_norms(g), jax.device_get(g)
        lr = decoder.cosine_lr(step, float(tr["learning_rate"]), int(tr["total_steps"]))
        p, m, v = decoder.adamw_step(p, g, m, v, step, lr=lr,
                                     weight_decay=float(tr["weight_decay"]))
        losses.append(float(loss))
    delta = jax.tree_util.tree_map(lambda a, b: a - b.astype(jnp.float32), p, lora0)
    return {"losses": losses, "first_grad": first, "first_grad_tree": first_tree,
            "update": leaf_norms(delta)}


def compare(got: dict, ref: dict) -> dict:
    """The numbers ``correct`` is decided by, program (or control) against reference."""
    return {"loss_rel": max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])),
            "grad_leaf_rel": worst_leaf_gap(got["first_grad"], ref["first_grad"]),
            "grad_diff_rel": diff_rel(got["first_grad_tree"], ref["first_grad_tree"]),
            "update_leaf_rel": worst_leaf_gap(got["update"], ref["update"])}


def setup(ctx) -> dict:
    """Build the one object the run is about (trainer, its compiled step, its
    state, its feed) and drive it through its first steps on the feed's first
    batches. Returns everything the window and the reference need."""
    import jax
    import jax.numpy as jnp
    import spec as spec_mod
    from datatunerx_tpu.data.prefetch import PipelineStats
    from datatunerx_tpu.parallel.mesh import make_mesh
    from datatunerx_tpu.parallel.sharding import shard_tree
    from datatunerx_tpu.training import TrainConfig, Trainer

    cell = ctx.cell
    tr = dict(cell.workload["train"])
    cfg = spec_mod.register_preset(cell, remat=tr.pop("remat"), attention_impl=tr.pop("attention"))
    mc = cell.model_fields
    tr["lora_targets"] = tuple(tr["lora_targets"])
    tcfg = TrainConfig(compute_dtype=jnp.bfloat16, **tr)
    mesh = make_mesh(devices=jax.devices()[: cell.chips])
    trainer = Trainer(cfg, tcfg, mesh=mesh)

    t0 = time.perf_counter()
    params = weights.draw_params(mc, ctx.seed)
    lora0 = weights.draw_lora(mc, ctx.seed, count=1, rank=tcfg.lora_rank,
                              targets=tcfg.lora_targets, b_std=0.0)
    lora0 = {name: {"a": ab["a"][0], "b": ab["b"][0]} for name, ab in lora0.items()}
    state = trainer.init_state(params, jax.random.PRNGKey(int(ctx.seed) % (2**31 - 1)))
    # the trainable start is the benchmark's draw (A as PEFT draws it, B zero),
    # so that the reference is handed the same arrays and nothing the program made
    state = state.replace(lora=shard_tree({"layers": lora0}, mesh))
    lora0_host = jax.device_get(lora0)

    stats = PipelineStats()
    first_batches: list = []
    token_counts: list = []  # non-padding tokens of every batch the loader made, in order
    batches, host_pf = build_feed(cell, ctx.seed, mesh, stats, first_batches, token_counts)
    t1 = time.perf_counter()
    n_check = int(cell.workload["check"]["steps"])
    got = {"losses": []}
    try:
        for i in range(n_check):  # through the window's own call and feed
            state, metrics = trainer.train_step(state, next(batches))
            got["losses"].append(float(metrics["loss"]))
            if i == 0:
                mu = jax.device_get(find_adam_mu(state.opt_state))
                # Adam's first moment after one step is (1 - b1) x the gradient it was given
                got["first_grad_tree"] = jax.tree_util.tree_map(lambda x: x / (1 - 0.9), mu["layers"])
                got["first_grad"] = leaf_norms(got["first_grad_tree"])
        after = jax.device_get(state.lora["layers"])
    except BaseException:
        host_pf.close()
        raise
    got["update"] = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64), after, lora0_host))
    log(f"[bench] weights and feed in {t1 - t0:.1f} s, first {n_check} steps in "
        f"{time.perf_counter() - t1:.1f} s, losses {got['losses']}")
    # the step donates its state, and the trainable start went into it: the
    # reference gets the copy that was read back before the first step
    return {"trainer": trainer, "state": state, "batches": batches, "host_pf": host_pf,
            "stats": stats, "got": got, "first_batches": first_batches,
            "token_counts": token_counts, "lora0": lora0_host, "mc": mc, "n_check": n_check}


def run(ctx) -> dict:
    import jax
    import spec as spec_mod

    cell, t = ctx.cell, ctx.cell.traffic
    s = setup(ctx)
    trainer, state, batches, host_pf = s["trainer"], s["state"], s["batches"], s["host_pf"]
    stats, got, n_check, mc = s["stats"], s["got"], s["n_check"], s["mc"]
    tokens_per_step = int(t["rows_per_step"]) * int(t["block_size"])
    try:
        stats.snapshot(reset=True)
        ctx.mark_window_start()
        w0 = time.perf_counter()
        trace_cm = None
        if ctx.trace:
            trace_cm = tracing(ctx.trace_dir, WINDOW_SPAN)
            trace_cm.__enter__()
        done_at, losses, pending = [], [], None
        steps = 0
        while True:
            with jax.profiler.TraceAnnotation("bench_next_batch"):
                batch = next(batches)
            state, metrics = trainer.train_step(state, batch)
            if pending is not None:  # one step of lag: wait for the previous step
                with jax.profiler.TraceAnnotation("bench_wait_step"):
                    losses.append(float(pending["loss"]))
                done_at.append(time.perf_counter())
                if trace_cm is not None and len(done_at) >= TRACE_STEPS:
                    trace_cm.__exit__(None, None, None)
                    trace_cm = None
                    trace_end = done_at[-1]
            pending = metrics
            steps += 1
            if time.perf_counter() - w0 >= ctx.seconds:
                break
        losses.append(float(pending["loss"]))
        done_at.append(time.perf_counter())
        if trace_cm is not None:
            trace_cm.__exit__(None, None, None)
            trace_end = done_at[-1]
        elapsed = done_at[-1] - w0
        compiles = ctx.compiles_in_window()
        mem = peak_memory_bytes()
        pipe = stats.snapshot(reset=False)
    finally:
        host_pf.close()

    # non-padding tokens of exactly the batches the window trained on
    window_tokens = sum(s["token_counts"][n_check:n_check + steps])
    trained = window_tokens / steps
    step_ms = np.diff([w0] + done_at) * 1e3
    rate = window_tokens / elapsed / cell.chips
    peaks = None if ctx.on_cpu else spec_mod.peaks_for(ctx.device["kind"])
    need = flops.train_flops_per_token_lora(mc, int(t["block_size"]))
    if peaks:
        log(f"[bench] model FLOP/s utilization {rate * need / peaks['bf16_flops'] * 100:.2f}% "
            f"of {peaks['bf16_flops']:.3g} (required operations, recomputation not counted)")

    t0 = time.perf_counter()
    ref = reference_steps(cell, state.params, s["lora0"], s["first_batches"][:n_check])
    log(f"[bench] reference followed {n_check} steps in {time.perf_counter() - t0:.1f} s, "
        f"losses {ref['losses']}")
    limits = cell.workload["check"]["limits"]
    numbers = compare(got, ref)
    checks = [("compiles_in_window", compiles, 0, "max"),
              ("losses_finite", int(all(np.isfinite(losses))), 1, "min")]
    for key, val in numbers.items():
        if key in limits:
            checks.append((key, val, limits[key], "max"))
    obs = Observed(cell=cell, train={
        "step_ms": step_ms.tolist(), "steps": steps, "tokens_per_step": trained,
        "pipe": pipe, "flops_per_token": need, "elapsed_s": elapsed,
        "trace_steps": TRACE_STEPS, "losses": losses}, peaks=peaks, check=numbers)
    if ctx.trace:
        obs.trace_window = (w0, trace_end)
    reduced = {"steps": steps, "padded_tokens_per_step": tokens_per_step,
               "trained_tokens_per_step": trained}
    if not ctx.on_cpu:
        reduced.update(elapsed_s=elapsed, step_ms_p50=float(np.median(step_ms)))
    return {"attempted": steps, "failed": 0, "checks": checks, "memory_peak_bytes": mem,
            "observed": obs, "reduced": reduced, "kind_metrics": {"train_tok_s": rate}}


def readings(ctx, control: bool = True) -> dict:
    """For setting limits (``calibrate.py``): the first steps of the sound
    program against the reference, and of the int8 control against it."""
    import jax

    s = setup(ctx)
    s["host_pf"].close()
    params, batches = s["state"].params, s["first_batches"][:s["n_check"]]
    ref = reference_steps(ctx.cell, params, s["lora0"], batches)
    out = {"sound": compare(s["got"], ref)}
    if control:
        out["control"] = compare(
            reference_steps(ctx.cell, params, s["lora0"], batches, precision="int8"), ref)
    for leaf in jax.tree_util.tree_leaves(s["state"]):
        leaf.delete()
    return out
