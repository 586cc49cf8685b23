#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the full
width AND depth of ``preset:tinyllama-1.1b`` with seeded random weights:

  trainer  ``python -m datatunerx_tpu.tuning.train`` — LoRA, flash attention,
           remat=dots, B8 x T1024, the prefetch pipeline on, a final
           checkpoint + manifest. Passes when it exits 0, every logged loss is
           finite, the last loss is below the first, and the manifest exists.
  server   ``python -m datatunerx_tpu.serving.server`` — 4 slots, paged KV
           (block 16), prefill budget 256, ``--paged_kernel`` and
           ``--sampling_epilogue`` left at ``auto``. ``/healthz`` must reach
           200; then five ``/chat/completions`` requests (short greedy; a
           prompt longer than two prefill chunks concurrent with a
           temperature-only one; temperature + top_p concurrent with a
           greedy one), each answering 200 with exactly the tokens asked
           for; ``/metrics`` must show the kernel decode path and the fused
           sampler at work.
  kernels  every Pallas kernel compiled by Mosaic (no interpret mode) and
           compared with its in-repo oracle at tinyllama and llama2-7b
           geometry, one PASS line per kernel and geometry.

One process holds the chip at a time: this parent never imports JAX; the
trainer, the server and the kernel check are children run one after another.
Each child prints what it runs on (jax, libtpu, backend, device kind and
count, compile-cache directory) and the smoke fails unless that is a TPU.
Nothing here is a measurement: no rate, no utilization, no peak is printed.

    python chip_smoke.py                       # everything, on the chip
    python chip_smoke.py --mesh dp=1,fsdp=4,tp=1 --phases trainer
    python chip_smoke.py --cpu-rehearsal       # debug size, on the CPU

``--cpu-rehearsal`` exists to debug this script's control flow in a sandbox
without a chip. It proves nothing about the chip and says so in its output;
it is never what the bare command does.

The last line of standard output is ``{"ok": true, "device": {...}}`` with
the device as JAX reported it. On any failure the exit code is non-zero and
no such line is printed. Every phase runs even after one has failed, so one
call reports everything that is wrong. Logs land in ``chiprun_out/chip_smoke/``;
the data and the checkpoints (gigabytes) live in ``.chip_smoke_work/`` and are
removed at the end.
"""

from __future__ import annotations

import argparse
import csv
import functools
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")   # logs: brought back
WORK = os.path.join(REPO, ".chip_smoke_work")  # data + checkpoints: removed
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
SEED = 20260926

MODEL = "preset:tinyllama-1.1b"
TRAIN_STEPS = 10
TRAIN_BATCH = 8       # per device
TRAIN_BLOCK = 1024
SERVE_SLOTS = 4       # --slots default; also the sampler check's S
SERVE_BLOCK = 16      # --kv_block_size of the README and the benchmark cells
SERVE_BUDGET = 256    # = default --prefill_chunk: one chunk per tick
SERVE_SEQ = 1024

# Shapes the kernel phase checks (and scripts/aot_certify.py lowers
# devicelessly): name -> (heads, kv_heads, head_dim, hidden, intermediate)
GEOMETRIES = {
    "tinyllama-1.1b": (32, 4, 64, 2048, 5632),
    "llama2-7b": (32, 32, 128, 4096, 11008),
}
# q_len values the engine really traces the multi-token kernel at: prefill
# chunks are multiples of DECODE_BUCKET=64 up to --prefill_chunk 256; chain
# verify is k+1 for k <= --spec_k 4; a 4x3 tree step is 1 + 12 columns
CHUNK_QLENS = (64, 128, 192, 256)
VERIFY_QLENS = (2, 3, 4, 5, 13)


class SmokeFailure(Exception):
    """A phase failed; the phases after it still run."""


class NoChip(SmokeFailure):
    """A child did not get a TPU: nothing after it can pass either."""


def _say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ children

class Child:
    """One chip-holding subprocess: output to a log file, its own process
    group so a timeout or a failure elsewhere takes everything it started."""

    def __init__(self, name: str, argv: list, env: dict):
        self.name = name
        self.log_path = os.path.join(OUT, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def wait(self, timeout_s: float) -> int:
        try:
            return self.proc.wait(timeout=max(timeout_s, 1.0))
        except subprocess.TimeoutExpired:
            self.stop()
            raise SmokeFailure(
                f"{self.name}: still running after {timeout_s:.0f}s — killed"
                f"\n{self.tail()}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            for sig, grace in ((signal.SIGTERM, 15), (signal.SIGKILL, 5)):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=grace)
                    break
                except subprocess.TimeoutExpired:
                    continue
        self._log.close()

    def lines(self) -> list:
        if not self._log.closed:
            self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read().splitlines()

    def tail(self, n: int = 40) -> str:
        return "\n".join(f"    | {ln}" for ln in self.lines()[-n:])

    def tagged(self, tag: str) -> list:
        """JSON payloads of this child's ``<tag> {...}`` log lines."""
        out = []
        for ln in self.lines():
            if ln.startswith(tag + " "):
                brace = ln.find("{")
                if brace >= 0:
                    try:
                        out.append(json.loads(ln[brace:]))
                    except ValueError:
                        pass
        return out


def _child_env(rehearsal: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _check_runtime(child: Child, tag: str, rehearsal: bool) -> dict:
    """The child's own ``[runtime] <tag> {...}`` line, read as soon as it is
    written (it is each entry point's first act): the child must run on a
    TPU, with real Mosaic kernels, and say where its compile cache lives."""
    deadline = time.monotonic() + 180
    while not (docs := child.tagged(f"[runtime] {tag}")):
        if child.proc.poll() is not None or time.monotonic() > deadline:
            child.stop()
            raise NoChip(f"{child.name}: no '[runtime] {tag}' line (exit "
                         f"code {child.proc.returncode})\n{child.tail()}")
        time.sleep(0.5)
    info = docs[0]
    _say(f"  {child.name}: jax={info['jax']} libtpu={info['libtpu']} "
         f"backend={info['backend']} platform={info['platform']} "
         f"device_kind={info['device_kind']!r} devices={info['count']} "
         f"pallas_interpret={info['pallas_interpret']} "
         f"compile_cache={info['compile_cache']}"
         + (f" native_packer={info['native_packer']}"
            if "native_packer" in info else ""))
    if not rehearsal:
        if info["backend"] != "tpu" or info["platform"] != "tpu":
            child.stop()
            raise NoChip(f"{child.name}: backend is {info['backend']!r}, "
                         "not tpu")
        if info["pallas_interpret"]:
            child.stop()
            raise SmokeFailure(f"{child.name}: Pallas resolved to interpret "
                               "mode on a TPU backend")
    if not info["compile_cache"]:
        child.stop()
        raise SmokeFailure(f"{child.name}: no compile cache directory")
    return info


def _report_cache(child: Child, stats: dict) -> None:
    _say(f"  {child.name}: compile cache dir={stats.get('dir')} "
         f"requests={stats.get('requests')} hits={stats.get('hits')}")


# ------------------------------------------------------------------- trainer

def _write_train_csv(path: str, rows: int) -> None:
    """Seeded instruction/response pairs, each filling most of a 1024-token
    block under the byte-level tokenizer. The text is a few words repeated,
    so even a frozen random base with a rank-8 adapter has something to
    learn inside ten steps."""
    rng = random.Random(SEED)
    words = ["tensor", "mesh", "shard", "adapter", "block", "token", "cache",
             "slot", "kernel", "batch", "prompt", "decode"]
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["instruction", "response"])
        for _ in range(rows):
            key = rng.sample(words, 3)
            w.writerow([" ".join(key * 8), " ".join(key * 36)])


def phase_trainer(rehearsal: bool, mesh: str, budget_s: float) -> dict:
    tag = "trainer" + (f"[{mesh}]" if mesh else "")
    _say(f"== {tag}")
    model, block, steps = ((MODEL, TRAIN_BLOCK, TRAIN_STEPS) if not rehearsal
                           else ("preset:debug", 128, 6))
    slug = mesh.replace("=", "").replace(",", "_") if mesh else "default"
    run = f"run_{slug}"
    data = os.path.join(WORK, "train.csv")
    storage = os.path.join(WORK, "storage")
    # enough rows for ten steps at B8 on every chip of a four-chip host
    _write_train_csv(data, rows=TRAIN_BATCH * 4 * (steps + 2))
    argv = [sys.executable, "-m", "datatunerx_tpu.tuning.train",
            "--model_name_or_path", model, "--train_path", data,
            "--template", "vanilla", "--finetuning_type", "lora",
            "--attention", "flash", "--remat", "dots",
            "--block_size", str(block),
            "--per_device_train_batch_size", str(TRAIN_BATCH),
            "--max_steps", str(steps), "--logging_steps", "1",
            "--learning_rate", "1e-3", "--lr_scheduler_type", "constant",
            "--lora_dropout", "0", "--seed", str(SEED),
            "--output_dir", os.path.join(WORK, run),
            "--storage_path", storage, "--uid", run]
    if mesh:
        argv += ["--mesh", mesh]
    child = Child(f"trainer_{slug}" if mesh else "trainer", argv,
                  _child_env(rehearsal))
    try:
        info = _check_runtime(child, "trainer", rehearsal)
        rc = child.wait(budget_s - (time.monotonic() - child.t0))
    finally:
        child.stop()
    wall = time.monotonic() - child.t0
    if rc != 0:
        raise SmokeFailure(f"{tag}: exited {rc}\n{child.tail()}")

    att = [ln for ln in child.lines() if ln.startswith("[attention] ")]
    for ln in att:
        _say(f"  {child.name}: {ln}")
    if not any(f"traced=flash T={block} " in ln for ln in att):
        raise SmokeFailure(f"{tag}: the train step did not trace flash "
                           f"attention at T={block}\n{child.tail()}")
    for doc in child.tagged("[mesh]"):
        _say(f"  {child.name}: mesh={doc['shape']} devices={doc['devices']}")
        for kind, per_dev in sorted(doc["per_device_bytes"].items()):
            _say(f"  {child.name}:   {kind} bytes per device: "
                 + " ".join(f"{d}:{n}" for d, n in sorted(per_dev.items())))

    recs = child.tagged("[train]")
    losses = [r.get("loss") for r in recs]
    _say(f"  {child.name}: {len(recs)} logged steps, loss "
         + " ".join(f"{x:.4f}" for x in losses if isinstance(x, float)))
    if len(recs) < steps:
        raise SmokeFailure(f"{tag}: {len(recs)} logged steps, wanted {steps}")
    if not all(isinstance(x, float) and math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{tag}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{tag}: loss did not fall: first {losses[0]} "
                           f"last {losses[-1]}")
    manifest = os.path.join(storage, run, "manifest.json")
    done = [ln for ln in child.lines() if ln.startswith("[done] ")]
    if done:
        manifest = done[-1].rsplit("manifest: ", 1)[-1].strip() or manifest
    if not os.path.isfile(manifest):
        raise SmokeFailure(f"{tag}: no completion manifest at {manifest}")
    _say(f"  {child.name}: manifest {os.path.relpath(manifest, REPO)}")
    for stats in child.tagged("[runtime] compile_cache"):
        _report_cache(child, stats)
    _say(f"  {child.name}: PASS in {wall:.0f}s wall")
    return info


# -------------------------------------------------------------------- server

def _http(method: str, url: str, body=None, headers=None,
          timeout: float = 30.0):
    data = json.dumps(body).encode() if body is not None else None
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    req = urllib.request.Request(url, data=data, method=method, headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def _metric(text: str, name: str, labels: str = "") -> float:
    head = name + (("{" + labels + "}") if labels else "")
    for ln in text.splitlines():
        if ln.startswith(head + " "):
            return float(ln.rsplit(" ", 1)[1])
    return float("nan")


def phase_server(rehearsal: bool, budget_s: float) -> dict:
    _say("== server")
    deadline = time.monotonic() + budget_s
    model, seq = (MODEL, SERVE_SEQ) if not rehearsal else ("preset:debug", 256)
    budget = SERVE_BUDGET if not rehearsal else 64
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    argv = [sys.executable, "-m", "datatunerx_tpu.serving.server",
            "--model_path", model, "--template", "vanilla",
            "--max_seq_len", str(seq), "--slots", str(SERVE_SLOTS),
            "--kv_block_size", str(SERVE_BLOCK),
            "--prefill_token_budget", str(budget), "--port", str(port)]
    if rehearsal:
        argv += ["--prefill_chunk", str(budget)]
    base = f"http://127.0.0.1:{port}"
    child = Child("server", argv, _child_env(rehearsal))
    try:
        info = _check_runtime(child, "server", rehearsal)
        # ---- /healthz must reach 200; 500 FAILED or an exit is a failure
        while True:
            if child.proc.poll() is not None:
                raise SmokeFailure(f"server: exited {child.proc.returncode} "
                                   f"before it was healthy\n{child.tail()}")
            if time.monotonic() > deadline:
                raise SmokeFailure("server: not healthy in time"
                                   f"\n{child.tail()}")
            try:
                code, body = _http("GET", base + "/healthz", timeout=5)
            except OSError:
                code, body = 0, ""
            if code == 200:
                health = json.loads(body)
                break
            if code == 500:
                raise SmokeFailure(f"server: /healthz 500 {body}"
                                   f"\n{child.tail()}")
            time.sleep(1.0)
        t_healthy = time.monotonic() - child.t0
        _say(f"  server: /healthz 200 after {t_healthy:.0f}s: {health}")
        for key in ("platform", "device_kind", "count"):
            if health.get(key) != info[key]:
                raise SmokeFailure(f"server: /healthz {key}="
                                   f"{health.get(key)!r} != {info[key]!r}")

        eng = child.tagged("[engine]")
        if not eng:
            raise SmokeFailure(f"server: no [engine] line\n{child.tail()}")
        _say(f"  server: engine resolved {eng[0]}")
        if not rehearsal:
            want = {"decode_path": "pallas", "sampling_epilogue": "on",
                    "epilogue_impl": "kernel", "pallas_interpret": False}
            bad = {k: eng[0].get(k) for k, v in want.items()
                   if eng[0].get(k) != v}
            if bad:
                raise SmokeFailure(f"server: auto resolved to {bad}, "
                                   f"wanted {want}")

        # ---- the requests. Byte-level tokenizer: one character, one token.
        long_chars = 2 * budget + budget // 2  # longer than two chunks
        reqs = {
            "short-greedy": dict(prompt="hello there", max_tokens=16),
            "long-greedy": dict(prompt="a long prompt. " * (long_chars // 15),
                                max_tokens=12),
            "temperature": dict(prompt="sample something", max_tokens=24,
                                temperature=0.8),
            "temperature-top_p": dict(prompt="sample a nucleus",
                                      max_tokens=24, temperature=0.8,
                                      top_p=0.9),
            "greedy-beside-top_p": dict(prompt="second greedy",
                                        max_tokens=16),
        }
        results: dict = {}

        def ask(name: str) -> None:
            spec = dict(reqs[name])
            body = {"messages": [{"role": "user",
                                  "content": spec.pop("prompt")}], **spec}
            left = max(deadline - time.monotonic(), 5.0)
            try:
                results[name] = _http(
                    "POST", base + "/chat/completions", body,
                    headers={"X-DTX-Trace-Id": f"smoke-{name}"},
                    timeout=left)
            except OSError as e:
                results[name] = (0, repr(e))

        for group in (["short-greedy"], ["long-greedy", "temperature"],
                      ["temperature-top_p", "greedy-beside-top_p"]):
            threads = [threading.Thread(target=ask, args=(n,)) for n in group]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=max(deadline - time.monotonic(), 5.0) + 10)
            if any(t.is_alive() for t in threads):
                raise SmokeFailure(f"server: {group} did not answer in time"
                                   f"\n{child.tail()}")

        total = 0
        for name, spec in reqs.items():
            code, body = results.get(name, (0, "no result"))
            if code != 200:
                raise SmokeFailure(f"server: {name} answered {code}: "
                                   f"{body[:300]}\n{child.tail()}")
            tcode, tbody = _http("GET", f"{base}/debug/trace/smoke-{name}")
            if tcode != 200:
                raise SmokeFailure(f"server: no trace for {name}: {tbody}")
            span = json.loads(tbody)
            span = span["spans"][0] if "spans" in span else span
            n_tok = span["attrs"]["n_tokens"]
            chunks = [e["tokens"] for e in span["events"]
                      if e["name"] == "prefill"]
            _say(f"  server: {name}: 200, {n_tok} tokens "
                 f"(asked {spec['max_tokens']}), prefill chunks {chunks}")
            if n_tok != spec["max_tokens"]:
                raise SmokeFailure(f"server: {name} produced {n_tok} tokens, "
                                   f"asked {spec['max_tokens']}")
            if name == "long-greedy" and len(chunks) < 3:
                raise SmokeFailure(f"server: long prompt took {chunks} "
                                   "prefill chunks, wanted more than two")
            total += n_tok

        code, metrics = _http("GET", base + "/metrics")
        if code != 200:
            raise SmokeFailure(f"server: /metrics answered {code}")
        gen = _metric(metrics, "dtx_serving_generated_tokens_total")
        fused = _metric(metrics, "dtx_serving_sampling_fused_steps_total",
                        'path="fused"')
        legacy = _metric(metrics, "dtx_serving_sampling_fused_steps_total",
                         'path="legacy"')
        path = [ln for ln in metrics.splitlines()
                if ln.startswith("dtx_serving_decode_path{")]
        epilogue = _metric(metrics, "dtx_serving_sampling_epilogue")
        _say(f"  server: /metrics generated_tokens={gen:.0f} "
             f"fused_steps={fused:.0f} legacy_steps={legacy:.0f} "
             f"sampling_epilogue={epilogue:.0f} {' '.join(path)}")
        if gen != total:
            raise SmokeFailure(f"server: generated-token counter {gen} != "
                               f"{total} tokens returned")
        if not rehearsal:
            if not fused > 0:
                raise SmokeFailure("server: no decode tick took the fused "
                                   "sampler")
            if path != ['dtx_serving_decode_path{kind="global",path="pallas"} 1']:
                raise SmokeFailure(f"server: decode path is {path}")
            if epilogue != 2:
                raise SmokeFailure("server: sampling epilogue is not the "
                                   f"Pallas kernel ({epilogue})")
        _report_cache(child, {
            "dir": info["compile_cache"],
            "requests": int(_metric(
                metrics, "dtx_serving_compile_cache_requests_total")),
            "hits": int(_metric(
                metrics, "dtx_serving_compile_cache_hits_total"))})
        if child.proc.poll() is not None:
            raise SmokeFailure(f"server: died ({child.proc.returncode})"
                               f"\n{child.tail()}")
    finally:
        child.stop()
    _say(f"  server: PASS in {time.monotonic() - child.t0:.0f}s wall "
         f"(healthy after {t_healthy:.0f}s)")
    return info


# ------------------------------------------------------------------- kernels

def phase_kernels(rehearsal: bool, budget_s: float, name: str = "kernels") -> dict:
    """A child of this script that prints PASS/FAIL verdicts: ``kernels``
    (every Pallas kernel against its oracle) or ``hybrid`` (a model of several
    layer kinds served by the batched engine, against the plain reference)."""
    _say(f"== {name}")
    argv = [sys.executable, os.path.abspath(__file__), f"--child-{name}"]
    if rehearsal:
        argv.append("--cpu-rehearsal")
    child = Child(name, argv, _child_env(rehearsal))
    try:
        info = _check_runtime(child, name, rehearsal)
        rc = child.wait(budget_s - (time.monotonic() - child.t0))
    finally:
        child.stop()
    verdicts = [ln for ln in child.lines()
                if ln.startswith(("PASS ", "FAIL "))]
    for ln in verdicts:
        _say(f"  {ln}")
    for stats in child.tagged("[runtime] compile_cache"):
        _report_cache(child, stats)
    failed = [ln for ln in verdicts if ln.startswith("FAIL ")]
    if rc != 0 or failed or not verdicts:
        raise SmokeFailure(f"{name}: exited {rc}, {len(failed)} FAIL of "
                           f"{len(verdicts)}\n{child.tail()}")
    _say(f"  {name}: PASS {len(verdicts)}/{len(verdicts)} in "
         f"{time.monotonic() - child.t0:.0f}s wall")
    return info


def _served_gaps(eng, reference, work_items, verdict, tag):
    """How far each served greedy token's logit lies below the plain float32
    reference's best, over each request's own full forward; ``work_items`` is
    [(prompt, adapter name, request)]."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    mc = dataclasses.asdict(eng.cfg)
    gaps = []
    for prompt, name, req in work_items:
        if not req.done.wait(900) or req.error:
            verdict(f"{tag}/serve[{name or 'base'}]", False, str(req.error))
            continue
        tokens = list(prompt) + list(req.tokens)
        rows = list(range(len(prompt) - 1, len(tokens) - 1))
        lora = None
        if name:
            e = eng.adapter_ids[name]
            lora = jax.tree_util.tree_map(lambda a: a[:, e], eng.lora_stack[0]["layers"])
        ref = reference.sequence_logits(
            eng.params, mc, tokens, rows, lora,
            float(eng.lora_stack[1][eng.adapter_ids[name]]) if name else 0.0)
        got = jnp.take_along_axis(ref, jnp.asarray(req.tokens)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(ref, axis=-1) - got))
    return np.concatenate(gaps) if gaps else np.asarray([np.inf])


def child_hybrid(rehearsal: bool) -> int:
    """Runs IN the chip-holding child: the batched engine on the debug preset
    of a model with window and global attention layers and sparse experts
    (``preset:debug-hybrid``), two adapters, paged pool. Served greedy tokens
    are held against the plain float32 reference (benchmarks/reference/
    mimo_v2.py, which imports nothing of the program) as logits. Its heads
    are widened to 2 x 128 (q/k) and 2 x 64 (v), whole lane tiles a pool row
    as every published model's are: the chip's compiler can then cut blocks
    out of the pools, and the global kind's token step takes the paged decode
    kernel while the window kind, with its sink, reads its gathered view."""
    import dataclasses
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from reference import mimo_v2 as reference

    from datatunerx_tpu.models.config import PRESETS
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine
    from datatunerx_tpu.utils import runtime

    runtime.startup("hybrid")
    preset = "debug-hybrid-tiles"
    PRESETS[preset] = dataclasses.replace(
        PRESETS["debug-hybrid"], name=preset, num_kv_heads=2, head_dim=128,
        v_head_dim=64)
    ok = True

    def verdict(name, passed, detail):
        nonlocal ok
        ok &= bool(passed)
        print(f"{'PASS' if passed else 'FAIL'} {name} {detail}", flush=True)

    work = tempfile.mkdtemp(prefix="smoke_hybrid_")
    adapters = {f"ad{i}": make_adapter_checkpoint(
        f"{work}/ad{i}", "preset:" + preset, seed=20 + i, rank=4) for i in range(2)}
    eng = BatchedEngine("preset:" + preset, adapters=adapters, slots=4, decode_chunk=4,
                        kv_block_size=8, kv_blocks=96, max_seq_len=256, prefill_chunk=64,
                        paged_kernel="on" if rehearsal else "auto")
    try:
        verdict("hybrid/decode_paths",
                eng.decode_paths == {"global": "pallas", "window": "gather"},
                json.dumps(eng.decode_paths))
        rng = np.random.default_rng(1)
        work_items = []
        for name in ("", "ad0", "ad1", "ad0"):
            prompt = rng.integers(10, 3000, size=int(rng.integers(40, 160))).tolist()
            work_items.append((prompt, name, eng.submit(prompt, max_new_tokens=24, adapter=name)))
        gaps = _served_gaps(eng, reference, work_items, verdict, "hybrid")
        # bf16 program against the float32 reference at debug widths: a served
        # token may trail the reference's best by rounding, never by a logit
        verdict("hybrid/served_vs_reference", float(gaps.max()) <= 0.05,
                f"gap_max {gaps.max():.4f} gap_mean {gaps.mean():.5f} tokens {gaps.size}")
        stats = eng.moe_stats
        verdict("hybrid/expert_counters",
                stats["decode_local_rows"] > 0 and stats["prefill_local_rows"] > 0
                and 0 < stats["decode_experts_hit"] <= 4 * stats["decode_layer_steps"],
                json.dumps(stats))
    finally:
        eng.close()
        shutil.rmtree(work, ignore_errors=True)
    return 0 if ok else 1


def child_ling(rehearsal: bool) -> int:
    """Runs IN the chip-holding child: a model whose mixers are linear
    attention (a recurrent state per slot) and latent attention (one pool of
    latent rows), with group-limited routing and a shared expert. First
    ``preset:debug-ling`` with two adapters on ``q_proj`` / ``o_proj``, six
    requests over three slots so that every slot is used twice; then (not in
    the CPU rehearsal) the benchmark's configuration at its cell's engine
    settings for ONE request of two prefill chunks. Served greedy tokens are
    held against benchmarks/reference/ling_v3.py as logits."""
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import spec
    from reference import ling_v3 as reference

    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine
    from datatunerx_tpu.utils import runtime

    runtime.startup("ling")
    ok = True

    def verdict(name, passed, detail):
        nonlocal ok
        ok &= bool(passed)
        print(f"{'PASS' if passed else 'FAIL'} {name} {detail}", flush=True)

    work = tempfile.mkdtemp(prefix="smoke_ling_")
    adapters = {f"ad{i}": make_adapter_checkpoint(
        f"{work}/ad{i}", "preset:debug-ling", seed=20 + i, rank=4,
        targets=("q_proj", "o_proj")) for i in range(2)}
    eng = BatchedEngine("preset:debug-ling", adapters=adapters, slots=3, decode_chunk=4,
                        kv_block_size=8, kv_blocks=96, max_seq_len=256, prefill_chunk=64)
    try:
        rng = np.random.default_rng(1)
        work_items = []
        for n, name in ((5, ""), (70, "ad0"), (130, "ad1"), (33, "ad0"), (90, ""), (64, "ad1")):
            prompt = rng.integers(10, 500, size=n).tolist()
            work_items.append((prompt, name, eng.submit(prompt, max_new_tokens=24, adapter=name)))
        gaps = _served_gaps(eng, reference, work_items, verdict, "ling")
        verdict("ling/served_vs_reference", float(gaps.max()) <= 0.05,
                f"gap_max {gaps.max():.4f} gap_mean {gaps.mean():.5f} tokens {gaps.size}")
        stats = eng.moe_stats
        verdict("ling/counters",
                0 < stats["decode_rows_here"] <= stats["decode_rows"]
                and stats["decode_local_rows"] >= stats["decode_rows_here"]
                and eng.state_bytes() == 4 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 2),
                json.dumps(dict(stats, state_bytes=eng.state_bytes())))
    finally:
        eng.close()
        shutil.rmtree(work, ignore_errors=True)
    if rehearsal or not ok:
        return 0 if ok else 1

    import jax

    cell = spec.load_cell("ling-serve-decode")
    spec.register_preset(cell)
    t0 = time.monotonic()
    eng = BatchedEngine(f"preset:{cell.config_name}", **cell.workload["engine"])
    try:
        built = time.monotonic() - t0
        prompt = rng.integers(10, eng.cfg.vocab_size, size=300).tolist()
        req = eng.submit(prompt, max_new_tokens=40)
        gaps = _served_gaps(eng, reference, [(prompt, "", req)], verdict, "ling/cell")
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
        # the cell's own limits (benchmarks/workloads/ling-serve-decode.json)
        limits = cell.workload["check"]["limits"]
        verdict("ling/cell/served_vs_reference",
                float(gaps.max()) <= limits["gap_max"] and float(gaps.mean()) <= limits["gap_mean"],
                f"gap_max {gaps.max():.4f} gap_mean {gaps.mean():.5f} tokens {gaps.size} "
                f"engine built in {built:.0f}s, request and reference in "
                f"{time.monotonic() - t0 - built:.0f}s, state_bytes {eng.state_bytes()} "
                f"peak_bytes_in_use {peak}")
        # what the expert layers of the cell's two programs run (ops/moe.py)
        verdict("ling/cell/moe_kernel",
                eng.moe_kernel == {"decode": ("dtx_moe_gmm", 16),
                                   "prefill": ("dtx_moe_gmm", 32)},
                json.dumps(eng.moe_kernel))
    finally:
        eng.close()
    return 0 if ok else 1


def child_glm(rehearsal: bool) -> int:
    """Runs IN the chip-holding child: a model whose queries SELECT the cached
    tokens they read (latent attention with a learned indexer, ops/dsa.py).
    First ``preset:debug-glm`` with two adapters on ``q_b_proj`` / ``o_proj``,
    eight requests over three slots, the last a prompt of 250 tokens whose
    chunks cross every step of 32 lanes of its view (ops/mla.py:view_steps) up
    to 256 of the table's 288; then (not in the CPU rehearsal), at the
    published widths: the selection's own ops on scores with ties at the cell's
    shapes against the reference's stable sort, EXACTLY; and the benchmark's
    configuration cut to its first two layers, one slot through a paged cache
    (prefill in chunks of 256 to 3,072 tokens, then eight token steps), the
    sets each position selects against benchmarks/reference/glm_5.py's: equal
    where the context is within ``index_topk`` (every visible token), and
    shared but for the picks bf16 and float32 order otherwise at the cut."""
    import dataclasses
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import spec
    from reference import glm_5 as reference

    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine
    from datatunerx_tpu.utils import runtime

    runtime.startup("glm")
    ok = True

    def verdict(name, passed, detail):
        nonlocal ok
        ok &= bool(passed)
        print(f"{'PASS' if passed else 'FAIL'} {name} {detail}", flush=True)

    work = tempfile.mkdtemp(prefix="smoke_glm_")
    adapters = {f"ad{i}": make_adapter_checkpoint(
        f"{work}/ad{i}", "preset:debug-glm", seed=20 + i, rank=4,
        targets=("q_b_proj", "o_proj")) for i in range(2)}
    eng = BatchedEngine("preset:debug-glm", adapters=adapters, slots=3, decode_chunk=4,
                        kv_block_size=8, kv_blocks=96, max_seq_len=288, prefill_chunk=64)
    try:
        rng = np.random.default_rng(1)
        work_items = []
        for n, name in ((5, ""), (70, "ad0"), (130, "ad1"), (33, "ad0"), (90, ""), (64, "ad1"), (160, ""),
                        (250, "ad1")):
            prompt = rng.integers(10, 500, size=n).tolist()
            work_items.append((prompt, name, eng.submit(prompt, max_new_tokens=24, adapter=name)))
        gaps = _served_gaps(eng, reference, work_items, verdict, "glm")
        # 32 of up to 274 tokens selected: one pick that bf16 orders otherwise is a
        # thirtieth of a row's attention (the CPU reads up to 0.067 on a whole forward)
        verdict("glm/served_vs_reference", float(gaps.max()) <= 0.1,
                f"gap_max {gaps.max():.4f} gap_mean {gaps.mean():.5f} tokens {gaps.size}")
        stats = eng.dsa_stats
        verdict("glm/counters",
                stats["decode_rows"] == 8 * 24 and stats["decode_selected"] <= 32 * stats["decode_rows"]
                and stats["decode_context"] > stats["decode_selected"] > 0
                # 17 chunks of 64 under a table of 288 lanes, each viewing as far as it reaches:
                # 64 (eight first chunks), 128 (five), 192 (three), 256 (the long prompt's last)
                and stats["prefill_table_lanes"] == 17 * 288
                and stats["prefill_view_lanes"] == 8 * 64 + 5 * 128 + 3 * 192 + 256
                and eng.index_pool_bytes() == 5 * 96 * 8 * 16 * 2,
                json.dumps(dict(stats, index_pool_bytes=eng.index_pool_bytes())))
    finally:
        eng.close()
        shutil.rmtree(work, ignore_errors=True)
    if rehearsal or not ok:
        return 0 if ok else 1
    _glm_cell_check(verdict, "glm-serve-docs")
    return 0 if ok else 1


def _glm_cell_check(verdict, cell_name: str) -> None:
    """The second half of ``child_glm``, at the widths of ``cell_name``'s
    configuration (``K`` its ``index_topk``: 2,048 in the benchmark's cell)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    import spec
    from reference import glm_5 as reference

    from datatunerx_tpu.models import forward
    from datatunerx_tpu.models import hybrid
    from datatunerx_tpu.ops import dsa, mla
    from datatunerx_tpu.ops.paged_attention import init_paged_cache

    cell = spec.load_cell(cell_name)
    cfg = dataclasses.replace(spec.register_preset(cell), num_layers=2, layer_types=("mla",) * 2,
                              ffn_types=("dense", "experts"))
    K = cfg.index_topk
    # the selection's ops at the cell's shapes (a view 4.25 times the selection), scores of few
    # distinct values: ties at every cut
    S = 17 * K // 4
    for tokens, slots, seen in ((256, 1, 11 * K // 4), (1, 16, 23 * K // 8), (1, 16, 3 * K // 4)):
        scores = jnp.round(jax.random.normal(jax.random.PRNGKey(tokens), (slots, tokens, S)) * 2) / 2
        visible = jnp.broadcast_to(jnp.arange(S)[None, None, :] < seen, scores.shape)
        lanes, real = jax.jit(lambda a, b: dsa.top_lanes(a, b, K))(scores, visible)
        mask = jax.jit(lambda a, b: dsa.top_mask(a, b, K))(scores, visible)
        want = jnp.stack([reference.select(scores[i], visible[i], K) for i in range(slots)])
        picked = np.zeros(scores.shape, bool)
        np.put_along_axis(picked, np.asarray(lanes), np.asarray(real), axis=-1)
        verdict(f"glm/select[{tokens}x{slots}, {seen} of {S} visible]",
                bool(jnp.all(mask == want)) and bool((picked == np.asarray(want)).all())
                and int(want.sum()) == slots * tokens * min(K, seen),
                f"selected {int(mask.sum())} = {slots * tokens} rows x {min(K, seen)}")

    # the published widths, the configuration's first two layers, through a paged cache
    mc = dict(cell.model_fields, num_layers=2, layer_types=["mla"] * 2, ffn_types=["dense", "experts"])
    weights = spec.load_module(cell.config["weights_module"])
    params = weights.draw_params(mc, 4100000001)
    T, steps, bs = 3 * K // 2, 8, 16
    blocks = -(-(T + 256) // bs)
    toks = np.random.default_rng(2).integers(10, cfg.vocab_size, size=T + steps).tolist()
    seen = []  # every selection as a mask [1, T, lanes], whichever form took it
    real_lanes, real_mask = dsa.top_lanes, dsa.top_mask

    def note_lanes(lanes, real, width):
        mask = np.zeros(lanes.shape[:2] + (int(width),), bool)
        np.put_along_axis(mask, np.asarray(lanes), np.asarray(real), axis=-1)
        seen.append(mask)

    def spy_lanes(scores, visible, k):
        lanes, real = real_lanes(scores, visible, k)
        jax.debug.callback(note_lanes, lanes, real, scores.shape[-1])
        return lanes, real

    def spy_mask(scores, visible, k):
        mask = real_mask(scores, visible, k)
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), mask)
        return mask

    read, real_attention = [], hybrid.xla_attention  # the lanes each layer's chunk attention read

    def spy_attention(q, k, v, bias, **kw):
        if q.shape[1] > 1:  # of the branches of static width, the one taken speaks
            jax.debug.callback(lambda _: read.append(k.shape[1]), q[0, 0, 0, 0])
        return real_attention(q, k, v, bias, **kw)

    dsa.top_lanes, dsa.top_mask, hybrid.xla_attention = spy_lanes, spy_mask, spy_attention
    step = jax.jit(lambda p, ids, cache, pos: forward(p, ids, cfg, cache=cache, positions=pos,
                                                     compute_dtype=jnp.bfloat16), donate_argnums=(2,))
    cache = init_paged_cache(cfg, 1, blocks + 48, bs, blocks, dtype=jnp.bfloat16)
    table = np.random.default_rng(3).permutation(blocks + 48)[:blocks]
    cache["block_tables"] = jnp.asarray(table[None], jnp.int32)
    sets, last, widths = {}, [], []
    spans = [(lo, min(lo + 256, T)) for lo in range(0, T, 256)] + [(T + i, T + i + 1) for i in range(steps)]
    for lo, hi in spans:
        out, cache = step(params, jnp.asarray([toks[lo:hi]], jnp.int32), cache,
                          jnp.arange(lo, hi, dtype=jnp.int32)[None])
        jax.effects_barrier()
        # a chunk that reaches no further than the selection views one step, reads every
        # visible token and never runs the indexer (ops/mla.py:view_steps): nothing to spy
        assert len(seen) == (2 if hi > K else 0), (lo, hi, len(seen))
        for j, t in enumerate(range(lo, hi)):
            sets[t] = {frozenset(np.flatnonzero(mask[0, j])) for mask in seen}
        widths.append(sorted(set(read)))
        seen.clear()
        read.clear()
        if hi - lo == 1:
            last.append(out[0, 0])
    dsa.top_lanes, dsa.top_mask, hybrid.xla_attention = real_lanes, real_mask, real_attention
    chosen = np.asarray(reference.sequence_selected(params, mc, toks))
    under = all(not sets[t] and chosen[:, t, :t + 1].all() for t in range(K))
    views = [[mla.view_lanes(lo, hi - lo, K, bs, blocks)] if hi - lo > 1 else [] for lo, hi in spans]
    verdict(f"glm/cell/selected_sets[context <= {K}]", under and widths == views,
            f"{K} rows in chunks that reach no further than the selection: every visible token, no indexer "
            f"run; lanes a chunk's attention read, of {blocks * bs}: {sorted({w for v in views for w in v})}")
    shared, total = 0, 0
    for t in range(K, T + steps):
        want = sorted((frozenset(np.flatnonzero(chosen[layer, t])) for layer in range(2)), key=sorted)
        got = sorted(sets[t], key=sorted)
        # layer order is not promised: pair the sets the way that shares most
        shared += max(sum(len(a & b) for a, b in zip(want, perm)) for perm in (got, got[::-1]))
        total += 2 * K
    verdict(f"glm/cell/selected_sets[context > {K}]", shared >= 0.97 * total,
            f"{shared} of {total} picks shared with the float32 reference ({100 * shared / total:.2f} %), "
            f"{T + steps - K} rows x 2 layers")
    ref = reference.sequence_logits(params, mc, toks, list(range(T, T + steps)))
    served = jnp.stack(last).astype(jnp.float32)
    # the same steps WITHOUT the selection (every visible token read: plain latent attention),
    # program and reference alike: what bf16 costs apart from the picks it orders otherwise
    plain = dataclasses.replace(cfg, index_topk=0)
    step = jax.jit(lambda p, ids, cache, pos: forward(p, ids, plain, cache=cache, positions=pos,
                                                     compute_dtype=jnp.bfloat16), donate_argnums=(2,))
    cache = init_paged_cache(plain, 1, blocks + 48, bs, blocks, dtype=jnp.bfloat16)
    cache["block_tables"] = jnp.asarray(table[None], jnp.int32)
    last = []
    for lo, hi in spans:
        out, cache = step(params, jnp.asarray([toks[lo:hi]], jnp.int32), cache,
                          jnp.arange(lo, hi, dtype=jnp.int32)[None])
        if hi - lo == 1:
            last.append(out[0, 0])
    ref_plain = reference.sequence_logits(params, dict(mc, index_topk=10 ** 9), toks,
                                          list(range(T, T + steps)))
    rms = lambda a: float(jnp.sqrt(jnp.mean(a * a)))  # noqa: E731
    noise = rms(served - ref) / rms(ref - jnp.mean(ref))
    noise_plain = rms(jnp.stack(last).astype(jnp.float32) - ref_plain) / rms(ref_plain - jnp.mean(ref_plain))
    # swapping m of N near-equally weighted rows moves a mean of N by sqrt(2 m / N) of itself: the
    # 0.5 % of picks that bf16 orders otherwise are a tenth of an attention output, in every layer
    verdict("glm/cell/decode_logits", noise_plain <= 0.03 and noise <= 0.25,
            f"rms (logit - reference) over the logits' spread: {noise:.4f} with the selection, "
            f"{noise_plain:.4f} without it ({steps} steps, {cfg.vocab_size} logits each)")


def child_kimi(rehearsal: bool) -> int:
    """Runs IN the chip-holding child: a model whose every mixer is DENSE
    latent attention under YaRN, served under a prefix cache (copy-on-write
    blocks of the latent pool). First ``preset:debug-kimi`` with two adapters on
    ``q_b_proj`` / ``o_proj``: a session of three turns an adapter (a cold turn,
    two that extend their history through shared blocks, pads mid-row) and the
    first turn again (an exact hit), served tokens against
    benchmarks/reference/kimi_k2.py as logits, and the admissions' paths. Then
    (not in the CPU rehearsal), at the published widths of the benchmark's
    configuration cut to its first two layers: YaRN's tables against the closed
    form in float64; one slot through a paged cache to 12k tokens, the chunks
    that end at contexts of 1k, 4k and 12k (viewing the first, the fourth and
    the last of the table's twelve widths) and the token steps after them against
    the reference's full forward;
    and a turn served through shared blocks against the same turn served cold."""
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from reference import kimi_k2 as reference

    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine
    from datatunerx_tpu.utils import runtime

    runtime.startup("kimi")
    ok = True

    def verdict(name, passed, detail):
        nonlocal ok
        ok &= bool(passed)
        print(f"{'PASS' if passed else 'FAIL'} {name} {detail}", flush=True)

    work = tempfile.mkdtemp(prefix="smoke_kimi_")
    adapters = {f"ad{i}": make_adapter_checkpoint(
        f"{work}/ad{i}", "preset:debug-kimi", seed=20 + i, rank=4,
        targets=("q_b_proj", "o_proj")) for i in range(2)}
    eng = BatchedEngine("preset:debug-kimi", adapters=adapters, slots=3, decode_chunk=4,
                        kv_block_size=8, kv_blocks=192, max_seq_len=512, prefill_chunk=64,
                        kv_overcommit="on", prefix_cache=8)
    try:
        rng = np.random.default_rng(1)
        items = []
        for name, n in (("", 70), ("ad0", 130), ("ad1", 33)):
            history = rng.integers(10, 500, size=n).tolist()
            first = list(history)
            for tool in (0, 37, 90):
                history = history + rng.integers(10, 500, size=tool).tolist()
                req = eng.submit(history, max_new_tokens=12, adapter=name)
                items.append((list(history), name, req))
                assert req.done.wait(900)
                history = history + list(req.tokens)
            items.append((first, name, eng.submit(first, max_new_tokens=12, adapter=name)))
        gaps = _served_gaps(eng, reference, items, verdict, "kimi")
        verdict("kimi/served_vs_reference", float(gaps.max()) <= 0.05,
                f"gap_max {gaps.max():.4f} gap_mean {gaps.mean():.5f} tokens {gaps.size}")
        modes = [e[3] for e in eng.sched_trace if e[0] == "admit"]
        stats = eng.prefix_stats
        verdict("kimi/prefix_paths",
                modes == ["chunked", "cow_extend", "cow_extend", "cow"] * 3
                and (stats["cold"], stats["extensions"], stats["hits"]) == (3, 6, 3)
                and eng.decode_paths == {"mla": "gather"},
                json.dumps(dict(stats, modes=modes, decode_paths=eng.decode_paths)))
    finally:
        eng.close()
        shutil.rmtree(work, ignore_errors=True)
    if rehearsal or not ok:
        return 0 if ok else 1
    _kimi_cell_check(verdict, "kimi-serve-agent")
    return 0 if ok else 1


def _kimi_cell_check(verdict, cell_name: str) -> None:
    """The second half of ``child_kimi``, at the widths of ``cell_name``'s configuration."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    import spec
    from reference import kimi_k2 as reference

    from datatunerx_tpu.models import forward
    from datatunerx_tpu.models.config import PRESETS, mixer_kinds
    from datatunerx_tpu.ops import mla
    from datatunerx_tpu.ops.paged_attention import init_paged_cache
    from datatunerx_tpu.ops.rope import rope_cos_sin, yarn_mscale
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    cell = spec.load_cell(cell_name)
    cfg = dataclasses.replace(spec.register_preset(cell), num_layers=2, layer_types=("mla",) * 2,
                              ffn_types=("dense", "experts"))
    kind = mixer_kinds(cfg)["mla"]
    # YaRN's tables against the closed form in float64, below the trained length, at it, at the
    # cell's longest context and at the model's. The chip's float32 power and product leave a
    # frequency good to 7e-7 of itself (PR 45: 0.0087 rad at position 12,287, 0.186 at 262,143;
    # the CPU's to 4e-7, tests/test_kimi_model.py): twice that is allowed
    d, base, y = kind.rope_dim, kind.rope_theta, kind.yarn
    corr = lambda n: d * math.log(y.original_max_len / (2 * math.pi * n)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(corr(y.beta_fast)), 0), min(math.ceil(corr(y.beta_slow)), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freq = base ** (-2 * i / d) * ((1 - ramp) + ramp / y.factor)
    pos = np.asarray([0, 1, 1000, 4095, 4096, 12287, 65536, 262143])
    cos, sin = rope_cos_sin(jnp.asarray(pos[None], jnp.int32), d, theta=base, yarn=y)
    err = np.maximum(np.abs(np.asarray(cos[0]) - np.cos(pos[:, None] * freq)),
                     np.abs(np.asarray(sin[0]) - np.sin(pos[:, None] * freq))).max(axis=1)
    verdict("kimi/cell/yarn_tables", bool((err <= 1.5e-6 * pos + 2e-6).all()) and (low, high) == (8, 20)
            and abs(kind.score_scale - 192 ** -0.5 * yarn_mscale(64, 1) ** 2) < 1e-12,
            f"low {low} high {high}; max |error| at positions {pos.tolist()}: "
            f"{[float(f'{e:.2e}') for e in err]}; score scale {kind.score_scale:.6f}")

    # the published widths, the configuration's first two layers, one slot through a paged cache
    mc = dict(cell.model_fields, num_layers=2, layer_types=["mla"] * 2, ffn_types=["dense", "experts"])
    weights = spec.load_module(cell.config["weights_module"])
    params = weights.draw_params(mc, 4100000045)
    bs, steps, marks = 16, 4, (1024, 4096, 12272)
    T = marks[-1] + steps
    blocks = -(-T // bs)
    toks = np.random.default_rng(2).integers(10, cfg.vocab_size, size=T).tolist()
    spans, at = [], 0
    for mark in marks:
        while at < mark:
            spans.append((at, min(at + 256, mark)))
            at = spans[-1][1]
        spans += [(mark + j, mark + j + 1) for j in range(steps)]
        at = mark + steps
    step = jax.jit(lambda p, ids, cache, pos: forward(p, ids, cfg, cache=cache, positions=pos,
                                                     compute_dtype=jnp.bfloat16), donate_argnums=(2,))
    cache = init_paged_cache(cfg, 1, blocks + 48, bs, blocks, dtype=jnp.bfloat16)
    table = np.random.default_rng(3).permutation(blocks + 48)[:blocks]
    cache["block_tables"] = jnp.asarray(table[None], jnp.int32)
    last, viewed = {}, {}
    for lo, hi in spans:
        out, cache = step(params, jnp.asarray([toks[lo:hi]], jnp.int32), cache,
                          jnp.arange(lo, hi, dtype=jnp.int32)[None])
        if hi - lo == 1:
            last[lo] = out[0, 0]
        elif hi in marks:  # the chunk that ends at a mark: its last rows, and the lanes it viewed
            last.update({hi - steps + j: out[0, hi - lo - steps + j] for j in range(steps)})
            viewed[hi] = mla.view_lanes(lo, hi - lo, kind.index_topk, bs, blocks)
    rows = sorted(last)
    ref = reference.sequence_logits(params, mc, toks, rows)
    rms = lambda a: float(jnp.sqrt(jnp.mean(a * a)))  # noqa: E731
    # a chunk's view is as wide as its context reaches (ops/mla.py:view_steps): the chunks that end
    # at the marks view the first, the fourth and the last of the table's twelve widths
    for name, mark in [("chunk", mark) for mark in marks] + [("decode", mark) for mark in marks]:
        first = mark - steps if name == "chunk" else mark  # a chunk's last rows; the token steps after it
        pick = [rows.index(first + j) for j in range(steps)]
        got = jnp.stack([last[first + j] for j in range(steps)]).astype(jnp.float32)
        want = ref[jnp.asarray(pick)]
        noise = rms(got - want) / rms(want - jnp.mean(want))
        agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
        sound, view = noise <= 0.03, ""
        if name == "chunk":
            sound &= viewed[mark] == -(-mark // mla.VIEW_STEP_LANES) * mla.VIEW_STEP_LANES
            view = f", a view of {viewed[mark]} of {blocks * bs} lanes"
        # bf16 weights, activations and cached rows against float32: cell 7's steps without their
        # selection read 0.010 of the logits' spread
        verdict(f"kimi/cell/{name}_logits[context {mark}]", sound,
                f"rms (logit - reference) over the logits' spread {noise:.4f}, the same first token "
                f"in {agree:.2f} of {steps} rows x {cfg.vocab_size} logits{view}")
    del cache, params

    # a turn served through shared blocks against the same turn served cold
    PRESETS["kimi-smoke-l2"] = dataclasses.replace(cfg, name="kimi-smoke-l2")
    eng = BatchedEngine("preset:kimi-smoke-l2", slots=2, decode_chunk=8, kv_block_size=16, kv_blocks=256,
                        max_seq_len=2048, prefill_chunk=256, kv_overcommit="on", prefix_cache=4)
    try:
        rng = np.random.default_rng(4)
        first = rng.integers(10, cfg.vocab_size, size=700).tolist()
        a = eng.submit(first, max_new_tokens=24)
        assert a.done.wait(900) and a.error is None, a.error
        turn = first + list(a.tokens) + rng.integers(10, cfg.vocab_size, size=150).tolist()
        shared = eng.submit(turn, max_new_tokens=24)
        assert shared.done.wait(900) and shared.error is None, shared.error
        eng._prefix.drop_adapter(0)  # the base's entries: the same turn now finds nothing
        cold = eng.submit(turn, max_new_tokens=24)
        assert cold.done.wait(900) and cold.error is None, cold.error
        modes = [e[3] for e in eng.sched_trace if e[0] == "admit"]
        gaps = _served_gaps(eng, reference, [(turn, "", shared), (turn, "", cold)], verdict, "kimi/cell")
        # bf16 rounds one way through pads mid-row and another through pads at the left: the
        # tokens need not be the same, each has to be what the float32 reference puts first or near
        verdict("kimi/cell/shared_vs_cold", modes == ["chunked", "cow_extend", "chunked"]
                and float(gaps.max()) <= 0.25,
                f"paths {modes}; same tokens {shared.tokens == cold.tokens}; gap_max {gaps.max():.4f} "
                f"gap_mean {gaps.mean():.5f} over {gaps.size} tokens")
    finally:
        eng.close()


def child_kernels(rehearsal: bool) -> int:
    """Runs IN the chip-holding child: compile every Pallas kernel with
    Mosaic and compare it with its oracle. On the CPU rehearsal the same
    checks run in interpret mode at toy sizes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from datatunerx_tpu.ops.attention import (
        attention_allow,
        kv_dequantize,
        kv_quantize,
        make_causal_bias,
        xla_attention,
    )
    from datatunerx_tpu.ops.flash_attention import flash_attention
    from datatunerx_tpu.ops.paged_attention import POS_SENTINEL
    from datatunerx_tpu.ops.pallas_lora import pallas_lora_matmul
    from datatunerx_tpu.ops.pallas_paged_attention import (
        paged_decode_attention,
        paged_multitoken_attention,
    )
    from datatunerx_tpu.ops.pallas_quant import (
        pallas_matmul_int8,
        pallas_matmul_nf4,
    )
    from datatunerx_tpu.ops.pallas_sampling import fused_sample
    from datatunerx_tpu.ops.quant import (
        matmul_int8,
        matmul_nf4,
        quantize_int8,
        quantize_nf4,
    )
    from datatunerx_tpu.utils import runtime

    # no kernel below is told how to lower: each takes the backend default
    # (ops/_pallas.py), which the parent asserts is Mosaic on the chip
    runtime.startup("kernels")
    results = []
    clock = [time.monotonic()]

    def check(name, got, want, atol, rtol=0.0, exact=False):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        ok = bool(np.all(np.isfinite(got))) and (
            bool(np.array_equal(got, want)) if exact
            else bool(np.all(err <= atol + rtol * np.abs(want))))
        results.append(ok)
        now = time.monotonic()
        print(f"{'PASS' if ok else 'FAIL'} kernel/{name}: "
              f"max_abs_err={err.max():.3e}"
              + (" (exact)" if exact else f" (atol={atol:g} rtol={rtol:g})")
              + f" [{now - clock[0]:.1f}s]", flush=True)
        clock[0] = now

    def guarded(name, fn):
        """A kernel Mosaic refuses is a FAIL line carrying the compiler's
        own message — never a swapped-in oracle."""
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — reported as the verdict
            results.append(False)
            msg = " ".join(str(e).split())[:600]
            print(f"FAIL kernel/{name}: {type(e).__name__}: {msg}",
                  flush=True)

    geoms = GEOMETRIES if not rehearsal else {"debug": (4, 2, 16, 64, 128)}
    # inputs are drawn on the host: an eager jax.random call is one more
    # program to compile, and this child compiles hundreds as it is
    rng = np.random.default_rng(SEED)

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(
            rng.standard_normal(shape, np.float32) * scale, dtype)

    # ---- flash attention fwd/bwd: causal GQA, and packed segments; both
    # again under a sliding window of a quarter tile over rows of four tiles,
    # so that tiles wholly behind the window are skipped (no cell reaches
    # that: a FinetuneJob's rows are shorter than the window of every model
    # it trains)
    def flash(gname, H, KV, d):
        B, T0 = (2, 1024) if not rehearsal else (2, 128)
        for T, w in ((T0, None), (2 * T0, T0 // 4)):
            q, k, v = (normal((B, T, n, d)) for n in (H, KV, KV))
            pos = jnp.asarray(np.broadcast_to(np.arange(T, dtype=np.int32),
                                              (B, T)))
            seg = jnp.asarray(np.broadcast_to(
                np.where(np.arange(T) < T // 2, 1, 2).astype(np.int32),
                (B, T)))
            for label, s in (("causal_gqa", None), ("segmented", seg)):
                if w is not None:
                    label = f"window{w}_{label}"

                def f_kernel(q, k, v, s=s, w=w):
                    return flash_attention(q, k, v, segment_ids=s,
                                           sliding_window=w)

                def f_oracle(q, k, v, s=s, w=w, pos=pos):
                    return xla_attention(q, k, v, make_causal_bias(
                        pos, pos, sliding_window=w, q_segment_ids=s,
                        kv_segment_ids=s))

                check(f"flash_fwd_{label} [{gname} B{B} T{T}]",
                      jax.jit(f_kernel)(q, k, v), jax.jit(f_oracle)(q, k, v),
                      atol=3e-2)

                def loss(f):
                    return lambda q, k, v: (
                        f(q, k, v).astype(jnp.float32) ** 2).sum()

                gk = jax.jit(jax.grad(loss(f_kernel), argnums=(0, 1, 2)))(
                    q, k, v)
                go = jax.jit(jax.grad(loss(f_oracle), argnums=(0, 1, 2)))(
                    q, k, v)
                for nm, a, b in zip(("dq", "dk", "dv"), gk, go):
                    scale = float(np.abs(np.asarray(b, np.float32)).max())
                    check(f"flash_bwd_{label}_{nm} [{gname} B{B} T{T}]", a, b,
                          atol=3e-2 * max(scale, 1.0))

    # ---- quantized matmuls at the model's projection shapes
    def quant(gname, D, F):
        M = 512 if not rehearsal else 64
        for K, N in ((D, D), (D, F), (F, D)):
            w = normal((K, N), jnp.float32, 0.05)
            x, g = normal((M, K)), normal((M, N))
            q4 = jax.jit(quantize_nf4)(w)
            q8 = jax.jit(quantize_int8)(w)
            tag = f"[{gname} M{M} K{K} N{N}]"

            # weights travel as ARGUMENTS: a closed-over array is baked into
            # the program as a constant, and XLA then spends its compile
            # time folding a 45 MB dequantisation
            def nf4_k(x, q4, q8):
                return pallas_matmul_nf4(x, q4, (K, N))

            def nf4_o(x, q4, q8):
                return matmul_nf4(x, q4, (K, N))

            def int8_k(x, q4, q8):
                return pallas_matmul_int8(x, q8["q"], q8["scale"])

            def int8_o(x, q4, q8):
                return matmul_int8(x, q8["q"], q8["scale"])

            def fwd(f):
                return jax.jit(f)(x, q4, q8)

            def dx(f):  # the backward each custom VJP really runs
                return jax.jit(lambda x, g, q4, q8: jax.vjp(
                    lambda x: f(x, q4, q8), x)[1](g)[0])(x, g, q4, q8)

            check(f"nf4_matmul_fwd {tag}", fwd(nf4_k), fwd(nf4_o),
                  atol=2e-2, rtol=2e-2)
            check(f"nf4_matmul_bwd_transposed {tag}", dx(nf4_k), dx(nf4_o),
                  atol=5e-1, rtol=3e-2)
            check(f"int8_matmul_fwd {tag}", fwd(int8_k), fwd(int8_o),
                  atol=2e-2, rtol=2e-2)
            check(f"int8_matmul_bwd {tag}", dx(int8_k), dx(int8_o),
                  atol=5e-1, rtol=3e-2)

    def lora(gname, D):
        M, r, scale = (512 if not rehearsal else 64), 8, 4.0
        w, a, b = (normal(sh, scale=0.05) for sh in ((D, D), (D, r), (r, D)))
        x = normal((M, D))

        def oracle(x, w, a, b):
            xf = x.astype(jnp.float32)
            return xf @ w.astype(jnp.float32) + (
                xf @ a.astype(jnp.float32)) @ b.astype(jnp.float32) * scale

        check(f"lora_fused_fwd [{gname} M{M} K{D} N{D} r{r}]",
              jax.jit(lambda *t: pallas_lora_matmul(*t, scale=scale))(
                  x, w, a, b),
              jax.jit(oracle)(x, w, a, b), atol=5e-1, rtol=3e-2)

    # ---- paged attention: a shuffled block pool at the engine's geometry
    def paged_pool(B, KV, d, lens, quantized, W=None):
        bs = SERVE_BLOCK
        W = W or (SERVE_SEQ if not rehearsal else 128)
        nbps = W // bs
        NB = B * nbps
        k_pool, v_pool = normal((NB, bs, KV, d)), normal((NB, bs, KV, d))
        perm = rng.permutation(NB).reshape(B, nbps)
        live = (np.arange(nbps)[None] * bs) < np.asarray(lens)[:, None]
        lane = np.arange(W).reshape(nbps, bs)
        pos = np.full((NB, bs), POS_SENTINEL, np.int32)
        for b in range(B):
            for j in range(nbps):
                if live[b, j]:
                    pos[perm[b, j]] = np.where(lane[j] < lens[b], lane[j],
                                               POS_SENTINEL)
        ks = vs = None
        if quantized:
            k_pool, ks = jax.jit(kv_quantize)(k_pool)
            v_pool, vs = jax.jit(kv_quantize)(v_pool)
        return (k_pool, v_pool, ks, vs,
                jnp.asarray(np.where(live, perm, -1), jnp.int32),
                jnp.asarray(pos))

    def gather_attention(q, q_pos, k_pool, v_pool, ks, vs, tables, pos,
                         window=None):
        """The XLA gather path: each slot's linear view through its table,
        causal bias from the gathered positions (a model's sliding ``window``
        in it), ``xla_attention``."""
        B = tables.shape[0]
        tbl = jnp.where(tables >= 0, tables, 0)
        k_all = k_pool[tbl].reshape(B, -1, *k_pool.shape[-2:])
        v_all = v_pool[tbl].reshape(B, -1, *v_pool.shape[-2:])
        if ks is not None:
            k_all = kv_dequantize(
                k_all, ks[tbl].reshape(B, -1, ks.shape[-1]), jnp.bfloat16)
            v_all = kv_dequantize(
                v_all, vs[tbl].reshape(B, -1, vs.shape[-1]), jnp.bfloat16)
        kv_pos = jnp.where((tables >= 0)[:, :, None], pos[tbl],
                           POS_SENTINEL).reshape(B, -1)
        return xla_attention(q, k_all, v_all, make_causal_bias(
            q_pos, kv_pos, sliding_window=window))

    # the kernels read one layer of the stacked pool the layer scan carries
    # ([L, NB, bs, KV * d]): the oracle's pool is the LAST of two layers, the
    # first holds the same blocks in reverse order, so a wrong layer offset
    # reads plausible wrong numbers
    def stacked(x):
        if x is None:
            return None
        if x.ndim == 4:
            x = x.reshape(x.shape[:2] + (-1,))
        return jnp.stack([x[::-1], x])

    def decode(q, q_pos, k_pool, v_pool, ks, vs, tables, pos, window=None):
        # the query sits on the last written lane (no pads): its lane cursor
        # is its position
        return paged_decode_attention(
            q, *(stacked(x) for x in (k_pool, v_pool, ks, vs)),
            jnp.asarray(1, jnp.int32), tables, pos, q_pos, q_pos,
            window=window)

    def multitoken(q, q_pos, k_pool, v_pool, ks, vs, tables, pos):
        tbl = jnp.where(tables >= 0, tables, 0)
        kv_pos = jnp.where((tables >= 0)[:, :, None], pos[tbl],
                           POS_SENTINEL).reshape(tables.shape[0], -1)
        return paged_multitoken_attention(
            q, *(stacked(x) for x in (k_pool, v_pool, ks, vs)),
            jnp.asarray(1, jnp.int32), tables,
            attention_allow(q_pos, kv_pos))

    def paged(gname, H, KV, d):
        W = SERVE_SEQ if not rehearsal else 128
        for quantized in (False, True):
            kvtag = "int8_kv" if quantized else "bf16"
            B = SERVE_SLOTS
            lens = [W, W // 2 + 3, 17, 1][:B]
            pool = paged_pool(B, KV, d, lens, quantized)
            q = normal((B, H, d))
            qpos = jnp.asarray([n - 1 for n in lens], jnp.int32)
            check(f"paged_decode_{kvtag} [{gname} B{B} bs{SERVE_BLOCK} "
                  f"W{W}]",
                  jax.jit(decode)(q, qpos, *pool),
                  jax.jit(gather_attention)(q[:, None], qpos[:, None],
                                            *pool)[:, 0],
                  atol=2e-2, rtol=2e-2)

            if rehearsal:
                qlens = [(1, 64), (B, 5)]
            elif gname == "tinyllama-1.1b" and not quantized:
                qlens = ([(1, t) for t in CHUNK_QLENS]
                         + [(B, t) for t in VERIFY_QLENS])
            else:
                qlens = [(1, CHUNK_QLENS[0]), (1, CHUNK_QLENS[-1]),
                         (B, VERIFY_QLENS[3])]
            for Bq, T in qlens:
                # the last T written tokens of each slot are the queries
                mlens = [max(n, T) for n in (lens[:Bq] if Bq > 1 else [W])]
                mpool = paged_pool(Bq, KV, d, mlens, quantized)
                qm = normal((Bq, T, H, d))
                qp = jnp.asarray([[n - T + i for i in range(T)]
                                  for n in mlens], jnp.int32)
                check(f"paged_multitoken_{kvtag} [{gname} B{Bq} q_len{T} "
                      f"bs{SERVE_BLOCK} W{W}]",
                      jax.jit(multitoken)(qm, qp, *mpool),
                      jax.jit(gather_attention)(qm, qp, *mpool),
                      atol=2e-2, rtol=2e-2)

    # ---- the decode kernel at the shape and fill of the benchmark's cell
    # qwen-serve-steady (16 slots, a table of 128 columns of 16, 32 KV heads
    # of 128, 384 blocks): three live slots whose tables hold a reserved tail
    # past the cursor, thirteen released ones (table -1, a stale cursor)
    def paged_cell():
        B, H, KV, d, bs = 16, 32, 32, 128, SERVE_BLOCK
        nbps, NB = (128, 384) if not rehearsal else (16, 48)
        live = [40, 500, 1100] if not rehearsal else [5, 40, 100]
        lens = np.asarray(live + [300 if not rehearsal else 30]
                          * (B - len(live)))
        k_pool, v_pool = normal((NB, bs, KV, d)), normal((NB, bs, KV, d))
        perm = rng.permutation(NB)
        tables = np.full((B, nbps), -1, np.int32)
        pos = np.full((NB, bs), POS_SENTINEL, np.int32)
        lane = np.arange(nbps * bs).reshape(nbps, bs)
        at = 0
        for b, n in enumerate(live):
            held = -(-n // bs) + 8  # admission reserves prompt + max_new
            tables[b, :held] = perm[at:at + held]
            at += held
            for j in range(-(-n // bs)):
                pos[tables[b, j]] = np.where(lane[j] < n, lane[j],
                                             POS_SENTINEL)
        pool = (k_pool, v_pool, None, None, jnp.asarray(tables),
                jnp.asarray(pos))
        q = normal((B, H, d))
        qpos = jnp.asarray(lens - 1, jnp.int32)
        got = jax.jit(decode)(q, qpos, *pool)
        want = jax.jit(gather_attention)(q[:, None], qpos[:, None],
                                         *pool)[:, 0]
        n_live = len(live)
        check(f"paged_decode_bf16 [cell B{B} bs{bs} W{nbps * bs} "
              f"live {live}]", got[:n_live], want[:n_live],
              atol=2e-2, rtol=2e-2)
        check("paged_decode_bf16 [cell: released slots read zero]",
              got[n_live:], jnp.zeros_like(got[n_live:]), atol=0,
              exact=True)

    # ---- the decode kernel's walk under a sliding window narrower than the
    # cache (no cell reaches that: mistral-serve-batch's cache of 2,048 lanes
    # lies inside its window of 4,096, which the kernel drops): Mistral-7B's
    # heads, 16 slots, a context of 2,048 and a window of 256. The walk
    # starts at the window's first trip, so it is held against the gather
    # path under the same window and must take less time than the
    # window-less call on the same cache
    def paged_window():
        B, H, KV, d = 16, 32, 8, 128
        W, window = (2048, 256) if not rehearsal else (256, 32)
        # full, behind the window by one lane, exactly the window, inside it
        lens = ([W, window + 1, window, 17]
                + [int(n) for n in rng.integers(W // 2, W, B - 4)])
        pool = paged_pool(B, KV, d, lens, False, W=W)
        q = normal((B, H, d))
        qpos = jnp.asarray([n - 1 for n in lens], jnp.int32)
        check(f"paged_decode_bf16_window{window} [mistral-7b B{B} "
              f"bs{SERVE_BLOCK} W{W}]",
              jax.jit(functools.partial(decode, window=window))(
                  q, qpos, *pool),
              jax.jit(functools.partial(gather_attention, window=window))(
                  q[:, None], qpos[:, None], *pool)[:, 0],
              atol=2e-2, rtol=2e-2)
        # timed over pools stacked beforehand, as the layer scan hands them
        # over: stacking them costs more than either walk
        args = (q, stacked(pool[0]), stacked(pool[1]), None, None,
                jnp.asarray(1, jnp.int32), *pool[4:], qpos, qpos)

        def seconds(window):
            fn = jax.jit(functools.partial(paged_decode_attention,
                                           window=window))
            jax.block_until_ready(fn(*args))
            took = []
            for _ in range(20):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                took.append(time.perf_counter() - t0)
            return float(np.median(took))

        t_walk, t_whole = seconds(window), seconds(None)
        # a CPU's time for the emulation says nothing: reported, not judged
        ok = rehearsal or t_walk < t_whole
        results.append(ok)
        print(f"{'PASS' if ok else 'FAIL'} kernel/paged_decode_bf16_window"
              f"{window} [walk {t_walk * 1e6:.0f} us a call under the "
              f"window-less {t_whole * 1e6:.0f} us]", flush=True)

    # ---- the decode kernel at the shapes of the attending kinds whose token
    # step models/hybrid.py hands it: MiMo's global kind in the cell
    # mimo-serve-batch (64 slots, 64 heads over 4 KV heads, q/k 192 and v 128
    # wide, tables of 160 columns over 2,560 blocks) and Granite's in
    # granite-serve-chat (32 heads over 8 KV heads of 64, scores x 1/64,
    # tables of 64 columns over 4,096 blocks), contexts as those cells hold
    # them. Each against what the step took before, a gathered view of every
    # slot's whole table and ``xla_attention`` over it, in values and in time.
    # And the walk MiMo's WINDOW kind would take (8 KV heads, a window of 128;
    # the kernel has no sink, so the values are held without one) against its
    # window-wide view under the sink: reported, not judged. That kind stays
    # on the view until the walk is the faster (ROADMAP B7)
    def paged_kinds():
        from datatunerx_tpu.ops.attention import KVStep
        from datatunerx_tpu.ops.paged_attention import gathered_positions

        bs = SERVE_BLOCK
        #        B, H, KV, d, dv, nbps, NB, scale, window, contexts, judged
        cases = {
            "mimo_global": (64, 64, 4, 192, 128, 160, 2560, None, None,
                            (150, 1000), True),
            "granite_global": (64, 32, 8, 64, 64, 64, 4096, 0.015625, None,
                               (60, 420), True),
            "mimo_window": (64, 64, 8, 192, 128, 160, 2560, None, 128,
                            (150, 1000), False),
        } if not rehearsal else {
            "debug_v_under_d": (4, 8, 2, 24, 16, 8, 32, None, None,
                                (5, 100), True),
            "debug_scaled": (4, 4, 2, 16, 16, 8, 32, 0.125, None,
                             (5, 100), True),
            "debug_window": (4, 4, 2, 24, 16, 8, 32, None, 24,
                             (5, 100), False),
        }
        for name, (B, H, KV, d, dv, nbps, NB, scale, window, (lo, hi),
                   judged) in cases.items():
            lens = rng.integers(lo, hi, B)
            lens[0], lens[1] = hi, 1
            while sum(-(-int(n) // bs) for n in lens) > NB:  # the pool holds them
                lens = np.maximum(1, lens * 9 // 10)
            perm = rng.permutation(NB)
            tables = np.full((B, nbps), -1, np.int32)
            pos = np.full((NB, bs), POS_SENTINEL, np.int32)
            lane = np.arange(nbps * bs).reshape(nbps, bs)
            at = 0
            for b, n in enumerate(lens):
                held = -(-int(n) // bs)
                tables[b, :held] = perm[at:at + held]
                at += held
                for j in range(held):
                    pos[tables[b, j]] = np.where(lane[j] < n, lane[j],
                                                 POS_SENTINEL)
            assert at <= NB
            # two stacked layers, the second one read
            k, v = normal((2, NB, bs, KV * d)), normal((2, NB, bs, KV * dv))
            q = normal((B, H, d))
            sink = normal((H,), jnp.float32) if window else None
            tables, pos = jnp.asarray(tables), jnp.asarray(pos)
            cursor = jnp.asarray(lens - 1, jnp.int32)
            li = jnp.asarray(1, jnp.int32)

            def view(q, k, v, sink):
                step = KVStep({"len": cursor, "pos": pos,
                               "block_tables": tables}, 1, window=window)
                bias = make_causal_bias(
                    cursor[:, None], gathered_positions(pos, step.view_tables),
                    None, sliding_window=window)
                return xla_attention(
                    q[:, None], step.read(k, li).reshape(B, -1, KV, d),
                    step.read(v, li).reshape(B, -1, KV, dv), bias, sink=sink,
                    scale=scale)[:, 0]

            def walk(q, k, v, sink):
                return paged_decode_attention(
                    q, k, v, None, None, li, tables, pos, cursor, cursor,
                    window=window, scale=scale)

            shape = (f"[{name} B{B} H{H}/{KV} d{d}/{dv} bs{bs} W{nbps * bs} "
                     f"contexts {lo}-{hi}]")
            check(f"paged_decode_kind {shape}", jax.jit(walk)(q, k, v, None),
                  jax.jit(view)(q, k, v, None), atol=2e-2, rtol=2e-2)

            def seconds(fn):
                # twenty calls in a row and one wait, as ssm_step times
                fn = jax.jit(fn)
                jax.block_until_ready(fn(q, k, v, sink))
                took = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    for _ in range(20):
                        out = fn(q, k, v, sink)
                    jax.block_until_ready(out)
                    took.append((time.perf_counter() - t0) / 20)
                return float(np.median(took))

            t_walk, t_view = seconds(walk), seconds(view)
            # a CPU's time for the emulation says nothing: reported, not judged
            ok = rehearsal or not judged or t_walk < t_view
            results.append(ok)
            print(f"{'PASS' if ok else 'FAIL'} kernel/paged_decode_kind {shape} "
                  f"[dtx_paged_decode {t_walk * 1e6:.0f} us a call, the view "
                  f"and xla_attention {t_view * 1e6:.0f} us"
                  + ("" if judged else "; not judged") + "]", flush=True)

    # ---- a windowed model through the batched engine: the token step takes
    # the decode kernel (pool rows of 2 x 64 = one lane tile, so the kernel
    # itself and not the multi-token one at q_len 1) with the window it was
    # built with, chunks and all else the gather; served greedy tokens are
    # held against the plain float32 reference as logits
    def windowed_engine():
        import dataclasses

        sys.path.insert(0, os.path.join(REPO, "benchmarks"))
        from reference import decoder as reference

        from datatunerx_tpu.models.config import PRESETS
        from datatunerx_tpu.serving.batched_engine import BatchedEngine

        PRESETS["debug-window"] = dataclasses.replace(
            PRESETS["debug"], name="debug-window", head_dim=64,
            sliding_window=32)
        eng = BatchedEngine(
            "preset:debug-window", template="vanilla", max_seq_len=256,
            slots=2, decode_chunk=4, kv_block_size=SERVE_BLOCK,
            prefill_chunk=64, paged_kernel="on" if rehearsal else "auto")

        def verdict(name, passed, detail):
            results.append(bool(passed))
            print(f"{'PASS' if passed else 'FAIL'} kernel/{name} {detail}",
                  flush=True)

        try:
            verdict("windowed_engine/decode_path",
                    (eng.decode_path, eng.decode_window) == ("pallas", 32),
                    f"{eng.decode_path} window {eng.decode_window}")
            work = [(p, "", eng.submit(p, max_new_tokens=24)) for p in
                    (rng.integers(10, 500, size=n).tolist() for n in (150, 40))]
            gaps = _served_gaps(eng, reference, work, verdict,
                                "windowed_engine")
            verdict("windowed_engine/served_vs_reference",
                    float(gaps.max()) <= 0.05,
                    f"gap_max {gaps.max():.4f} gap_mean {gaps.mean():.5f} "
                    f"tokens {gaps.size}")
        finally:
            eng.close()

    # ---- fused sampler at S = slots and at the benchmark cells' 16: greedy
    # bitwise, simple exact by seed
    def sampler(S, V):
        logits = normal((S, V), jnp.float32, 3.0)
        temps = jnp.asarray(([0.7, 1.0, 0.0, 1.3] * S)[:S], jnp.float32)
        top_ps = jnp.ones((S,), jnp.float32)
        keys = jnp.asarray(rng.integers(0, 2 ** 32, (S, 2), np.uint32))
        for mode in ("greedy", "simple"):
            run = lambda impl: jax.jit(  # noqa: E731
                lambda lg, t, p, k: fused_sample(
                    lg, t, p, k, mode=mode, impl=impl))(
                        logits, temps, top_ps, keys)
            check(f"fused_sample_{mode} [S{S} V{V}]", run("kernel"),
                  run("xla"), atol=0, exact=True)

    # ---- the expert layer's grouped matmul at the two sparse-expert cells'
    # widths, rows of a decode step, a run's stacked weights and a traced
    # layer: dtx_moe_gmm at the row tile the shapes give against ragged_dot
    def moe_gmm():
        from datatunerx_tpu.ops import moe

        shapes = ({"mimo": (64, 8, 256, 16, 4096, 2048),
                   "ling": (128, 8, 512, 64, 2560, 768)} if not rehearsal
                  else {"debug": (16, 4, 32, 8, 256, 128)})
        got, want = [], []
        for rows, k, total, held, D, F in shapes.values():
            name, tm = moe.grouped_matmul(rows, top_k=k, experts_total=total,
                                          d=D, f=F)
            assert name == "dtx_moe_gmm", (name, tm)
            sizes = np.minimum(rng.poisson(rows * k / total, held), 3 * tm)
            sizes[:: 5] = 0  # experts no row chose
            real = int(sizes.sum())
            args = (normal((rows * k, D)), jnp.asarray(sizes, jnp.int32),
                    normal((2, held, D, F), scale=0.02),
                    normal((2, held, D, F), scale=0.02),
                    normal((2, held, F, D), scale=0.02), jnp.asarray(1, jnp.int32))
            for out, row_tile in ((got, tm), (want, None)):
                out.append(np.asarray(jax.jit(
                    lambda xs, sizes, g, u, d, layer, row_tile=row_tile:
                    moe.grouped_swiglu(xs, sizes, g, u, d, layer, row_tile)
                )(*args)[:real], np.float32).ravel())
        check("moe_gmm_vs_ragged_dot [" + " ".join(
            f"{n}:{r * k}x{D}x{F}/E{held}" for n, (r, k, _, held, D, F)
            in shapes.items()) + "]",
            np.concatenate(got), np.concatenate(want), atol=2e-2, rtol=2e-2)

    # ---- the Mamba-2 token step at the cell granite-serve-chat's shapes (64
    # slots, one layer of a 36-layer float32 leaf carried and donated, as the
    # decode program has it): dtx_ssm_step against ssm.state_step, the leaf's
    # other layers untouched, and both timings over the head tiles
    def ssm_step():
        from datatunerx_tpu.ops import pallas_ssm, ssm

        L, B, H, P, N, G = ((36, 64, 64, 64, 128, 1) if not rehearsal
                            else (3, 4, 8, 8, 128, 1))
        name, th0 = pallas_ssm.step_kernel(
            jax.ShapeDtypeStruct((L, B, H, P, N), jnp.float32), 1)
        assert name == "dtx_ssm_step", (name, th0)
        li = jnp.asarray(L - 2, jnp.int32)

        @jax.jit
        def draw():  # on the device, a layer a program step: 4.8 GB
            return jax.lax.map(
                lambda k: jax.random.normal(k, (B, H, P, N), jnp.float32),
                jax.random.split(jax.random.PRNGKey(SEED), L))

        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (B, 1, H)))
        dt[1] = 0.0  # an idle row: its state must come back bit for bit
        ops = (normal((B, 1, H, P), jnp.float32), normal((B, 1, G, N), jnp.float32),
               normal((B, 1, G, N), jnp.float32), jnp.asarray(dt, jnp.float32),
               jnp.asarray(-dt * rng.uniform(1, 16, (H,)), jnp.float32),
               normal((H,), jnp.float32))
        fresh = jnp.asarray(np.arange(B) % 8 == 3)

        def xla(leaf, li):
            state = jnp.where(fresh[:, None, None, None], 0.0, leaf[li])
            y, state = ssm.state_step(state, *(a[:, 0] for a in ops[:5]), ops[5])
            return y[:, None], leaf.at[li].set(state)

        def kernel(th):
            return lambda leaf, li: pallas_ssm.ssm_step(
                leaf, li, fresh, *ops, th=th)

        def run(fn):
            return jax.jit(fn, donate_argnums=0)

        before = draw()
        edge = np.asarray(before[L - 3:, :2, :2])  # the layer stepped and its neighbours
        want_y, want = run(xla)(before, li)
        want_y, want_state = np.asarray(want_y), np.asarray(want[li])
        del want
        got_y, got = run(kernel(th0))(draw(), li)
        shape = f"[{L}x{B}x{H}x{P}x{N} th{th0}]"
        check(f"ssm_step_y_vs_state_step {shape}", got_y, want_y, atol=5e-5, rtol=1e-6)
        check(f"ssm_step_state_vs_state_step {shape}", got[li], want_state,
              atol=1e-6, rtol=1e-6)
        after = np.asarray(got[L - 3:, :2, :2])
        check(f"ssm_step_other_layers_and_idle_row_untouched {shape}",
              np.concatenate([after[0].ravel(), after[2].ravel(), after[1, 1].ravel()]),
              np.concatenate([edge[0].ravel(), edge[2].ravel(), edge[1, 1].ravel()]),
              atol=0, exact=True)

        def seconds(fn, leaf):
            # twenty calls in a row and one wait: a call alone is mostly the
            # host's dispatch and the wait's round trip (0.8 ms on v5e)
            fn = run(fn)
            _, leaf = fn(leaf, li)
            took = []
            for _ in range(5):
                jax.block_until_ready(leaf)
                t0 = time.perf_counter()
                for _ in range(20):
                    y, leaf = fn(leaf, li)
                jax.block_until_ready((y, leaf))
                took.append((time.perf_counter() - t0) / 20)
            return float(np.median(took)), leaf

        moved = 2 * B * H * P * N * 4  # the layer's state read once and written once
        t_xla, leaf = seconds(xla, got)
        sweep = []
        for th in [t for t in (8, 16, 32, 64) if H % t == 0] or [th0]:
            t, leaf = seconds(kernel(th), leaf)
            sweep.append((th, t))
        t_kernel = dict(sweep).get(th0) or seconds(kernel(th0), leaf)[0]
        # a CPU's time for the emulation says nothing: reported, not judged
        ok = rehearsal or t_kernel < t_xla
        results.append(ok)
        print(f"{'PASS' if ok else 'FAIL'} kernel/ssm_step {shape} [dtx_ssm_step "
              f"{t_kernel * 1e6:.0f} us a layer ({moved / t_kernel / 1e9:.0f} GB/s of "
              f"state) under ssm.state_step {t_xla * 1e6:.0f} us "
              f"({moved / t_xla / 1e9:.0f} GB/s); by head tile: "
              + ", ".join(f"th{th} {t * 1e6:.0f} us" for th, t in sweep) + "]",
              flush=True)

    # ---- one QLoRA train step, --quant_impl pallas against xla: the fused
    # kernels forward AND backward inside the real step program with remat
    def qlora_step():
        from datatunerx_tpu.models import get_config, init_params
        from datatunerx_tpu.ops.quant import quantize_model_params
        from datatunerx_tpu.training import TrainConfig, Trainer
        from datatunerx_tpu.training.loss import IGNORE_INDEX

        B, T = 4, 128
        out = {}
        for impl in ("pallas", "xla"):
            cfg = get_config("debug", quantization="int4", quant_impl=impl,
                             remat="full")
            tr = Trainer(cfg, TrainConfig(
                finetuning_type="lora", lora_rank=8, lora_alpha=32.0,
                lora_dropout=0.0, lora_targets=("q_proj", "v_proj"),
                learning_rate=2e-4, optimizer="adamw", total_steps=10,
                compute_dtype=jnp.bfloat16))
            params = quantize_model_params(
                init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16),
                "int4")
            state = tr.init_state(params, jax.random.PRNGKey(1))
            toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0,
                                      cfg.vocab_size, jnp.int32)
            labels = jnp.where(jnp.arange(T)[None, :] < T // 4,
                               IGNORE_INDEX, toks)
            state, m = tr.train_step(state,
                                     {"input_ids": toks, "labels": labels})
            out[impl] = (np.asarray(m["loss"], np.float32).reshape(1),
                         np.concatenate([
                             np.asarray(x, np.float32).ravel()
                             for x in jax.tree_util.tree_leaves(state.lora)]))
        check("qlora_step_loss_pallas_vs_xla [debug B4 T128]",
              out["pallas"][0], out["xla"][0], atol=5e-2, rtol=1e-2)
        check("qlora_step_lora_update_pallas_vs_xla [debug B4 T128]",
              out["pallas"][1], out["xla"][1], atol=5e-4, rtol=5e-2)

    for gname, (H, KV, d, D, F) in geoms.items():
        guarded(f"flash [{gname}]", lambda: flash(gname, H, KV, d))
        guarded(f"quant [{gname}]", lambda: quant(gname, D, F))
        guarded(f"lora [{gname}]", lambda: lora(gname, D))
        guarded(f"paged [{gname}]", lambda: paged(gname, H, KV, d))
    guarded("paged_cell", paged_cell)
    guarded("paged_window", paged_window)
    guarded("paged_kinds", paged_kinds)
    guarded("windowed_engine", windowed_engine)
    # both models' vocab 32000 and Qwen's 151936 (several tiles, the last
    # one ragged)
    for V in ((32000, 151936) if not rehearsal else (512,)):
        for S in sorted({SERVE_SLOTS, 16}):
            guarded(f"fused_sample [S{S} V{V}]", lambda: sampler(S, V))
    guarded("moe_gmm", moe_gmm)
    guarded("ssm_step", ssm_step)
    if not rehearsal:  # interpret-mode QLoRA steps are slow and tier-1's job
        guarded("qlora_step", qlora_step)

    print("[runtime] compile_cache "
          + json.dumps(runtime.compile_cache_stats(), sort_keys=True),
          flush=True)
    print(f"{sum(results)}/{len(results)} kernel checks passed", flush=True)
    return 0 if results and all(results) else 1


# ---------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug-size run on the CPU to debug THIS SCRIPT; "
                         "proves nothing about the chip")
    ap.add_argument("--phases", default="trainer,server,kernels,hybrid,ling,glm,kimi",
                    help="comma list out of trainer,server,kernels,hybrid,ling,glm,kimi")
    ap.add_argument("--mesh", action="append", default=None,
                    help="trainer --mesh (e.g. dp=1,fsdp=4,tp=1); repeat to "
                         "run the trainer once per mesh. 'auto' (the "
                         "default) is the trainer's own choice: every local "
                         "device on dp")
    ap.add_argument("--child-kernels", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-hybrid", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-ling", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-glm", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-kimi", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    for var in ("DTX_PALLAS_INTERPRET", "DTX_SAMPLING_EPILOGUE_KERNEL"):
        if os.environ.get(var, "").strip():
            print(f"chip_smoke: refusing to run with {var} set — the smoke "
                  "proves what the backend default resolves to",
                  file=sys.stderr)
            return 2
    if args.child_kernels:
        return child_kernels(args.cpu_rehearsal)
    if args.child_hybrid:
        return child_hybrid(args.cpu_rehearsal)
    if args.child_ling:
        return child_ling(args.cpu_rehearsal)
    if args.child_glm:
        return child_glm(args.cpu_rehearsal)
    if args.child_kimi:
        return child_kimi(args.cpu_rehearsal)
    if not os.path.isdir(os.path.join(REPO, "datatunerx_tpu")):
        print("chip_smoke: no datatunerx_tpu package beside this script",
              file=sys.stderr)
        return 2
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = set(phases) - {"trainer", "server", "kernels", "hybrid", "ling", "glm", "kimi"}
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")

    rehearsal = args.cpu_rehearsal
    if not rehearsal and os.environ.get(
            "JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("chip_smoke: JAX_PLATFORMS=cpu — there is no chip to prove "
              "anything on (the CPU run is --cpu-rehearsal, by name)",
              file=sys.stderr)
        return 2
    if rehearsal:
        _say("CPU REHEARSAL at debug size: this run debugs chip_smoke.py's "
             "own control flow and proves NOTHING about the chip.")
    for path in (OUT, WORK):
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
    _say("chip_smoke: env " + json.dumps(
        {k: os.environ.get(k) for k in
         ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "TPU_VISIBLE_CHIPS",
          "TPU_ACCELERATOR_TYPE", "XLA_FLAGS")}, sort_keys=True))
    t0 = time.monotonic()
    runs = []
    if "trainer" in phases:
        runs += [(f"trainer[{m}]" if m else "trainer",
                  lambda left, m=m: phase_trainer(rehearsal, m, left))
                 for m in ("" if m == "auto" else m
                           for m in (args.mesh or ["auto"]))]
    if "server" in phases:
        runs.append(("server", lambda left: phase_server(rehearsal, left)))
    if "kernels" in phases:
        runs.append(("kernels", lambda left: phase_kernels(rehearsal, left)))
    for name in ("hybrid", "ling", "glm", "kimi"):
        if name in phases:
            runs.append((name, lambda left, name=name: phase_kernels(rehearsal, left, name)))

    devices, failed = [], []
    try:
        for name, run in runs:
            left = DEADLINE_S - (time.monotonic() - t0)
            try:
                if left < 30:
                    raise SmokeFailure(f"{name}: no time left to start it")
                devices.append(run(left))
            except SmokeFailure as e:
                failed.append(name)
                print(f"chip_smoke: FAILED {e}", file=sys.stderr, flush=True)
                if isinstance(e, NoChip):
                    break
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    wall = time.monotonic() - t0
    if failed or not devices:
        print(f"chip_smoke: FAILED phases {failed or 'none ran'} after "
              f"{wall:.0f}s wall", file=sys.stderr, flush=True)
        return 1
    dev = {"platform": devices[0]["platform"],
           "kind": devices[0]["device_kind"], "count": devices[0]["count"]}
    if any((d["platform"], d["device_kind"], d["count"])
           != (dev["platform"], dev["kind"], dev["count"]) for d in devices):
        print(f"chip_smoke: children disagree on the device: {devices}",
              file=sys.stderr)
        return 1
    _say(f"chip_smoke: phases {','.join(n for n, _ in runs)} passed in "
         f"{wall:.0f}s wall")
    if rehearsal:
        _say(json.dumps({"rehearsal": "cpu — proves nothing about the chip",
                         "device": dev}))
        return 0
    _say(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
