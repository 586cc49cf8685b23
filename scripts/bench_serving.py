"""Serving throughput benchmark (VERDICT r2 next-round #7): continuous-batching
decode tokens/s vs slot count, plus the prefix-cache hit path.

The serving half of the parity story — the reference serves via Ray Serve
LlamaDeployment replicas (reference pkg/util/generate/generate.go:160-329);
here one BatchedEngine decodes S slots inside a single jitted program.

Prints one JSON line per configuration:
  {"metric": "serving_decode_tokens_per_sec[tinyllama-1.1b,slots=4]", ...}
plus a prefix-cache line (admission latency with/without a warm prefix).

CPU fallback: marked "cpu_fallback": true with the debug preset (shape
signal only, no TPU claim) — same honesty contract as bench.py.

Run: python scripts/bench_serving.py [--slots 1,4,8] [--tokens 128]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_slots(model: str, slots: int, gen_tokens: int, prompt_len: int,
                max_seq: int, cpu_fallback: bool) -> dict:
    """Saturate all S slots with concurrent requests; measure aggregate
    emitted tokens/s from submit of the batch to last completion."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine(model, template="vanilla", max_seq_len=max_seq,
                        slots=slots, decode_chunk=8)
    try:
        import numpy as np

        rng = np.random.default_rng(0)
        prompts = [
            [int(t) for t in rng.integers(10, 1000, prompt_len)]
            for _ in range(slots)
        ]
        # warmup: compile prefill + decode chunks, fill each slot once
        for p in prompts[:1]:
            eng.generate(p, max_new_tokens=8, timeout=900)

        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=gen_tokens, temperature=0.0,
                           stop_ids={-1})  # unreachable stop: full budget
                for p in prompts]
        total = 0
        for r in reqs:
            if not r.done.wait(timeout=900):
                raise TimeoutError("decode timed out")
            if r.error:
                raise RuntimeError(r.error)
            total += len(r.tokens)
        dt = time.perf_counter() - t0
        tag = f"{model.split(':')[-1]},slots={slots},gen={gen_tokens}"
        lines = [
            {
                "metric": f"serving_decode_tokens_per_sec[{tag}]",
                "value": round(total / dt, 1),
                "unit": "tokens/s",
                "vs_baseline": None,
            },
            # per-slot steady-state decode rate: the number that composes
            # across TPU runs and slot counts (VERDICT r3 #8)
            {
                "metric": f"serving_decode_tokens_per_sec_per_slot[{tag}]",
                "value": round(total / dt / slots, 1),
                "unit": "tokens/s/slot",
                "vs_baseline": None,
            },
        ]
        if cpu_fallback:
            for line in lines:
                line["cpu_fallback"] = True
        return lines
    finally:
        eng.close()


def bench_prefix_cache(model: str, prompt_len: int, max_seq: int,
                       cpu_fallback: bool) -> dict:
    """Admission cost with a warm longest-prefix hit vs a cold full prefill:
    the trie lookup + suffix-extension path end-to-end."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine(model, template="vanilla", max_seq_len=max_seq,
                        slots=2, decode_chunk=4, prefix_cache=8)
    try:
        import numpy as np

        rng = np.random.default_rng(1)
        base = [int(t) for t in rng.integers(10, 1000, prompt_len)]
        tail1 = [int(t) for t in rng.integers(10, 1000, 16)]
        tail2 = [int(t) for t in rng.integers(10, 1000, 16)]

        eng.generate(base, max_new_tokens=1, timeout=900)  # warm prefill+cache
        # first extension COMPILES the suffix-extension program — warm it so
        # the timed run measures steady-state admission, not XLA compile
        eng.generate(base + tail1, max_new_tokens=1, timeout=900)

        t0 = time.perf_counter()
        eng.generate(base + tail2, max_new_tokens=1, timeout=900)
        warm = time.perf_counter() - t0
        assert eng.prefill_stats["extend"] >= 2, eng.prefill_stats

        cold_eng_stats = dict(eng.prefill_stats)
        rng2 = np.random.default_rng(2)
        cold_prompt = [int(t) for t in rng2.integers(10, 1000,
                                                     prompt_len + 16)]
        t0 = time.perf_counter()
        eng.generate(cold_prompt, max_new_tokens=1, timeout=900)
        cold = time.perf_counter() - t0
        assert eng.prefill_stats["full"] == cold_eng_stats["full"] + 1

        tag = f"{model.split(':')[-1]},prompt={prompt_len}"
        lines = [
            # absolute admission latencies in ms (VERDICT r3 #8): these
            # compose with TPU runs directly, unlike the ratio
            {
                "metric": f"serving_admission_latency_ms[{tag},warm_prefix]",
                "value": round(warm * 1e3, 2),
                "unit": "ms",
                "vs_baseline": None,
            },
            {
                "metric": f"serving_admission_latency_ms[{tag},cold]",
                "value": round(cold * 1e3, 2),
                "unit": "ms",
                "vs_baseline": None,
            },
            {
                "metric": f"serving_prefix_hit_speedup[{tag}]",
                "value": round(cold / max(warm, 1e-9), 2),
                "unit": "x (cold prefill / warm suffix-extension latency)",
                "vs_baseline": None,
            },
        ]
        if cpu_fallback:
            for line in lines:
                line["cpu_fallback"] = True
        return lines
    finally:
        eng.close()


def bench_multi_adapter(model: str, n_adapters: int, gen_tokens: int,
                        prompt_len: int, max_seq: int,
                        cpu_fallback: bool) -> list:
    """BASELINE row 6 schema: N tuned checkpoints served side-by-side by ONE
    engine (stacked adapters, per-slot indexing) — per-adapter admission
    latency + per-slot decode tok/s while all N decode concurrently."""
    import tempfile

    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    tag = f"{model.split(':')[-1]},adapters={n_adapters}"
    with tempfile.TemporaryDirectory() as tmp:
        paths = {
            f"a{i}": make_adapter_checkpoint(f"{tmp}/ckpt{i}", model, seed=i,
                                             rank=8)
            for i in range(n_adapters)
        }
        eng = BatchedEngine(model, adapters=paths, template="vanilla",
                            max_seq_len=max_seq, slots=n_adapters,
                            decode_chunk=8)
        try:
            import numpy as np

            rng = np.random.default_rng(3)
            prompts = {name: [int(t) for t in rng.integers(10, 1000,
                                                           prompt_len)]
                       for name in paths}
            # warm compile (prefill + decode with adapter indexing)
            eng.generate(prompts["a0"], max_new_tokens=4, adapter="a0",
                         timeout=900)

            lines = []
            # per-adapter admission latency: prefill + first token
            for name in paths:
                t0 = time.perf_counter()
                eng.generate(prompts[name], max_new_tokens=1, adapter=name,
                             timeout=900)
                lines.append({
                    "metric": (f"serving_admission_latency_ms[{tag},"
                               f"slot={name}]"),
                    "value": round((time.perf_counter() - t0) * 1e3, 2),
                    "unit": "ms",
                    "vs_baseline": None,
                })

            # concurrent decode: one request per adapter, all slots busy
            t0 = time.perf_counter()
            reqs = {name: eng.submit(prompts[name],
                                     max_new_tokens=gen_tokens,
                                     temperature=0.0, stop_ids={-1},
                                     adapter=name)
                    for name in paths}
            per_slot = {}
            for name, r in reqs.items():
                if not r.done.wait(timeout=900):
                    raise TimeoutError(f"adapter {name} decode timed out")
                if r.error:
                    raise RuntimeError(r.error)
                per_slot[name] = len(r.tokens)
            dt = time.perf_counter() - t0
            for name, n_tok in sorted(per_slot.items()):
                lines.append({
                    "metric": (f"serving_multi_adapter_decode_tokens_per_sec"
                               f"[{tag},slot={name}]"),
                    "value": round(n_tok / dt, 1),
                    "unit": "tokens/s",
                    "vs_baseline": None,
                })
            lines.append({
                "metric": (f"serving_multi_adapter_decode_tokens_per_sec"
                           f"[{tag},aggregate]"),
                "value": round(sum(per_slot.values()) / dt, 1),
                "unit": "tokens/s",
                "vs_baseline": None,
            })
            if cpu_fallback:
                for line in lines:
                    line["cpu_fallback"] = True
            return lines
        finally:
            eng.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="1,4,8")
    ap.add_argument("--tokens", type=int, default=128)
    ap.add_argument("--prompt_len", type=int, default=64)
    args = ap.parse_args()

    import jax

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        model, max_seq = "preset:tinyllama-1.1b", 1024
        gen_tokens, prompt_len = args.tokens, args.prompt_len
    else:
        model, max_seq = "preset:debug", 256
        gen_tokens, prompt_len = min(args.tokens, 32), min(args.prompt_len, 32)

    results = []
    for s in [int(x) for x in args.slots.split(",") if x]:
        for line in bench_slots(model, s, gen_tokens, prompt_len, max_seq,
                                cpu_fallback=not on_tpu):
            print(json.dumps(line), flush=True)
            results.append(line)
    for line in bench_prefix_cache(model, prompt_len, max_seq,
                                   cpu_fallback=not on_tpu):
        print(json.dumps(line), flush=True)
        results.append(line)
    for line in bench_multi_adapter(model, 3, gen_tokens, prompt_len, max_seq,
                                    cpu_fallback=not on_tpu):
        print(json.dumps(line), flush=True)
        results.append(line)

    if on_tpu:
        from datetime import datetime, timezone

        doc = {
            "timestamp": datetime.now(timezone.utc).isoformat(
                timespec="seconds"),
            "hardware": jax.devices()[0].device_kind,
            "lines": results,
        }
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_SERVING_TPU.json")
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"[bench_serving] wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
