"""One process, one cell, one run:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell's files by name (``spec.py``), runs the traffic kind's driver in
this process, and prints as its last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``. Every number compared for ``correct`` is printed beside its limit.

Without a chip it exits non-zero, unless ``JAX_PLATFORMS=cpu`` asked for the CPU
by name: then it is a rehearsal of the harness, prints ``platform: cpu``,
counts and ``correct`` only, and writes no time or rate under a metric's name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

TRAINING_KINDS = ("packed-docs",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import spec as spec_mod
    from common import CompileCounter, Context, log, open_device

    cell = spec_mod.load_cell(args.workload)
    device = open_device()
    on_cpu = device["platform"] == "cpu"
    if device["count"] < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, JAX sees {device['count']}")
        return 3
    if on_cpu and cell.listed:
        log(f"cell {cell.name} is a chip cell; on the CPU run a rehearsal-* workload")
        return 3
    log(f"[bench] platform: {device['platform']} kind: {device['kind']} count: {device['count']} "
        f"cell: {cell.name} seed: {args.seed} seconds: {args.seconds} trace: {args.trace}")

    trace_dir = os.path.join(HERE, ".trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                  on_cpu=on_cpu, device=device, t_process=T_PROCESS, trace_dir=trace_dir,
                  counter=CompileCounter())

    kind = spec_mod.load_module("traffic", "kinds", cell.kind + ".py")
    if hasattr(kind, "run"):
        out = kind.run(ctx)
    else:
        import serving

        out = serving.run(ctx, kind)

    ok = True
    for name, value, limit, sense in out["checks"]:
        passed = value <= limit if sense == "max" else value >= limit
        ok &= bool(passed)
        log(f"[check] {name} = {value!r} ({'<=' if sense == 'max' else '>='} {limit!r}) "
            f"{'ok' if passed else 'FAILED'}")
    timed = ("_ms", "_s", "_ms_max")  # a rehearsal on the CPU prints what was counted, nothing timed
    log("[bench] reduced: " + json.dumps({
        k: v for k, v in out["reduced"].items()
        if not isinstance(v, list) and not (on_cpu and k.endswith(timed))}))
    if out["observed"].check:
        log(f"[bench] output check: {json.dumps(out['observed'].check)}")

    result = {"correct": bool(ok), "attempted": int(out["attempted"]), "failed": int(out["failed"]),
              "metrics": {}, "device": dict(device, memory_peak_bytes=int(out["memory_peak_bytes"]))}
    candidates = dict(out["kind_metrics"], setup_s=ctx.setup_s)
    if on_cpu:
        # a rehearsal: what was counted, nothing that was timed
        log(f"[bench] rehearsal on the CPU: kind metrics {sorted(candidates)} computed and withheld")
        result["rehearsal"] = True
    elif not args.trace:
        log(f"[bench] all kind metrics: {json.dumps(candidates)}")
        for m in cell.end_to_end:
            if candidates.get(m["name"]) is None:
                log(f"end-to-end metric {m['name']} was not produced")
                return 4
            result["metrics"][m["name"]] = {"value": candidates[m["name"]], "unit": m["unit"]}
    else:
        import trace_reduce

        obs = out["observed"]
        obs.peaks = spec_mod.peaks_for(device["kind"])
        obs.flat = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        lo, hi = trace_reduce.window_of(obs.flat, "bench_window")
        obs.trace_clock = (lo, hi)
        busy = trace_reduce.busy_idle(obs.flat, lo, hi)
        result["device"].update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        for m in cell.per_layer:
            reader = spec_mod.load_module("metrics", m["name"] + ".py")
            value = reader.read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        spans = ("dtx_engine_decode", "dtx_engine_prefill_chunk", "dtx_host_prefetch_build",
                 "dtx_device_prefetch_put", "bench_submit", "bench_next_batch", "bench_wait_step")
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(obs.flat, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps_by_host_span(obs.flat, lo, hi, spans)}
        # the raw trace is tens of megabytes: keep what a reader of the next trace
        # needs (names and seconds) inside the checkout, and drop the rest
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        with open(os.path.join(trace_dir, "inventory.json"), "w") as f:
            json.dump(trace_reduce.inventory(obs.flat, 80), f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    from common import exit_now

    exit_now(main())
