"""Find the knee of an open-loop serving cell once: the same engine and
set-up, the cell's traffic at each of a few fixed rates, one window each. The
knee is the highest rate at which the queue does not grow through the window
(requests still waiting at its end stay a handful) and the tails stay flat.
A cell then runs at about four fifths of it. README.md keeps the sweep that
set the rate in use.

    python3 benchmarks/sweep.py --workload qwen-serve-steady --rates 4,6,8,10,12,14 --seconds 20
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import numpy as np

    import serving
    import spec as spec_mod
    from common import CompileCounter, Context, log, open_device

    cell = spec_mod.load_cell(args.workload)
    device = open_device()
    kind = spec_mod.load_module("traffic", "kinds", cell.kind + ".py")
    rates = [float(r) for r in args.rates.split(",") if r]
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds, trace=False,
                  on_cpu=device["platform"] == "cpu", device=device, t_process=T_PROCESS,
                  trace_dir="", counter=CompileCounter())
    engine, lora, names = serving.build_engine(cell, args.seed)
    vocab = engine.cfg.vocab_size
    plans = {}
    for rate in rates:
        cell.traffic["rate_rps"] = rate
        plans[rate] = kind.plan(cell, args.seed + int(rate * 100), args.seconds, names, vocab)
    # every rate's plan has its own set of sizes: warm them all, or a window compiles
    serving.warm_up(engine, [r for p in plans.values() for r in p["requests"]], vocab, args.seed)
    rows = []
    for rate in rates:
        plan = plans[rate]
        records, w0, w1, _ = serving.window(ctx, engine, kind, plan)
        red = serving.reduce_records(records, w0, w1)
        waiting = sum(1 for r in records if r.first is None or r.first > w1)
        row = {"rate_rps": rate, "attempted": red["attempted"], "failed": red["failed"],
               "unstarted_at_window_end": waiting, "compiles": ctx.compiles_in_window(),
               "late_ms_max": max(red["late_ms"]) if red["late_ms"] else None}
        if device["platform"] != "cpu":
            row.update({k: float(np.percentile(red[src], q)) for k, src, q in (
                ("ttft_p50_ms", "ttft_ms", 50), ("ttft_p95_ms", "ttft_ms", 95),
                ("tpot_p50_ms", "tpot_ms", 50), ("tpot_p95_ms", "tpot_ms", 95))},
                tok_s=red["tokens_finished_in_window"] / args.seconds)
        rows.append(row)
        log(f"[sweep] {json.dumps(row)}")
    serving.release(engine)
    print(json.dumps({"cell": cell.name, "device": device, "seconds": args.seconds, "rows": rows}))
    return 0


if __name__ == "__main__":
    from common import exit_now

    exit_now(main())
