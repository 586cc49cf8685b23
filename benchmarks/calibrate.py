"""Readings from which the limits of ``correct`` are set, many seeds in ONE
process (set-up is most of a run): for each seed the numbers that the sound
program gives, and the numbers of the control, the plain reference computed in
int8 and put in the program's place. A limit goes above the sound runs' largest
and below the control's smallest (PERF.md gives the readings beside each limit).

    python3 benchmarks/calibrate.py --workload <cell> --seeds 101,102,... --seconds 12
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control_seeds", type=int, default=10**9,
                    help="read the control on the first N seeds only (it needs three or more)")
    args = ap.parse_args()

    import spec as spec_mod
    from common import CompileCounter, Context, log, open_device

    cell = spec_mod.load_cell(args.workload)
    device = open_device()
    kind = spec_mod.load_module("traffic", "kinds", cell.kind + ".py")
    counter = CompileCounter()
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        ctx = Context(cell=cell, seed=seed, seconds=args.seconds, trace=False,
                      on_cpu=device["platform"] == "cpu", device=device, t_process=time.perf_counter(),
                      trace_dir="", counter=counter)
        control = i < args.control_seeds
        if hasattr(kind, "readings"):
            r = kind.readings(ctx, control)
        else:
            import serving

            r = serving.readings(ctx, kind, control)
        rows.append(dict(r, seed=seed))
        log(f"[calibrate] {json.dumps(rows[-1])}")
    keys = [k for k, v in rows[0]["sound"].items() if isinstance(v, float)]
    summary = {}
    for k in keys:
        sound = [r["sound"][k] for r in rows]
        control = [r["control"][k] for r in rows if (r.get("control") or {}).get(k) is not None]
        summary[k] = {"sound_max": max(sound), "sound_min": min(sound),
                      "control_min": min(control) if control else None,
                      "control_max": max(control) if control else None,
                      "ratio": (min(control) / max(sound)) if control and max(sound) > 0 else None}
    print(json.dumps({"cell": cell.name, "device": device, "seeds": len(rows), "summary": summary}))
    return 0


if __name__ == "__main__":
    from common import exit_now

    exit_now(main())
