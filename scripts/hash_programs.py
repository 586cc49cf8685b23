"""Which serving programs did a change touch? Hashes what two checkouts of
this repo compile, and says SAME or DIFF a program.

    python3 scripts/hash_programs.py --parent .scratch/parent [--only debug-hybrid]

``--parent`` is a checkout of the commit to compare with (``git archive
<commit> | tar -x -C .scratch/parent``); the other side is the checkout this
file lies in. A case is a debug preset served by ``BatchedEngine`` on the CPU
backend with ``paged_kernel`` on or off (a prompt of three chunks, six tokens
out), each side in a process of its own; ``debug.kernels+prefix`` and
``debug.kernels+overcommit`` serve a second session whose requests share
prefixes and outgrow a small pool, so that the prefix-cache, copy-on-write,
growth, preemption and resume paths are in it (``events``). A program is the
optimized HLO of one of the engine's jitted programs, less what an edit moves
without changing the program: op metadata (scopes, source lines) and the
stack-frame tables. Every OTHER module the session compiled (the eager
scatters and slices of the scheduler, the row programs, set-up) is on the
``(other modules)`` line: their count and one digest over their sorted hashes.
The Pallas kernels are emulated there, so the hash covers a kernel's body too.
``kernel/*`` cases hash the lowered text of the paged decode kernel's call
alone. Nothing here is timed and nothing needs the chip: a program that
hashes as the parent's is the parent's program, whatever the diff says.

Exit code 1 if a side failed to run, else 0: what SHOULD differ is the
reader's to judge (CHANGES.md names it a PR).
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import re
import subprocess
import sys
import tempfile
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# case -> (preset, paged_kernel, further engine keywords)
ENGINES = {
    f"{preset}.{'kernels' if mode == 'on' else 'gather'}": (preset, mode, {})
    for preset in ("debug", "debug-hybrid", "debug-ling", "debug-granite", "debug-glm")
    for mode in ("on", "off")
}
# 24 blocks under two slots of 16 columns: two sessions outgrow the pool, and
# a cold admission reclaims the blocks idle entries hold (``reclaim_entry``)
ENGINES["debug.kernels+prefix"] = ("debug", "on", {"prefix_cache": 4})
ENGINES["debug.kernels+overcommit"] = (
    "debug", "on", {"prefix_cache": 4, "kv_overcommit": "on", "kv_blocks": 24})
# a model of several layer kinds under the prefix cache: blocks of a latent pool
# (no kernel takes its token step, and this session runs what a plain one does)
ENGINES["debug-kimi.gather+overcommit"] = (
    "debug-kimi", "off", {"prefix_cache": 4, "kv_overcommit": "on", "kv_blocks": 24})
# case -> keywords of ``paged_decode_attention`` over bf16 pools of one width
KERNELS = {"kernel/paged_decode": {}, "kernel/paged_decode_window": {"window": 100}}
PROGRAMS = "decode_impl|prefill_chunk_impl|activate_impl|install_table"
OTHER = "(other modules)"


def _strip(text: str) -> str:
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    # the stack-frame tables at the module's head
    text = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n", "", text)
    return re.sub(r"^HloModule [^\n]*\n", "", text)


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def child(root: str, case: str) -> None:
    dump = tempfile.mkdtemp(prefix="hlo_")
    os.environ["XLA_FLAGS"] = f"--xla_dump_to={dump} --xla_dump_hlo_as_text"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    os.chdir(root)
    sys.path.insert(0, root)
    out = {"case": case, "programs": {}}
    if case in KERNELS:
        import jax
        import jax.numpy as jnp

        from datatunerx_tpu.ops.pallas_paged_attention import paged_decode_attention

        B, KV, G, d, nbps, NB, bs = 4, 2, 4, 16, 40, 8, 8
        pool = jnp.zeros((3, NB, bs, KV * d), jnp.bfloat16)
        ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
        text = jax.jit(lambda q: paged_decode_attention(
            q, pool, pool, None, None, 1, ints(B, nbps), ints(NB, bs), ints(B), ints(B),
            **KERNELS[case])).lower(jnp.zeros((B, KV * G, d), jnp.bfloat16)).as_text()
        out["programs"]["lowered"] = [_digest(text)]
    else:
        from datatunerx_tpu.serving.batched_engine import BatchedEngine

        preset, mode, more = ENGINES[case]
        kw = dict(max_seq_len=256, slots=2, decode_chunk=4, kv_block_size=16,
                  prefill_chunk=64, paged_kernel=mode, **more)
        if preset == "debug":
            kw["template"] = "vanilla"
        eng = BatchedEngine("preset:" + preset, **kw)
        try:
            out["tokens"] = eng.generate(list(range(3, 3 + 150)), max_new_tokens=6)
            if more:
                out["tokens"] += _shared_prefix_session(eng)
                out["events"] = sorted({e[0] for e in eng.sched_trace}
                                       | {"admit:" + e[3] for e in eng.sched_trace
                                          if e[0] == "admit"})
            out["decode_path"] = eng.decode_path
        finally:
            eng.close()
        other = []
        for f in sorted(glob.glob(os.path.join(dump, "*after_optimizations.txt"))):
            name = re.sub(r"^module_\d+\.", "", os.path.basename(f)).split(".")[0]
            with open(f) as fh:
                digest = _digest(_strip(fh.read()))
            if re.search(PROGRAMS, name):
                out["programs"].setdefault(name, []).append(digest)
            else:
                other.append(digest)
        out["programs"][OTHER] = [len(other), _digest(" ".join(sorted(other)))]
    print("RESULT " + json.dumps(out))


def _shared_prefix_session(eng) -> list:
    """An exact prefix hit, a strict-prefix hit, then two requests at once
    whose decode outgrows the pool (the younger is parked and resumed)."""
    base = list(range(3, 3 + 90))
    tokens = eng.generate(base, max_new_tokens=4)
    tokens += eng.generate(base, max_new_tokens=4)
    tokens += eng.generate(base + list(range(200, 230)), max_new_tokens=4)
    reqs = [eng.submit(list(range(lo, lo + 100)), max_new_tokens=100)
            for lo in (40, 500)]
    for req in reqs:
        req.done.wait(600)
        tokens += req.tokens
    return tokens


def _run(root: str, case: str):
    p = subprocess.run([sys.executable, os.path.abspath(__file__), "--child", root, case],
                       capture_output=True, text=True)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(lines[0][7:]) if lines else {"failed": p.stderr[-1500:]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a checkout of the commit to compare with")
    ap.add_argument("--only", default="", help="cases whose name holds this")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--child", nargs=2, metavar=("ROOT", "CASE"), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(*args.child)
        return 0
    if not args.parent:
        ap.error("--parent is required")
    roots = {"parent": os.path.abspath(args.parent), "change": HERE}
    cases = [c for c in list(KERNELS) + list(ENGINES) if args.only in c]
    with ThreadPoolExecutor(args.jobs) as pool:
        jobs = {(case, side): pool.submit(_run, root, case)
                for case in cases for side, root in roots.items()}
    bad = 0
    for case in cases:
        a, b = (jobs[(case, side)].result() for side in roots)
        for side, doc in zip(roots, (a, b)):
            if "failed" in doc:
                bad += 1
                print(f"== {case}: {side} FAILED\n{doc['failed']}")
        if "failed" in a or "failed" in b:
            continue
        print(f"== {case}: decode_path {a.get('decode_path')} -> {b.get('decode_path')}; "
              f"tokens equal: {a.get('tokens') == b.get('tokens')}"
              + (f"; events {' '.join(b['events'])}" if "events" in b else ""))
        for name in sorted(set(a["programs"]) | set(b["programs"])):
            ha, hb = a["programs"].get(name), b["programs"].get(name)
            print(f"   {name:40s} {'SAME' if ha == hb else 'DIFF'} {ha} {hb}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
