"""By hand, on the chip: prefill in chunks and then decode through the paged KV
pools of a model of several layer kinds, against the plain reference's full
forward pass, as LOGITS (not tokens), at the published widths.

    python3 scripts/check_hybrid_logits.py --config mimo-v2.5-l7-ep16 --seed 7

Draws the configuration's weights and two adapters from the seed (the
benchmark's draws), runs ``models.forward`` as the engine runs it (bf16,
prefill chunks of 256 into a block pool, then single-token steps, window
layers through their window-wide view), once for the base and once for an
adapter, and prints the largest and the mean absolute difference of the logits
at the last prompt position and at every decoded position, beside the spread
of the reference's own logits. The contexts pass the window several times over,
so both kinds of layer are read past their first blocks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mimo-v2.5-l7-ep16")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prompt", type=int, default=768)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--block", type=int, default=16)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import spec as spec_mod
    from common import open_device

    from datatunerx_tpu.models import forward
    from datatunerx_tpu.ops.paged_attention import init_paged_cache

    device = open_device()
    with open(os.path.join(ROOT, "benchmarks", "configs", args.config + ".json")) as f:
        config = json.load(f)
    spec_mod.check_config(config)
    cell = spec_mod.Cell(name="by-hand", config_name=args.config, traffic_name="", chips=1,
                         workload={}, config=config, traffic={}, listed=False,
                         end_to_end=[], per_layer=[])
    cfg = spec_mod.register_preset(cell)
    mc = cell.model_fields
    weights = spec_mod.load_module(*config["weights_module"].split("/"))
    reference = spec_mod.load_module(*config["reference_module"].split("/"))
    params = weights.draw_params(mc, args.seed)
    rank, alpha = 8, 32.0
    lora = weights.draw_lora(mc, args.seed, count=2, rank=rank, targets=["q_proj", "v_proj"],
                             b_std=0.05)
    # the engine's stack: entry 0 the all-zero base, [n, E, d_in, r]
    stack = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([jnp.zeros_like(a[:1]), a], axis=0).swapaxes(0, 1), lora)
    scales = jnp.asarray([0.0, alpha / rank, alpha / rank], jnp.float32)

    T = args.prompt + args.steps
    nbps = -(-T // args.block) + 1
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(10, mc["vocab_size"], size=T).tolist()

    @jax.jit
    def run(params, stack, cache, toks, pos, idx):  # weights as arguments, never closed over
        return forward(params, toks, cfg, positions=pos, cache=cache,
                       lora=({"layers": stack}, scales), lora_adapter_idx=idx,
                       compute_dtype=jnp.bfloat16)

    def step(cache, toks, pos, idx):
        return run(params, stack, cache, toks, pos, idx)

    out = {"device": device, "config": args.config, "seed": args.seed,
           "prompt": args.prompt, "steps": args.steps}
    for label, adapter in (("base", 0), ("adapter", 1)):
        cache = init_paged_cache(cfg, 1, nbps + 2, args.block, nbps, dtype=jnp.bfloat16)
        cache["block_tables"] = jnp.arange(2, nbps + 2, dtype=jnp.int32)[None]
        idx = jnp.asarray([adapter], jnp.int32)
        got = []
        for lo in range(0, args.prompt, args.chunk):
            hi = min(lo + args.chunk, args.prompt)
            logits, cache = step(cache, jnp.asarray([tokens[lo:hi]], jnp.int32),
                                 jnp.arange(lo, hi, dtype=jnp.int32)[None], idx)
        got.append(logits[0, -1])
        for t in range(args.prompt, T - 1):
            logits, cache = step(cache, jnp.asarray([[tokens[t]]], jnp.int32),
                                 jnp.asarray([[t]], jnp.int32), idx)
            got.append(logits[0, -1])
        got = jnp.stack(got)
        rows = list(range(args.prompt - 1, T - 1))
        ll = jax.tree_util.tree_map(lambda a: a[adapter - 1], lora) if adapter else None
        want = reference.sequence_logits(params, mc, tokens, rows, ll, alpha / rank)
        diff = jnp.abs(got - want)
        top = jnp.max(want, axis=-1)
        served = jnp.take_along_axis(want, jnp.argmax(got, axis=-1)[:, None], axis=-1)[:, 0]
        out[label] = {"max_abs_diff": float(diff.max()), "mean_abs_diff": float(diff.mean()),
                      "reference_logit_std": float(jnp.std(want)),
                      "reference_top_minus_second": float(jnp.mean(
                          top - jnp.sort(want, axis=-1)[:, -2])),
                      "gap_max": float(jnp.max(top - served)),
                      "argmax_agree": float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1))),
                      "positions": len(rows)}
        stats = np.asarray(cache["moe_stats"])
        out[label]["moe_stats"] = stats.tolist()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
