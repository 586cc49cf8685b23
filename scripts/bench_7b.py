"""7B-scale single-chip proof (BASELINE.json configuration 1).

Llama-2-7B architecture, nf4-quantized base + LoRA, one v5e chip:
init + quantize on host (7B bf16 = 13.5 GB; nf4 ≈ 3.5 GB fits the 16 GB HBM
with remat'd activations), then time train steps on the device.

Prints one JSON line per measured config:
  {"metric": "qlora_sft_tokens_per_sec_per_chip[llama2-7b,...]", ...}

Run: python scripts/bench_7b.py [--batch 4] [--seq 1024] [--steps 10]
     [--attention flash] [--quant_impl xla|pallas]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _tree_flatten_paths(tree):
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(p.key for p in path)
        out[key] = leaf
    return out


def _npz_path(path):
    return path if path.endswith(".npz") else path + ".npz"  # savez appends


def _cache_format():
    # a cache from an older nf4 layout would silently reintroduce the tile-
    # padding HBM OOM the flat-byte layout fixed — version the file and
    # requantize on any mismatch
    from datatunerx_tpu.ops.quant import NF4_LAYOUT_VERSION

    return {"mode": "int4", "nf4_layout": NF4_LAYOUT_VERSION,
            "packed_flat": True}


def _save_cached(path, params):
    import json

    import numpy as np

    import jax

    flat, dtypes = {}, {}
    for k, v in _tree_flatten_paths(params).items():
        arr = np.asarray(jax.device_get(v))
        dtypes[k] = str(arr.dtype)
        if arr.dtype.name == "bfloat16":  # npy can't portably store bf16
            arr = arr.astype(np.float32)
        flat[k] = arr
    flat["__dtypes__"] = np.asarray(json.dumps(dtypes))
    flat["__format__"] = np.asarray(json.dumps(_cache_format()))
    np.savez(_npz_path(path), **flat)


def _load_cached(path):
    import json
    import os

    import numpy as np

    import jax.numpy as jnp

    path = _npz_path(path)
    if not os.path.exists(path):
        return None
    z = np.load(path)
    if "__format__" not in z.files or \
            json.loads(str(z["__format__"])) != _cache_format():
        print(f"[cache] {path}: stale/unversioned format — requantizing",
              file=sys.stderr)
        return None
    dtypes = json.loads(str(z["__dtypes__"]))
    tree = {}
    for key in z.files:
        if key == "__dtypes__":
            continue
        node = tree
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(z[key]).astype(dtypes[key])
    return tree


def _fast_host_init(cfg, init_params, seed: int):
    """Throughput-bench init: same param TREE as init_params (via eval_shape)
    but leaves filled with numpy's PCG64 instead of jax's counter-based
    threefry — ~50× faster on a single host core, and a 7B threefry init
    takes half an hour there. Values only need plausible scale for a
    tokens/sec measurement, not reproducibility against training runs."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    abstract = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def fill(path, s):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "scale":   # rms-norm scales init to 1
            return jnp.ones(s.shape, s.dtype)
        if name == "bias":
            return jnp.zeros(s.shape, s.dtype)
        w = rng.standard_normal(s.shape, dtype=np.float32) * 0.02
        return jnp.asarray(w, s.dtype)

    return jax.tree_util.tree_map_with_path(fill, abstract)


def _synth_packed_init(cfg, init_params, seed: int):
    """Direct synthesis of the QUANTIZED param tree — random packed nf4 bytes
    with plausible scales, no bf16 materialization and no quantize pass.
    Throughput-only: the compiled program is byte-identical to one fed real
    quantized weights (same shapes/dtypes), so tokens/sec is unaffected, and
    init drops from ~40 min (threefry+quantize) to seconds. Loss values are
    meaningless; use the cache/--real_quant path for numerics."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from datatunerx_tpu.ops.quant import NF4_BLOCK, NF4_LAYOUT_VERSION

    abstract = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def fill(path, s):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "scale":
            return jnp.ones(s.shape, s.dtype)
        if name == "bias":
            return jnp.zeros(s.shape, s.dtype)
        w = rng.standard_normal(s.shape, dtype=np.float32) * 0.02
        return jnp.asarray(w, s.dtype)

    full = jax.tree_util.tree_map_with_path(fill, abstract)
    # replace the stacked transformer kernels with synthesized packed leaves
    from datatunerx_tpu.ops.quant import QUANT_KERNELS

    layers = dict(full["layers"])
    for kname in QUANT_KERNELS:
        proj = dict(layers[kname])
        kern = proj.pop("kernel")
        L, in_dim, out_dim = kern.shape
        del kern
        nb = in_dim * out_dim // NF4_BLOCK
        packed = rng.integers(0, 256, (L, nb * NF4_BLOCK // 2), dtype=np.uint8)
        scale_q = rng.integers(1, 128, (L, nb), dtype=np.int8)
        meta = np.stack(
            [np.full((L,), 0.08 / 127.0, np.float32),
             np.full((L,), NF4_LAYOUT_VERSION, np.float32)], axis=1)
        proj["quant"] = {
            "packed": jnp.asarray(packed),
            "scale_q": jnp.asarray(scale_q),
            "meta": jnp.asarray(meta),
        }
        layers[kname] = proj
    full = dict(full)
    full["layers"] = layers
    return full


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--attention", default="flash", choices=["xla", "flash"])
    ap.add_argument("--quant_impl", default="pallas",
                    choices=["xla", "pallas"],
                    help="pallas = fused nf4 kernels fwd+bwd (weights stay "
                         "packed in HBM; round-3 default), xla = dequant+dot "
                         "(the round-2 709 tok/s/chip path)")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--cache", default="/tmp/bench7b_params.npz",
                    help="quantized-params disk cache ('' disables): host "
                         "init+quantize of 7B costs ~40 min on one core, "
                         "variant sweeps shouldn't pay it twice")
    ap.add_argument("--real_quant", action="store_true",
                    help="on cache miss, do the real init+quantize pass "
                         "instead of synthesizing packed bytes (slow; only "
                         "needed when loss values must be meaningful)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from datatunerx_tpu.models import get_config, init_params
    from datatunerx_tpu.ops.quant import quantize_model_params
    from datatunerx_tpu.training import TrainConfig, Trainer
    from datatunerx_tpu.training.loss import IGNORE_INDEX

    assert jax.default_backend() == "tpu", "7B bench needs the real chip"
    cpu = jax.devices("cpu")[0]

    cfg = get_config(
        "llama2-7b", remat=args.remat, attention_impl=args.attention,
        quantization="int4", quant_impl=args.quant_impl,
    )

    t0 = time.perf_counter()
    params = _load_cached(args.cache) if args.cache else None
    if params is None:
        with jax.default_device(cpu):
            if args.real_quant:
                params = _fast_host_init(cfg, init_params, seed=0)
                params = quantize_model_params(params, "int4")
            else:
                params = _synth_packed_init(cfg, init_params, seed=0)
            jax.block_until_ready(params)
        if args.cache and args.real_quant:
            _save_cached(args.cache, params)
    print(f"host init+quantize: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    tr = Trainer(
        cfg,
        TrainConfig(
            finetuning_type="lora", lora_rank=8, lora_alpha=32.0,
            lora_dropout=0.05, lora_targets=("q_proj", "v_proj"),
            learning_rate=2e-4, scheduler="cosine", optimizer="adamw",
            total_steps=1000, compute_dtype=jnp.bfloat16,
        ),
    )
    t0 = time.perf_counter()
    params = jax.device_put(params, jax.devices()[0])
    state = tr.init_state(params, jax.random.PRNGKey(1))
    print(f"device transfer + opt init: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    B, T = args.batch, args.seq
    toks = jax.random.randint(
        jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size, jnp.int32)
    labels = jnp.where(jnp.arange(T)[None, :] < T // 8, IGNORE_INDEX, toks)
    batch = {"input_ids": toks, "labels": labels}

    t0 = time.perf_counter()
    state, m = tr.train_step(state, batch)
    loss0 = float(jax.block_until_ready(m["loss"]))
    print(f"compile + first step: {time.perf_counter() - t0:.1f}s "
          f"loss={loss0:.3f}", file=sys.stderr)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, m = tr.train_step(state, batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    toks_per_sec = B * T * args.steps / dt

    # 7B LoRA step ≈ 2 (fwd) + 4 (bwd) matmul-FLOPs per param-token
    approx_flops = 6 * 6.74e9 * toks_per_sec
    # NOTE for ROADMAP S1: this divides by the v5e peak whatever device ran;
    # the benchmark's peaks table (keyed by device_kind, unknown = error)
    # replaces it
    mfu = approx_flops / 197e12  # v5e bf16 peak 197 TFLOP/s

    print(json.dumps({
        "metric": (f"qlora_sft_tokens_per_sec_per_chip[llama2-7b,nf4,"
                   f"B{B}xT{T},{args.attention},remat={args.remat},"
                   f"quant={args.quant_impl}]"),
        "value": round(toks_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu, 3),  # MFU in lieu of a reference number
    }))


if __name__ == "__main__":
    main()
