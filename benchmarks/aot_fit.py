"""Does a cell fit one chip? Compiles the cell's real programs, at its real
sizes, for a DESCRIBED v5e:2x2 device (no chip attached, no chip time) and
prints the compiler's ``memory_analysis()``: the bytes one program needs while
it runs, arguments included. It counts one program at a time, not what else
the process keeps. Nothing runs; this is never a chip run.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit.py --workload qwen-serve-steady
    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit.py --workload mistral-train-lora --remat none,dots,full
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# real Mosaic lowering of the Pallas kernels although this process's backend is
# the CPU; without it the interpret-mode emulation would be compiled and measured
os.environ["DTX_PALLAS_INTERPRET"] = "0"


def memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    arg, out = int(ma.argument_size_in_bytes), int(ma.output_size_in_bytes)
    tmp, alias = int(ma.temp_size_in_bytes), int(ma.alias_size_in_bytes)
    return {"argument_GB": arg / 1e9, "output_GB": out / 1e9, "temp_GB": tmp / 1e9,
            "alias_GB": alias / 1e9, "live_GB": (arg + out + tmp - alias) / 1e9}


def with_sharding(tree, sh):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), tree)


def serving(cell, sh):
    import jax
    import jax.numpy as jnp
    import spec as spec_mod
    import weights
    from datatunerx_tpu.ops.paged_attention import init_paged_cache
    from datatunerx_tpu.serving.batched_engine import MAX_STOP, _Programs

    e = cell.workload["engine"]
    kernels = cell.model_fields.get("sliding_window") is None
    cfg = spec_mod.register_preset(cell, paged_kernel=kernels)
    mc = cell.model_fields
    S, bs, W = e["slots"], e["kv_block_size"], e["max_seq_len"]
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    progs = _Programs(cfg, W, None, epilogue="kernel")
    cache = with_sharding(jax.eval_shape(lambda: init_paged_cache(
        cfg, S, e["kv_blocks"], bs, W // bs, dtype=jnp.bfloat16, quantize=None)), sh)
    params = with_sharding(jax.eval_shape(lambda: weights.draw_params(mc, 0)), sh)
    lora = None
    ad = cell.workload.get("adapters") or {"count": 0}
    if ad["count"]:
        E, r, L = ad["count"] + 1, ad["rank"], mc["num_layers"]
        dims = weights.param_shapes(mc)["layer"]
        lora = ({"layers": {t: {"a": sds((L, E, dims[t][0], r), jnp.float32),
                               "b": sds((L, E, r, dims[t][1]), jnp.float32)}
                            for t in ad["targets"]}}, sds((E,), jnp.float32))
    out = {}
    for mode in ("greedy", "simple"):
        lowered = progs.decode.lower(
            params, lora, cache, sds((S, cfg.vocab_size), jnp.float32), sds((S,), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((S, 2), jnp.uint32),
            sds((S,), jnp.float32), sds((S,), jnp.float32), sds((S, MAX_STOP), jnp.int32),
            sds((S,), jnp.int32), K=e["decode_chunk"], mode=mode)
        out[f"decode[{mode}]"] = dict(memory(lowered.compile()),
                                      mosaic_calls=lowered.as_text().count("tpu_custom_call"))
    c = e["prefill_chunk"]
    row = sds((1, c), jnp.int32)
    lowered = progs.prefill_chunk.lower(params, lora, cache, sds((), jnp.int32), row, row, row,
                                        sds((), jnp.int32), chunk_len=c)
    out[f"prefill_chunk[{c}]"] = dict(memory(lowered.compile()),
                                      mosaic_calls=lowered.as_text().count("tpu_custom_call"))
    return out


def training(cell, sh, remats):
    import jax
    import jax.numpy as jnp
    import spec as spec_mod
    import weights
    from datatunerx_tpu.training import TrainConfig, Trainer

    t, out = cell.traffic, {}
    for remat in remats:
        tr = dict(cell.workload["train"])
        tr.pop("remat")
        cfg = spec_mod.register_preset(cell, remat=remat, attention_impl=tr.pop("attention"))
        tr["lora_targets"] = tuple(tr["lora_targets"])
        trainer = Trainer(cfg, TrainConfig(compute_dtype=jnp.bfloat16, **tr))
        params = jax.eval_shape(lambda: weights.draw_params(cell.model_fields, 0))
        state = with_sharding(jax.eval_shape(trainer.init_state, params, jax.random.PRNGKey(1)), sh)
        B, T = int(t["rows_per_step"]), int(t["block_size"])
        batch = {k: jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=sh)
                 for k in ("input_ids", "labels", "attention_mask", "segment_ids", "positions")}
        try:
            compiled = jax.jit(trainer._train_step_impl, donate_argnums=(0,)).lower(state, batch).compile()
            out[f"train_step[remat={remat}]"] = memory(compiled)
        except Exception as e:  # noqa: BLE001 -- the compiler's refusal IS the answer
            out[f"train_step[remat={remat}]"] = {"refused": str(e).splitlines()[0][:300]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--remat", default="", help="training: remat policies to try, comma-separated")
    ap.add_argument("--kv_blocks", type=int, default=0, help="serving: try another pool size")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import spec as spec_mod

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec_mod.load_cell(args.workload)
    if args.kv_blocks:
        cell.workload["engine"]["kv_blocks"] = args.kv_blocks
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    if "train" in cell.workload:
        remats = [r for r in args.remat.split(",") if r] or [cell.workload["train"]["remat"]]
        out = training(cell, sh, remats)
    else:
        out = serving(cell, sh)
    print(json.dumps({"cell": cell.name, "kv_blocks": (cell.workload.get("engine") or {}).get("kv_blocks"), "compiled_for": "v5e:2x2, one described device, nothing ran",
                      "programs": out}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
