"""Deviceless AOT certification against the v5e TPU target.

JAX topology-based AOT compilation against the locally-installed libtpu
runs the REAL Mosaic / XLA-TPU pipeline — lowering, tiling, buffer
assignment — with zero devices attached, so a kernel Mosaic refuses is found
in a sandbox without spending chip time:

    jax.config.update("jax_platforms", "cpu")     # this process computes on CPU
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.jit(fn).lower(<abstract args on topo.devices[0]>).compile()

It proves that programs COMPILE for the chip. That they compute the right
thing there is ``chip_smoke.py``'s kernel phase, on the chip.

``DTX_PALLAS_INTERPRET=0`` (set below) is load-bearing: with the platform
forced to cpu the kernels' default interpret gate would silently swap in
the emulated pallas path and the "certification" would prove nothing
(ops/_pallas.py).

Certified artifacts (each records compile status + compiler cost analysis +
buffer-assignment memory analysis into ``AOT_CERTIFY.json``):

  kernels   flash attention fwd/bwd (causal GQA + packed segments), int8
            matmul fwd/bwd, nf4 matmul fwd, TRANSPOSED nf4 backward (the
            default training path), fused LoRA, paged decode attention, and
            — at the geometry ``serving.server`` runs by default on a TPU —
            multi-token paged attention and the fused sampler
  steps     full Llama-2-7B QLoRA train step under both --quant_impl
            values (BASELINE row 2 geometry); Qwen1.5-14B nf4 B1 + B2
            (BASELINE row 5 + its stated over-budget point); Mistral-7B
            full-param fsdp=16 per-shard program on a 16-chip v5e
            topology (BASELINE row 4)
  serving   the engine's decode and prefill-chunk programs with both
            serving kernels engaged, tinyllama width, depth cut to 2
  memory    compiler buffer-assignment bytes vs parallel/memory.py's
            ``estimate_footprint`` for the three BASELINE configs
            (VERDICT r4 #3)
  roofline  per-step flops + HBM bytes for the pallas vs xla 7B paths →
            bandwidth/compute-bound tokens/s/chip upper bounds on v5e
            (197 TFLOP/s bf16, 819 GB/s HBM; VERDICT r4 #4)

Run:  python scripts/aot_certify.py [--only PATTERN] [--out AOT_CERTIFY.json]
Make: make aot-certify
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys
import time
import traceback
from datetime import datetime, timezone

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Must be set before the kernels' interpret gates are consulted: with the
# platform on the CPU they would otherwise lower the emulation path.
os.environ["DTX_PALLAS_INTERPRET"] = "0"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (  # noqa: E402
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

# v5e peaks for the roofline (How to Scale Your Model, v5e spec sheet).
V5E_BF16_FLOPS = 197e12
V5E_HBM_BYTES_S = 819e9

TOPOLOGY_1CHIP = "v5e:2x2"   # v5e:1x1 is rejected (chips_per_host_bounds 2x2)
TOPOLOGY_16CHIP = "v5e:4x4"


def _topo(name: str):
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


def _sds(tree, sharding):
    """Attach `sharding` to every leaf of an abstract (eval_shape) tree."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _cost(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # jax returns [dict] on some versions
        ca = ca[0] if ca else {}
    return {
        "flops": ca.get("flops"),
        "bytes_accessed": ca.get("bytes accessed"),
    }


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    tmp = int(ma.temp_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "alias_bytes": alias,
        # live HBM while the program runs: args + outputs + scratch, minus
        # donated buffers counted on both sides
        "peak_bytes": arg + out + tmp - alias,
    }


class Certifier:
    def __init__(self, out_path: str, only: str | None):
        self.out_path = out_path
        self.only = only
        self.records = []
        self.meta = {
            "date": datetime.now(timezone.utc).isoformat(timespec="seconds"),
            "jax": jax.__version__,
            "topology": {"single": TOPOLOGY_1CHIP, "sharded": TOPOLOGY_16CHIP},
            "pallas_interpret": False,
        }

    def run(self, name: str, fn):
        if self.only and not fnmatch.fnmatch(name, self.only):
            return None
        t0 = time.perf_counter()
        rec = {"name": name}
        try:
            extra = fn() or {}
            rec.update(extra)
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 — each artifact independent
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc(limit=8)
        rec["compile_s"] = round(time.perf_counter() - t0, 1)
        self.records.append(rec)
        self.flush()
        status = "OK " if rec["ok"] else "FAIL"
        print(f"[{status}] {name} ({rec['compile_s']}s)"
              + ("" if rec["ok"] else f"  {rec['error']}"), flush=True)
        return rec

    def flush(self):
        doc = dict(self.meta)
        doc["artifacts"] = self.records
        doc["ok"] = all(r["ok"] for r in self.records)
        with open(self.out_path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")


# ------------------------------------------------------------------ kernels

def kernel_artifacts(cert: Certifier, dev):
    from datatunerx_tpu.ops.flash_attention import flash_attention
    from datatunerx_tpu.ops.pallas_lora import pallas_lora_matmul
    from datatunerx_tpu.ops.pallas_quant import (
        pallas_matmul_int8,
        pallas_matmul_nf4,
    )
    from datatunerx_tpu.ops.quant import quantize_int8, quantize_nf4

    sh = SingleDeviceSharding(dev)
    B, T, H, KV, D = 1, 1024, 8, 2, 64  # GQA 4:1
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((B, T, KV, D), jnp.bfloat16, sharding=sh)
    seg = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=sh)

    def _lower(fn, *args, mosaic: bool = True):
        c = (lower_mosaic(fn, *args) if mosaic
             else jax.jit(fn).lower(*args).compile())
        return {"cost": _cost(c), "memory": _memory(c)}

    cert.run("kernel/flash_fwd_causal_gqa",
             lambda: _lower(lambda q, k, v: flash_attention(q, k, v), q, kv, kv))
    cert.run("kernel/flash_bwd_causal_gqa", lambda: _lower(
        lambda q, k, v: jax.grad(
            lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), q, kv, kv))
    cert.run("kernel/flash_fwd_segmented", lambda: _lower(
        lambda q, k, v, s: flash_attention(q, k, v, segment_ids=s),
        q, kv, kv, seg))
    cert.run("kernel/flash_bwd_segmented", lambda: _lower(
        lambda q, k, v, s: jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, segment_ids=s).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), q, kv, kv, seg))

    K, N, M = 4096, 4096, 512
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=sh)
    qw = _sds(jax.eval_shape(
        quantize_nf4, jax.ShapeDtypeStruct((K, N), jnp.bfloat16)), sh)
    q8 = _sds(jax.eval_shape(
        quantize_int8, jax.ShapeDtypeStruct((K, N), jnp.bfloat16)), sh)

    cert.run("kernel/nf4_matmul_fwd", lambda: _lower(
        lambda x, qw: pallas_matmul_nf4(x, qw, (K, N)), x, qw))
    cert.run("kernel/nf4_matmul_bwd_transposed", lambda: _lower(
        lambda x, qw: jax.grad(
            lambda x: pallas_matmul_nf4(
                x, qw, (K, N)).astype(jnp.float32).sum())(x), x, qw))
    cert.run("kernel/int8_matmul_fwd", lambda: _lower(
        lambda x, q8: pallas_matmul_int8(x, q8["q"], q8["scale"]), x, q8))
    # int8's custom VJP is deliberately XLA (dx = (g*scale) @ qT einsum —
    # pallas_quant.py:64-71): certify it compiles for TPU, not that it's Mosaic
    cert.run("kernel/int8_matmul_bwd_xla_vjp", lambda: _lower(
        lambda x, q8: jax.grad(
            lambda x: pallas_matmul_int8(
                x, q8["q"], q8["scale"]).astype(jnp.float32).sum())(x),
        x, q8, mosaic=False))

    # down_proj shapes whose K is not a power of two: tinyllama's 5632 (the
    # transposed kernel's lane-dim chunk must stay a 128-multiple) and
    # llama2-7b's 11008 (a whole-K int8 block overflows scoped VMEM) — both
    # were refused by Mosaic until PR 21's first chip run found them
    for Kd, Nd in ((5632, 2048), (11008, 4096)):
        xd = jax.ShapeDtypeStruct((M, Kd), jnp.bfloat16, sharding=sh)
        qwd = _sds(jax.eval_shape(
            quantize_nf4, jax.ShapeDtypeStruct((Kd, Nd), jnp.bfloat16)), sh)
        q8d = _sds(jax.eval_shape(
            quantize_int8, jax.ShapeDtypeStruct((Kd, Nd), jnp.bfloat16)), sh)
        tag = f"K{Kd}_N{Nd}"
        cert.run(f"kernel/nf4_matmul_fwd_{tag}", lambda x=xd, q=qwd, s=(Kd, Nd):
                 _lower(lambda x, q: pallas_matmul_nf4(x, q, s), x, q))
        cert.run(f"kernel/nf4_matmul_bwd_transposed_{tag}",
                 lambda x=xd, q=qwd, s=(Kd, Nd): _lower(
                     lambda x, q: jax.grad(lambda x: pallas_matmul_nf4(
                         x, q, s).astype(jnp.float32).sum())(x), x, q))
        cert.run(f"kernel/int8_matmul_fwd_{tag}", lambda x=xd, q=q8d: _lower(
            lambda x, q: pallas_matmul_int8(x, q["q"], q["scale"]), x, q))

    w = jax.ShapeDtypeStruct((K, N), jnp.bfloat16, sharding=sh)
    a = jax.ShapeDtypeStruct((K, 8), jnp.bfloat16, sharding=sh)
    b = jax.ShapeDtypeStruct((8, N), jnp.bfloat16, sharding=sh)
    cert.run("kernel/lora_fused_fwd", lambda: _lower(
        lambda x, w, a, b: pallas_lora_matmul(x, w, a, b, scale=4.0),
        x, w, a, b))

    # paged-decode attention (ops/pallas_paged_attention.py): the serving
    # fast path — one grid step a slot, a loop over the slot's written
    # blocks with hand-issued copies; bf16 and int8 pools at tinyllama
    # serving geometry (GQA 32q/4kv, bs=16, 64 blocks/slot)
    from datatunerx_tpu.ops.pallas_paged_attention import (
        paged_decode_attention,
    )

    Bd, Hd, KVd, dd, bsd, nbps, NBd = 4, 32, 4, 64, 16, 64, 256
    qd = jax.ShapeDtypeStruct((Bd, Hd, dd), jnp.bfloat16, sharding=sh)
    tables = jax.ShapeDtypeStruct((Bd, nbps), jnp.int32, sharding=sh)
    pos = jax.ShapeDtypeStruct((NBd, bsd), jnp.int32, sharding=sh)
    qpos = jax.ShapeDtypeStruct((Bd,), jnp.int32, sharding=sh)
    # the kernels read one layer of the stacked pool the layer scan carries
    # ([L, NB, bs, KV * d]); the layer is a traced scalar
    Ld = ENGINE_LAYERS
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
    pool_bf16 = jax.ShapeDtypeStruct((Ld, NBd, bsd, KVd * dd), jnp.bfloat16,
                                     sharding=sh)
    pool_i8 = jax.ShapeDtypeStruct((Ld, NBd, bsd, KVd * dd), jnp.int8,
                                   sharding=sh)
    pool_sc = jax.ShapeDtypeStruct((Ld, NBd, bsd, KVd), jnp.float32,
                                   sharding=sh)
    # qpos twice: the query's rope position and its lane cursor
    cert.run("kernel/paged_decode_bf16", lambda: _lower(
        lambda q, k, v, *rest: paged_decode_attention(
            q, k, v, None, None, *rest),
        qd, pool_bf16, pool_bf16, layer, tables, pos, qpos, qpos))
    cert.run("kernel/paged_decode_int8_kv", lambda: _lower(
        paged_decode_attention,
        qd, pool_i8, pool_i8, pool_sc, pool_sc, layer, tables, pos, qpos,
        qpos))

    for name, fn, args in serving_kernel_cases(sh):
        cert.run(name, lambda fn=fn, args=args: _lower(fn, *args))


# Geometry ``python -m datatunerx_tpu.serving.server`` runs by default on a
# TPU (chip_smoke.py checks the same shapes' numerics on the chip): 4 slots,
# --kv_block_size 16 (README / CI / bench), prefill chunks in multiples of
# DECODE_BUCKET=64 up to --prefill_chunk 256, chain verify k+1 for
# k <= --spec_k 4, a 4x3 tree step (1 + 12 columns).
ENGINE_SLOTS, ENGINE_BLOCK, ENGINE_SEQ = 4, 16, 1024
ENGINE_LAYERS = 2  # layers of the stacked pool a kernel is handed
ENGINE_QLENS = (64, 128, 192, 256) + (2, 3, 4, 5, 13)
ENGINE_HEADS = {"tinyllama": (32, 4, 64), "llama2_7b": (32, 32, 128)}
ENGINE_VOCABS = (32000, 151936)
# the sampler as the benchmark's serving cells run it (16 slots; BENCHMARK.json:
# qwen-serve-steady samples at vocab 151,936, mistral-serve-batch is greedy at
# 32,000): (slots, vocab, mode)
CELL_SAMPLERS = ((16, 151936, "simple"), (16, 32000, "greedy"))


def serving_kernel_cases(sh):
    """``(name, fn, abstract args)`` for every (kernel, static mode,
    geometry) the default engine traces — shared by ``kernel_artifacts`` and
    the tier-1 lowering test (tests/test_aot_certify.py)."""
    from datatunerx_tpu.ops.pallas_paged_attention import (
        paged_multitoken_attention,
    )
    from datatunerx_tpu.ops.pallas_sampling import fused_sample

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cases = []
    bs, nbps = ENGINE_BLOCK, ENGINE_SEQ // ENGINE_BLOCK
    for gname, (H, KV, d) in ENGINE_HEADS.items():
        for T in ENGINE_QLENS:
            B = 1 if T >= 64 else ENGINE_SLOTS  # chunk: one slot; verify: all
            NB = ENGINE_SLOTS * nbps
            q = sds((B, T, H, d), jnp.bfloat16)
            tables = sds((B, nbps), jnp.int32)
            allow = sds((B, T, nbps * bs), jnp.bool_)
            layer = sds((), jnp.int32)
            for kv_dtype in (jnp.bfloat16, jnp.int8):
                pool = sds((ENGINE_LAYERS, NB, bs, KV * d), kv_dtype)
                if kv_dtype == jnp.int8:
                    sc = sds((ENGINE_LAYERS, NB, bs, KV), jnp.float32)
                    fn = paged_multitoken_attention
                    args = (q, pool, pool, sc, sc, layer, tables, allow)
                    tag = "int8_kv"
                else:
                    def fn(q, k, v, li, t, a):
                        return paged_multitoken_attention(
                            q, k, v, None, None, li, t, a)
                    args = (q, pool, pool, layer, tables, allow)
                    tag = "bf16"
                cases.append(
                    (f"kernel/paged_multitoken_{tag}_{gname}_T{T}", fn, args))
    samplers = [(ENGINE_SLOTS, V, mode) for V in ENGINE_VOCABS
                for mode in ("greedy", "simple")]
    for S, V, mode in samplers + list(CELL_SAMPLERS):
        def fn(lg, t, p, k, mode=mode):
            return fused_sample(lg, t, p, k, mode=mode, impl="kernel")
        cases.append((
            f"kernel/fused_sample_{mode}_S{S}_V{V}", fn,
            (sds((S, V), jnp.float32), sds((S,), jnp.float32),
             sds((S,), jnp.float32), sds((S, 2), jnp.uint32))))
    return cases


def lower_mosaic(fn, *args):
    """Lower ``fn``, insist the result is a Mosaic custom call (not the
    interpret-mode emulation), and compile it for the TPU target."""
    lo = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lo.as_text(), "not Mosaic-lowered"
    return lo.compile()


# -------------------------------------------------------------- train steps

def _abstract_params(cfg):
    from datatunerx_tpu.models import init_params

    def build(key):
        p = init_params(cfg, key, dtype=jnp.bfloat16)
        if cfg.quantization:
            from datatunerx_tpu.ops.quant import quantize_model_params

            p = quantize_model_params(p, cfg.quantization)
        return p

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def _single_chip_step(cfg, train_cfg, batch: int, seq: int, dev):
    """Compile one full Trainer.train_step on one topology device; returns
    (compiled, trainer)."""
    from datatunerx_tpu.training import Trainer
    from datatunerx_tpu.training.loss import IGNORE_INDEX  # noqa: F401

    sh = SingleDeviceSharding(dev)
    tr = Trainer(cfg, train_cfg)
    params_abs = _abstract_params(cfg)
    state_abs = _sds(
        jax.eval_shape(tr.init_state, params_abs, jax.random.PRNGKey(1)), sh)
    batch_abs = {
        "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sh),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sh),
    }
    compiled = jax.jit(tr._train_step_impl, donate_argnums=(0,)).lower(
        state_abs, batch_abs).compile()
    return compiled


def _estimate(cfg, train_cfg, batch, seq, mesh_shape=None):
    from datatunerx_tpu.parallel.memory import estimate_footprint

    fp = estimate_footprint(cfg, train_cfg, batch=batch, seq=seq,
                            mesh_shape=mesh_shape)
    return fp


def _mem_vs_estimate(compiled, fp) -> dict:
    mem = _memory(compiled)
    est = fp.total
    peak = mem.get("peak_bytes")
    out = {
        "memory": mem,
        "estimate_bytes": int(est),
        "estimate_gb": fp.gb(),
    }
    if peak:
        out["compiler_peak_gb"] = round(peak / 1e9, 3)
        out["estimate_over_compiler"] = round(est / peak, 3)
    return out


def _lora_cfg(**kw):
    from datatunerx_tpu.training import TrainConfig

    return TrainConfig(
        finetuning_type="lora", lora_rank=8, lora_alpha=32.0,
        lora_dropout=0.05, lora_targets=("q_proj", "v_proj"),
        learning_rate=2e-4, scheduler="cosine", optimizer="adamw",
        total_steps=1000, compute_dtype=jnp.bfloat16, **kw)


def step_artifacts(cert: Certifier, dev):
    from datatunerx_tpu.models import get_config

    def seven_b(quant_impl):
        def go():
            cfg = get_config("llama2-7b", remat="full", attention_impl="flash",
                             quantization="int4", quant_impl=quant_impl)
            tc = _lora_cfg()
            compiled = _single_chip_step(cfg, tc, 4, 1024, dev)
            fp = _estimate(cfg, tc, 4, 1024)
            rec = _mem_vs_estimate(compiled, fp)
            rec["cost"] = _cost(compiled)
            rec["cost_note"] = ("XLA cost_analysis counts the layer scan "
                                "body ONCE (trip count invisible) and sees "
                                "no flops inside Mosaic custom calls — see "
                                "analysis/roofline_7b_v5e for the corrected "
                                "per-step totals")
            rec["tokens_per_step"] = 4 * 1024
            return rec
        return go

    cert.run("step/train_7b_qlora_pallas", seven_b("pallas"))
    cert.run("step/train_7b_qlora_xla", seven_b("xla"))

    # Roofline from compiler-derived per-layer costs (VERDICT r4 #4).
    # Method: cost_analysis counts a lax.scan body ONCE (trip count is
    # invisible), so the full-step numbers above under-report by ~L×. To
    # recover exact per-layer cost WITHOUT compiling a 32-layer unrolled
    # program (measured pathological: >1 h), compile the same step for
    # num_layers=1 and num_layers=2 models of identical geometry with the
    # scan FULLY unrolled (DTX_SCAN_UNROLL = L, so the loop is inlined and
    # every op is counted): C2 - C1 = one layer's exact fwd+remat+bwd cost,
    # nonscan (embed+lm_head+loss) = C1 - (C2 - C1), per-step total =
    # L*(C2-C1) + nonscan. Mosaic custom-call flops are invisible to the
    # compiler either way, so kernel matmul flops (exact by construction:
    # 2*b*t*K*N per projection) are added analytically for the pallas path;
    # bytes_accessed DOES count custom-call operands, so HBM traffic needs
    # no correction.
    def roofline():
        from datatunerx_tpu.models import get_config as _gc

        out = {}
        L = 32
        B, T = 4, 1024
        tok = B * T
        # exact matmul flops inside the Mosaic kernels, per layer per step:
        # 7 quantized projections (q,k,v,o 4096x4096; gate,up 4096x11008;
        # down 11008x4096) x (fwd + remat-refwd + bwd dx) = 3 passes
        D, F = 4096, 11008
        proj_flops = 2 * tok * (4 * D * D + 3 * D * F)
        kernel_flops_per_layer = 3 * proj_flops
        # flash attention also lives in Mosaic custom calls (invisible to
        # cost_analysis on BOTH paths): per layer, causal-halved QK^T/AV
        # matmuls — fwd 2, bwd 4 (dQ/dK/dV/dS) + remat refwd 2 = 8 passes of
        # 2*B*H*T^2*Dh*0.5 each (~1.4e11 at T=1024, ~2.8% of a layer; grows
        # quadratically with T, so omitting it would eventually flip the
        # compute-vs-HBM verdict at long context)
        Hh, Dh = 32, 128
        flash_flops_per_layer = 8 * (2 * B * Hh * T * T * Dh // 2)
        for impl in ("pallas", "xla"):
            cs = {}
            for n_layers in (1, 2):
                os.environ["DTX_SCAN_UNROLL"] = str(n_layers)
                try:
                    cfg = _gc("llama2-7b", remat="full",
                              attention_impl="flash", quantization="int4",
                              quant_impl=impl, num_layers=n_layers)
                    compiled_n = _single_chip_step(cfg, _lora_cfg(), B, T,
                                                   dev)
                    cs[n_layers] = _cost(compiled_n)
                finally:
                    os.environ["DTX_SCAN_UNROLL"] = "1"
            c1, c2 = cs[1], cs[2]
            layer = {k: c2[k] - c1[k] for k in ("flops", "bytes_accessed")}
            nonscan = {k: c1[k] - layer[k] for k in layer}
            fl = L * layer["flops"] + nonscan["flops"]
            by = L * layer["bytes_accessed"] + nonscan["bytes_accessed"]
            fl += L * flash_flops_per_layer  # flash kernels, both paths
            if impl == "pallas":
                fl += L * kernel_flops_per_layer
            t_flops = fl / V5E_BF16_FLOPS
            t_hbm = by / V5E_HBM_BYTES_S
            out[impl] = {
                "per_layer": layer,
                "nonscan": nonscan,
                "kernel_flops_per_layer": (kernel_flops_per_layer
                                           if impl == "pallas" else 0),
                "flash_flops_per_layer": flash_flops_per_layer,
                "flops_per_step": fl,
                "hbm_bytes_per_step": by,
                "flops_time_s": round(t_flops, 5),
                "hbm_time_s": round(t_hbm, 5),
                "bound": "hbm" if t_hbm > t_flops else "flops",
                "tokens_per_sec_upper_bound": round(
                    tok / max(t_flops, t_hbm), 1),
                "mfu_at_bound": round(
                    (fl / max(t_flops, t_hbm)) / V5E_BF16_FLOPS, 3),
            }
        return {"roofline": out, "tokens_per_step": tok, "layers": L}

    cert.run("analysis/roofline_7b_v5e", roofline)

    def qwen(batch):
        def go():
            cfg = get_config("qwen1.5-14b", remat="full",
                             attention_impl="flash", quantization="int4",
                             quant_impl="pallas")
            tc = _lora_cfg()
            compiled = _single_chip_step(cfg, tc, batch, 1024, dev)
            fp = _estimate(cfg, tc, batch, 1024)
            rec = _mem_vs_estimate(compiled, fp)
            rec["cost"] = _cost(compiled)
            from datatunerx_tpu.parallel.memory import hbm_budget

            rec["hbm_budget_bytes"] = hbm_budget("v5e")
            peak = rec["memory"].get("peak_bytes")
            if peak:
                rec["fits_v5e1_by_compiler"] = peak <= rec["hbm_budget_bytes"]
            return rec
        return go

    cert.run("step/train_qwen14b_qlora_b1", qwen(1))
    cert.run("step/train_qwen14b_qlora_b2_overbudget", qwen(2))


def mistral_fsdp_artifact(cert: Certifier):
    from datatunerx_tpu.models import get_config
    from datatunerx_tpu.parallel.mesh import make_mesh
    from datatunerx_tpu.parallel.sharding import batch_shardings, tree_shardings
    from datatunerx_tpu.training import TrainConfig, Trainer

    def go():
        topo = _topo(TOPOLOGY_16CHIP)
        mesh = make_mesh(devices=topo.devices, fsdp=16)
        cfg = get_config("mistral-7b", remat="full", attention_impl="flash")
        tc = TrainConfig(finetuning_type="full", compute_dtype=jnp.bfloat16)
        tr = Trainer(cfg, tc, mesh=mesh)
        params_abs = _abstract_params(cfg)
        state_abs = jax.eval_shape(tr.init_state, params_abs,
                                   jax.random.PRNGKey(1))
        # shard the abstract state by the trainer's OWN rules (the same
        # _spec_for path rules shard_tree applies on device): adam moment
        # trees mirror the param tree's paths, so tree_shardings covers
        # params + opt state; scalars/rng fall to P() (replicated). Relying
        # on XLA output-sharding propagation through an AOT init compile
        # instead replicated the moments and "OOM"ed the per-shard step at
        # 27.8 GB of arguments.
        state_sh = tree_shardings(state_abs, mesh)
        state_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            state_abs, state_sh)
        B, T = 16, 1024
        batch_abs = {"input_ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
        bsh = batch_shardings(batch_abs, mesh)
        batch_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            batch_abs, bsh)
        # pin the new state to the input layouts so donation aliases (else
        # XLA may re-shard outputs, no buffers alias, and "peak" double
        # counts the whole state); metrics are replicated scalars
        metrics_abs = jax.eval_shape(tr._train_step_impl, state_abs,
                                     batch_abs)[1]
        repl = NamedSharding(mesh, P())
        out_sh = (state_sh, jax.tree_util.tree_map(lambda _: repl,
                                                   metrics_abs))
        compiled = jax.jit(tr._train_step_impl, donate_argnums=(0,),
                           out_shardings=out_sh).lower(
            state_in, batch_in).compile()
        fp = _estimate(cfg, tc, B, T, mesh_shape={"fsdp": 16})
        rec = _mem_vs_estimate(compiled, fp)
        rec["cost"] = _cost(compiled)
        rec["mesh"] = {"fsdp": 16}
        return rec

    cert.run("step/train_mistral7b_full_fsdp16", go)


# ----------------------------------------------------------------- serving

def serving_artifact(cert: Certifier, dev):
    """The engine's own jitted programs (``_Programs``) as the default TPU
    engine traces them — paged KV, the in-place attention kernels, the fused
    sampler kernel — at tinyllama width with the depth cut to 2 (the layer
    scan makes lowering depth-independent). Arguments are abstract: no
    engine, no weights."""
    from datatunerx_tpu.models import get_config
    from datatunerx_tpu.ops.paged_attention import init_paged_cache
    from datatunerx_tpu.serving.batched_engine import MAX_STOP, _Programs

    sh = SingleDeviceSharding(dev)
    cfg = get_config("tinyllama-1.1b", num_layers=2, paged_kernel=True)
    S, bs, W = ENGINE_SLOTS, ENGINE_BLOCK, ENGINE_SEQ
    nbps = W // bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def programs_and_state(kv_quant):
        progs = _Programs(cfg, W, kv_quant, epilogue="kernel")
        cache = _sds(jax.eval_shape(lambda: init_paged_cache(
            cfg, S, S * nbps, bs, nbps, dtype=jnp.bfloat16,
            quantize=kv_quant)), sh)
        return progs, _sds(_abstract_params(cfg), sh), cache

    def decode(kv_quant, mode):
        def go():
            progs, params, cache = programs_and_state(kv_quant)
            lowered = progs.decode.lower(
                params, None, cache, sds((S, cfg.vocab_size), jnp.float32),
                sds((S,), jnp.int32), sds((S,), jnp.int32),
                sds((S,), jnp.bool_), sds((S, 2), jnp.uint32),
                sds((S,), jnp.float32), sds((S,), jnp.float32),
                sds((S, MAX_STOP), jnp.int32), sds((S,), jnp.int32),
                K=8, mode=mode)
            # paged attention + the sampler: both must be Mosaic calls
            assert lowered.as_text().count("tpu_custom_call") >= 2, \
                "decode step lowered without its kernels"
            compiled = lowered.compile()
            return {"cost": _cost(compiled), "memory": _memory(compiled),
                    "scale": "tinyllama width, 2 layers"}
        return go

    def prefill_chunk():
        progs, params, cache = programs_and_state(None)
        c = ENGINE_QLENS[3]
        row = sds((1, c), jnp.int32)
        lowered = progs.prefill_chunk.lower(
            params, None, cache, sds((), jnp.int32), row, row, row,
            sds((), jnp.int32), chunk_len=c)
        assert "tpu_custom_call" in lowered.as_text(), \
            "prefill chunk lowered without the multi-token kernel"
        compiled = lowered.compile()
        return {"cost": _cost(compiled), "memory": _memory(compiled),
                "scale": "tinyllama width, 2 layers"}

    cert.run("serving/decode_step", decode(None, "greedy"))
    cert.run("serving/decode_step_sampled", decode(None, "simple"))
    cert.run("serving/decode_step_int8_kv", decode("int8", "greedy"))
    cert.run("serving/prefill_chunk_step", prefill_chunk)


def extra_artifacts(cert: Certifier, dev):
    """The remaining compute paths: preference stages (dpo/rm), PPO
    rollout+update, ring-SP sharded training. Certified at
    debug/1B scale — lowering legality is geometry-independent; the 7B/14B
    artifacts above already cover full-scale memory."""
    from datatunerx_tpu.models import get_config
    from datatunerx_tpu.training import TrainConfig, Trainer

    sh = SingleDeviceSharding(dev)

    def stage_step(stage):
        def go():
            cfg = get_config("debug", attention_impl="flash", remat="full")
            tc = TrainConfig(stage=stage, finetuning_type="lora",
                             lora_rank=4, lora_dropout=0.0,
                             compute_dtype=jnp.bfloat16)
            tr = Trainer(cfg, tc)
            params_abs = _abstract_params(cfg)
            state_abs = _sds(jax.eval_shape(
                tr.init_state, params_abs, jax.random.PRNGKey(1)), sh)
            B, T = 2, 128
            ids = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=sh)
            batch = {"chosen_ids": ids, "chosen_labels": ids,
                     "rejected_ids": ids, "rejected_labels": ids}
            compiled = jax.jit(tr._train_step_impl, donate_argnums=(0,)
                               ).lower(state_abs, batch).compile()
            return {"cost": _cost(compiled), "memory": _memory(compiled)}
        return go

    cert.run("extra/train_dpo_step", stage_step("dpo"))
    cert.run("extra/train_rm_step", stage_step("rm"))

    def ppo():
        from datatunerx_tpu.models.lora import init_lora_params, lora_scaling
        from datatunerx_tpu.training.ppo import PPOConfig, PPOTrainer

        cfg = get_config("debug", attention_impl="xla", remat="none")
        tc = TrainConfig(stage="ppo", finetuning_type="lora", lora_rank=4,
                         lora_dropout=0.0, scheduler="constant",
                         compute_dtype=None)
        rwd = jax.eval_shape(
            lambda k: init_lora_params(cfg, k, rank=4), jax.random.PRNGKey(7))
        rwd = dict(rwd)
        rwd["v_head"] = jax.ShapeDtypeStruct((cfg.hidden_size,), jnp.float32)
        # reward tree must be concrete for trainer construction; zeros have
        # the right shapes and PPO numerics are irrelevant to lowering
        rwd = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), rwd)
        tr = PPOTrainer(cfg, tc, PPOConfig(gen_len=16),
                        reward_lora=rwd, reward_scaling=lora_scaling(32.0, 4),
                        eos_id=2, pad_id=0)
        params_abs = _abstract_params(cfg)
        state_abs = _sds(jax.eval_shape(
            tr.init_state, params_abs, jax.random.PRNGKey(1)), sh)
        B, T = 2, 32
        batch = {"prompt_ids": jax.ShapeDtypeStruct((B, T), jnp.int32,
                                                    sharding=sh),
                 "prompt_mask": jax.ShapeDtypeStruct((B, T), jnp.int32,
                                                     sharding=sh)}
        ro_lower = jax.jit(tr._rollout_impl).lower(state_abs, batch,
                                                   jnp.float32(0.2))
        ro_c = ro_lower.compile()
        ro_abs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(tr._rollout_impl, state_abs, batch,
                           jnp.float32(0.2))[0])  # (ro, stats) -> ro
        up_c = jax.jit(tr._ppo_update_impl, donate_argnums=(0,)).lower(
            state_abs, ro_abs).compile()
        return {"rollout": {"cost": _cost(ro_c), "memory": _memory(ro_c)},
                "update": {"cost": _cost(up_c), "memory": _memory(up_c)}}

    cert.run("extra/ppo_rollout_and_update", ppo)

    def ring_sp():
        from datatunerx_tpu.parallel.mesh import make_mesh
        from datatunerx_tpu.parallel.sharding import (
            batch_shardings,
            tree_shardings,
        )

        topo = _topo(TOPOLOGY_1CHIP)
        mesh = make_mesh(devices=topo.devices, sp=4, dp=1)
        cfg = get_config("tinyllama-1.1b", attention_impl="ring",
                         remat="dots")
        tc = _lora_cfg()
        tr = Trainer(cfg, tc, mesh=mesh)
        params_abs = _abstract_params(cfg)
        state_abs = jax.eval_shape(tr.init_state, params_abs,
                                   jax.random.PRNGKey(1))
        state_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            state_abs, tree_shardings(state_abs, mesh))
        B, T = 1, 4096  # sequence sharded 4-way over sp
        babs = {"input_ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
        batch_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            babs, batch_shardings(babs, mesh))
        compiled = jax.jit(tr._train_step_impl, donate_argnums=(0,)).lower(
            state_in, batch_in).compile()
        return {"cost": _cost(compiled), "memory": _memory(compiled),
                "mesh": {"sp": 4}}

    cert.run("extra/train_ring_sp4_tinyllama", ring_sp)

    def dcn_hybrid():
        """Multi-slice shape: dp-major crosses slices over DCN
        (parallel/mesh.py make_mesh(dcn_dp=…)); without slice indices the
        contiguous chunks of the topology's device list emulate slices —
        the SAME program shape that runs on real multislice compiles here
        for the TPU target."""
        from datatunerx_tpu.parallel.mesh import make_mesh
        from datatunerx_tpu.parallel.sharding import (
            batch_shardings,
            tree_shardings,
        )

        topo = _topo(TOPOLOGY_16CHIP)
        mesh = make_mesh(devices=topo.devices, dp=4, fsdp=4, dcn_dp=2)
        cfg = get_config("tinyllama-1.1b", attention_impl="flash",
                         remat="dots")
        tc = _lora_cfg()
        tr = Trainer(cfg, tc, mesh=mesh)
        params_abs = _abstract_params(cfg)
        state_abs = jax.eval_shape(tr.init_state, params_abs,
                                   jax.random.PRNGKey(1))
        state_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            state_abs, tree_shardings(state_abs, mesh))
        B, T = 16, 1024
        babs = {"input_ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
        batch_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            babs, batch_shardings(babs, mesh))
        compiled = jax.jit(tr._train_step_impl, donate_argnums=(0,)).lower(
            state_in, batch_in).compile()
        return {"cost": _cost(compiled), "memory": _memory(compiled),
                "mesh": {"dp": 4, "fsdp": 4, "dcn_dp": 2}}

    cert.run("extra/train_dcn_hybrid_dp4x2_fsdp4", dcn_hybrid)

    def ring_long_context():
        """Long-context shape: ring SP at T=32k (8k tokens/device on sp=4)
        — the O(T_local) memory claim is only real if the sharded program
        actually compiles at long T for the TPU target."""
        from datatunerx_tpu.parallel.mesh import make_mesh
        from datatunerx_tpu.parallel.sharding import (
            batch_shardings,
            tree_shardings,
        )

        topo = _topo(TOPOLOGY_1CHIP)
        mesh = make_mesh(devices=topo.devices, sp=4, dp=1)
        cfg = get_config("tinyllama-1.1b", attention_impl="ring",
                         remat="full", max_seq_len=32768)
        tc = _lora_cfg()
        tr = Trainer(cfg, tc, mesh=mesh)
        params_abs = _abstract_params(cfg)
        state_abs = jax.eval_shape(tr.init_state, params_abs,
                                   jax.random.PRNGKey(1))
        state_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            state_abs, tree_shardings(state_abs, mesh))
        B, T = 1, 32768
        babs = {"input_ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
        batch_in = jax.tree_util.tree_map(
            lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
            babs, batch_shardings(babs, mesh))
        compiled = jax.jit(tr._train_step_impl, donate_argnums=(0,)).lower(
            state_in, batch_in).compile()
        return {"cost": _cost(compiled), "memory": _memory(compiled),
                "mesh": {"sp": 4}, "seq_len": T}

    cert.run("extra/train_ring_sp4_T32k_long_context", ring_long_context)

    def serving_prefill():
        from datatunerx_tpu.serving.batched_engine import BatchedEngine

        eng = BatchedEngine("preset:debug", template="vanilla",
                            max_seq_len=256, slots=4, decode_chunk=8)
        try:
            to_sds = lambda t: _sds(  # noqa: E731
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t), sh)
            plen = 64
            tok = jax.ShapeDtypeStruct((1, plen), jnp.int32, sharding=sh)
            aidx = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
            # eng._prefill is the memoized _Programs.prefill jit (the impl
            # lives on the shared program holder, not the engine); lora is
            # an argument now (None = base-only engine)
            compiled = eng._prefill.lower(
                to_sds(eng.params), None, tok, tok, tok, aidx,
                prompt_len=plen).compile()
            return {"cost": _cost(compiled), "memory": _memory(compiled)}
        finally:
            eng.close()

    cert.run("serving/prefill_step", serving_prefill)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "AOT_CERTIFY.json"))
    ap.add_argument("--only", default=None,
                    help="fnmatch pattern over artifact names")
    args = ap.parse_args()

    cert = Certifier(args.out, args.only)
    dev = _topo(TOPOLOGY_1CHIP).devices[0]

    kernel_artifacts(cert, dev)
    step_artifacts(cert, dev)
    mistral_fsdp_artifact(cert)
    serving_artifact(cert, dev)
    extra_artifacts(cert, dev)

    cert.flush()
    n_ok = sum(r["ok"] for r in cert.records)
    print(f"\n{n_ok}/{len(cert.records)} artifacts certified "
          f"-> {args.out}", flush=True)
    return 0 if n_ok == len(cert.records) else 1


if __name__ == "__main__":
    sys.exit(main())
