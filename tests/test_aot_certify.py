"""Deviceless AOT compile path (scripts/aot_certify.py) regression guard.

Certifies, at debug scale, that the topology-based AOT pipeline this repo's
TPU compile evidence rests on keeps working: get_topology_desc for a v5e
target, Mosaic lowering of a Pallas kernel with the interpret gate forced
off, and a full train step lowered/compiled for the TPU target with cost +
memory analysis available. Runs in a subprocess because the AOT flow needs
DTX_PALLAS_INTERPRET=0 and a topology client registered before model code
traces — state that must not leak into the CPU-mesh suite process.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import json
import os
os.environ["DTX_PALLAS_INTERPRET"] = "0"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = topo.devices[0]
sh = SingleDeviceSharding(dev)

# 1) a Pallas kernel must actually lower through Mosaic, not interpret mode
from datatunerx_tpu.ops.flash_attention import flash_attention
q = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.bfloat16, sharding=sh)
lo = jax.jit(lambda q, k, v: flash_attention(q, k, v)).lower(q, q, q)
assert "tpu_custom_call" in lo.as_text(), "flash kernel not Mosaic-lowered"
lo.compile()

# 2) a full debug train step compiles for the TPU target with analyses
import sys
sys.path.insert(0, os.environ["DTX_REPO"])
from scripts.aot_certify import _lora_cfg, _single_chip_step, _cost, _memory
from datatunerx_tpu.models import get_config

cfg = get_config("debug", attention_impl="flash", remat="full")
compiled = _single_chip_step(cfg, _lora_cfg(), 2, 128, dev)
cost, mem = _cost(compiled), _memory(compiled)
assert cost["flops"] and cost["bytes_accessed"], cost
assert mem["peak_bytes"] > 0, mem
print(json.dumps({"ok": True, "cost": cost, "peak": mem["peak_bytes"]}))
"""


# The serving kernels at the geometry ``serving.server`` runs by default on a
# TPU (block 16, 4 slots, every prefill-chunk and verify q_len, both sampler
# modes): interpret-mode parity cannot see a block shape Mosaic refuses, a
# scratch that overflows scoped VMEM, or a primitive with no TPU lowering —
# all three shipped once behind ``auto``. Importing scripts.aot_certify sets
# DTX_PALLAS_INTERPRET=0 and the CPU platform before jax loads.
_SERVING_PROBE = r"""
import json, os, sys
sys.path.insert(0, os.environ["DTX_REPO"])
from scripts.aot_certify import (
    ENGINE_BLOCK, ENGINE_HEADS, ENGINE_LAYERS, ENGINE_SEQ, ENGINE_SLOTS,
    TOPOLOGY_1CHIP, _topo, lower_mosaic, serving_kernel_cases)
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding
from datatunerx_tpu.ops.pallas_paged_attention import paged_decode_attention

sh = SingleDeviceSharding(_topo(TOPOLOGY_1CHIP).devices[0])


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


# the single-token decode kernel at the same geometry, and at the shape the
# benchmark's cell qwen-serve-steady runs it at (16 slots, 32 KV heads of 128,
# a table of 128 columns, 384 blocks, 16 stacked layers), bf16 and int8 pools
# (the shared list holds the multi-token kernel and the sampler); and at
# Mistral-7B's (8 KV heads of 128, a window of 4,096) with the cache its preset
# allows, 8,192 lanes: the walk that starts at the window's first trip; and at
# the attending kinds of two models of several layer kinds whose token step
# takes it (models/hybrid.py), as the cells mimo-serve-batch and
# granite-serve-chat shape them: 64 slots, 4 KV heads of 192 (q/k) and 128 (v)
# over tables of 160 columns; 8 KV heads of 64 over 64 columns, scores x 1/64
def decode_cases(tag, heads, slots, nbps, blocks, layers, window=None,
                 dv=None, scale=None):
    H, KV, d = heads
    # layer, tables, pos pool, q positions, lane cursors
    rest = (sds((), jnp.int32), sds((slots, nbps), jnp.int32),
            sds((blocks, ENGINE_BLOCK), jnp.int32), sds((slots,), jnp.int32),
            sds((slots,), jnp.int32))
    q = sds((slots, H, d), jnp.bfloat16)
    shape = (layers, blocks, ENGINE_BLOCK, KV * d)
    v_shape = shape[:3] + (KV * (dv or d),)
    scales = sds(shape[:3] + (KV,), jnp.float32)
    return [
        (f"kernel/paged_decode_bf16_{tag}",
         lambda q, k, v, *r: paged_decode_attention(
             q, k, v, None, None, *r, window=window, scale=scale),
         (q, sds(shape, jnp.bfloat16), sds(v_shape, jnp.bfloat16)) + rest),
        (f"kernel/paged_decode_int8_kv_{tag}",
         lambda *a: paged_decode_attention(*a, window=window, scale=scale),
         (q, sds(shape, jnp.int8), sds(v_shape, jnp.int8), scales, scales)
         + rest)]


nbps = ENGINE_SEQ // ENGINE_BLOCK
cases = (list(serving_kernel_cases(sh))
         + decode_cases("tinyllama", ENGINE_HEADS["tinyllama"], ENGINE_SLOTS,
                        nbps, ENGINE_SLOTS * nbps, ENGINE_LAYERS)
         + decode_cases("cell", ENGINE_HEADS["llama2_7b"], 16, 128, 384, 16)
         + decode_cases("windowed", (32, 8, 128), 16, 512, 2048, 16,
                        window=4096)
         # a model of several layer kinds has no int8 cache
         + decode_cases("mimo_global", (64, 4, 192), 64, 160, 2560, 2,
                        dv=128)[:1]
         + decode_cases("granite_global", (32, 8, 64), 64, 64, 4096, 4,
                        scale=0.015625)[:1])
KERNEL_NAMES = ("dtx_paged_decode", "dtx_paged_multitoken", "dtx_fused_sample")
done, failed, named = [], {}, {}
for name, fn, args in cases:
    if not ("fused_sample" in name or "paged_decode" in name or name.endswith((
            "bf16_tinyllama_T5", "bf16_tinyllama_T13", "bf16_tinyllama_T64",
            "bf16_tinyllama_T256", "int8_kv_tinyllama_T5",
            "int8_kv_tinyllama_T256"))):
        continue  # the full list is `make aot-certify`'s
    try:
        text = lower_mosaic(fn, *args).as_text()
        done.append(name)
        # the pallas_call's name= after Mosaic and XLA's TPU pipeline: the
        # custom call's instruction name, which the chip's trace shows
        named[name] = [k for k in KERNEL_NAMES if "%" + k in text]
    except Exception as e:
        failed[name] = f"{type(e).__name__}: {str(e)[:400]}"
print(json.dumps({"done": done, "failed": failed, "named": named}))
"""


def _run_probe(code: str, timeout: int, **more) -> dict:
    env = dict(os.environ, DTX_REPO=REPO,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""), **more)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_serving_kernels_lower_through_mosaic_at_engine_geometry():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    doc = _run_probe(_SERVING_PROBE, timeout=600)
    assert not doc["failed"], doc["failed"]
    names = set(doc["done"])
    # the default engine's shapes are all there: prefill chunk = 256, the
    # widest chain verify (spec_k 4 -> 5 columns), both KV dtypes, and the
    # sampler's two kernel modes at S = 4 (not a multiple of 8 sublanes) and
    # at the benchmark cells' 16 slots
    for want in ("kernel/paged_multitoken_bf16_tinyllama_T256",
                 "kernel/paged_multitoken_int8_kv_tinyllama_T256",
                 "kernel/paged_multitoken_bf16_tinyllama_T5",
                 "kernel/fused_sample_greedy_S4_V32000",
                 "kernel/fused_sample_simple_S4_V151936",
                 "kernel/fused_sample_simple_S16_V151936",
                 "kernel/fused_sample_greedy_S16_V32000"):
        assert want in names, (want, sorted(names))
    # each kernel keeps the name its pallas_call was given through Mosaic:
    # the device trace's readers (benchmarks/scope_readers.py) find it by that
    for want in ("kernel/paged_decode_bf16_tinyllama",
                 "kernel/paged_decode_int8_kv_tinyllama",
                 "kernel/paged_decode_bf16_cell",
                 "kernel/paged_decode_int8_kv_cell",
                 "kernel/paged_decode_bf16_windowed",
                 "kernel/paged_decode_int8_kv_windowed",
                 "kernel/paged_decode_bf16_mimo_global",
                 "kernel/paged_decode_bf16_granite_global"):
        assert want in names, (want, sorted(names))
    for case, kernels in doc["named"].items():
        want = ("dtx_fused_sample" if "fused_sample" in case else
                "dtx_paged_decode" if "paged_decode" in case else
                "dtx_paged_multitoken")
        assert kernels == [want], (case, kernels)


# The engine's decode and prefill-chunk programs over a paged bf16 cache, at
# debug size, through the CHIP's compiler with the Mosaic paged kernels: the
# KV leaves of the cache argument are the result's buffers and nothing moves
# a whole leaf or a whole layer of one (tests/test_kv_in_place.py checks the
# same on this process's backend, where a kernel is an emulation).
_IN_PLACE_PROBE = r"""
import json, os, sys
sys.path.insert(0, os.environ["DTX_REPO"])
sys.path.insert(0, os.path.join(os.environ["DTX_REPO"], "tests"))
from scripts.aot_certify import TOPOLOGY_1CHIP, _topo
from jax.sharding import SingleDeviceSharding
import test_kv_in_place as kv

sh = SingleDeviceSharding(_topo(TOPOLOGY_1CHIP).devices[0])
out = {}
for program in ("decode", "prefill_chunk"):
    for kernels in (True, False):
        cache, hlo = kv._compiled("paged-bf16", program, kernels, sharding=sh)
        try:
            kv._assert_in_place(cache, hlo, layer_is_read_whole=False)
            verdict = "ok"
        except AssertionError as e:
            verdict = str(e)[:400]
        out[f"{program}/{'kernels' if kernels else 'gather'}"] = {
            "verdict": verdict, "mosaic_calls": hlo.count("tpu_custom_call")}
print(json.dumps(out))
"""


@pytest.mark.parametrize("case", ["decode/kernels", "decode/gather",
                                  "prefill_chunk/kernels",
                                  "prefill_chunk/gather"])
def test_engine_programs_write_the_kv_pool_in_place_for_v5e(case, in_place_doc):
    got = in_place_doc[case]
    assert got["verdict"] == "ok", got
    assert (got["mosaic_calls"] > 0) == case.endswith("kernels"), got


@pytest.fixture(scope="module")
def in_place_doc():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    return _run_probe(_IN_PLACE_PROBE, timeout=600)


# One expert layer at the published widths of the benchmark's two sparse-expert
# configurations, at the rows of their decode steps and of a 256-token prefill
# chunk, with a run's stacked weights and ``layer=`` as ``models/hybrid.py``
# passes them: the grouped matmuls must be the ``dtx_moe_gmm`` Mosaic kernels
# (gate, up and the activation in one, down in the other: the device trace
# books them by their scope and shows them by that name), nothing may copy an
# expert leaf, and a shape the rule leaves to ``jax.lax.ragged_dot`` (every
# expert held, long rows) must still become XLA's own Mosaic kernels.
_EXPERTS_PROBE = r"""
import json, os, re
os.environ["DTX_PALLAS_INTERPRET"] = "0"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from datatunerx_tpu.ops import moe

sh = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
k = 8
# name: rows, D, F, experts in all, held, layers of the run, groups, groups kept
CASES = {"mimo/decode": (64, 4096, 2048, 256, 16, 5, 1, 1),
         "mimo/chunk": (256, 4096, 2048, 256, 16, 5, 1, 1),
         "ling/decode": (128, 2560, 768, 512, 64, 5, 8, 4),
         "ling/chunk": (256, 2560, 768, 512, 64, 5, 8, 4),
         "all_held/long": (1024, 4096, 2048, 8, 8, 2, 1, 1)}
out = {}
for name, (rows, D, F, E, Eh, n, n_group, topk_group) in CASES.items():
    p = {"router": {"kernel": sds((D, E), jnp.bfloat16)},
         "e_score_correction_bias": sds((E,), jnp.bfloat16),
         "experts": {"gate_proj": sds((n, Eh, D, F), jnp.bfloat16),
                     "up_proj": sds((n, Eh, D, F), jnp.bfloat16),
                     "down_proj": sds((n, Eh, F, D), jnp.bfloat16)}}
    fn = lambda x, valid, p, layer: moe.expert_layer(
        x, valid, p, experts_total=E, experts_held=Eh, first_held=0, top_k=k,
        normalize=True, scaling=1.0, layer=layer, n_group=n_group,
        topk_group=topk_group)
    text = jax.jit(fn).lower(sds((rows, D), jnp.bfloat16), sds((rows,), jnp.bool_),
                             p, sds((), jnp.int32)).compile().as_text()
    leaf = r"bf16\[(%d,)?%d,(%d,%d|%d,%d)\]" % (n, Eh, D, F, F, D)
    out[name] = {"chosen": list(moe.grouped_matmul(rows, top_k=k, experts_total=E, d=D, f=F)),
                 "gmm": len(re.findall(r"%dtx_moe_gmm[.\w]* = ", text)),
                 "in_scope": len(re.findall(r"%dtx_moe_gmm[.\w]* = .*dtx\.moe_experts", text)),
                 "ragged": text.count("%ragged-dot-none"),
                 "ragged_tiling": sorted(set(re.findall(r'ragged_dot_tiling="([\d,]+)"', text))),
                 "mosaic": text.count('custom_call_target="tpu_custom_call"'),
                 "leaf_copies": len(re.findall(r" = " + leaf + r"[^ ]* copy\(", text))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def experts_doc():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    return _run_probe(_EXPERTS_PROBE, timeout=600)


@pytest.mark.parametrize("case,tm", [("mimo/decode", 16), ("mimo/chunk", 64),
                                     ("ling/decode", 16), ("ling/chunk", 32)])
def test_expert_layer_compiles_to_grouped_matmul_kernels_for_v5e(case, tm, experts_doc):
    seen = experts_doc[case]
    assert seen["chosen"] == ["dtx_moe_gmm", tm], seen
    # two named kernels under the experts' scope, and no ragged-dot beside them
    assert seen["gmm"] == seen["in_scope"] == seen["mosaic"] == 2, seen
    assert seen["ragged"] == 0 and seen["leaf_copies"] == 0, seen


def test_long_groups_stay_on_xlas_grouped_matmul_for_v5e(experts_doc):
    seen = experts_doc["all_held/long"]
    assert seen["chosen"] == ["ragged_dot", None], seen
    # gate, up and down: three ragged-dot Mosaic kernels at XLA's own tiling
    assert seen["gmm"] == 0 and seen["ragged"] >= 3 and seen["mosaic"] >= 3, seen
    assert seen["ragged_tiling"] and seen["leaf_copies"] == 0, seen


# The training kernels at the shape the benchmark's cell mistral-train-lora
# runs them at (8 rows of 1,024, 32 heads over 8 KV heads of 128, bf16, packed
# segments): forward and both backward kernels, window-less as the cell emits
# them (rows shorter than Mistral's window) and under a window that binds and
# skips tiles (rows of 2,048, window 256), which no cell reaches.
_FLASH_PROBE = r"""
import json, os
os.environ["DTX_PALLAS_INTERPRET"] = "0"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from datatunerx_tpu.ops.flash_attention import flash_attention

sh = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
out = {}
for T, window in ((1024, 4096), (2048, 256)):
    def loss(q, k, v, seg):
        return flash_attention(q, k, v, segment_ids=seg,
                               sliding_window=window).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((8, T, 32, 128), jnp.bfloat16), sds((8, T, 8, 128), jnp.bfloat16),
        sds((8, T, 8, 128), jnp.bfloat16), sds((8, T), jnp.int32)
    ).compile().as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    out[f"T{T}_w{window}"] = [k for k in (
        "dtx_flash_fwd", "dtx_flash_bwd_dq", "dtx_flash_bwd_dkv")
        if sum(k + ")" in l for l in calls) == 1]
print(json.dumps(out))
"""


def test_flash_kernels_compile_for_v5e_at_the_training_cell_shape():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    doc = _run_probe(_FLASH_PROBE, timeout=600)
    assert sorted(doc) == ["T1024_w4096", "T2048_w256"]
    for case, kernels in doc.items():
        # one Mosaic custom call each, its pallas_call's name in its op_name:
        # the device trace of the cell shows the three under train.attn_share
        assert kernels == ["dtx_flash_fwd", "dtx_flash_bwd_dq",
                           "dtx_flash_bwd_dkv"], (case, kernels)


# The decode program (128 slots, 8 token steps) and the 256-token prefill-chunk
# program of the benchmark's cell ling-serve-decode at its published widths and
# its engine settings, adapters on q_proj and o_proj: three scanned blocks (KDA
# + dense, KDA + experts, MLA + experts), the recurrent state [6, 128, 32, 128,
# 128] float32 donated with the cache. The chip's compiler must take them, fit
# them in one chip's 16 GB beside their arguments, alias the state leaves to
# the result (nothing copies 1.6 GB of state), and run the expert layers'
# grouped matmuls as ``dtx_moe_gmm`` Mosaic kernels.
# The same probe takes the cell granite-serve-chat (``DTX_CELL``): nine scanned
# blocks (36 Mamba-2 layers in five runs around 4 attention layers), the
# recurrent state [36, 64, 64, 64, 128] float32 donated with the cache, adapters
# on q_proj, in_proj and o_proj, each in the runs that have the projection.
# And the cell glm-serve-docs: two scanned blocks (latent attention with the
# indexer over a dense layer, then over four expert layers), 16 slots of up to
# 8,704 tokens, the latent pool [5, 8704, 16, 640] and the index-key pool
# [5, 8704, 16, 128] donated with the cache, adapters on q_b_proj and o_proj.
# And the cell kimi-serve-agent: the same two blocks without the indexer, under
# YaRN, 16 slots of up to 12,288 tokens, the latent pool [5, 18432, 16, 640].
_CELL_PROBE = r"""
import json, os, re, sys
os.environ["DTX_PALLAS_INTERPRET"] = "0"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.environ["DTX_REPO"], "benchmarks"))
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import spec
from datatunerx_tpu.models import init_params
from datatunerx_tpu.models.lora import lora_groups
from datatunerx_tpu.ops.paged_attention import init_paged_cache, state_leaf_keys
from datatunerx_tpu.serving.batched_engine import MAX_STOP, _Programs

cell = spec.load_cell(os.environ["DTX_CELL"])
# the cells' engines ask for the paged kernels ("auto" on a TPU): a sink-less
# softmax-attention kind's token step then takes the decode kernel
cfg = spec.register_preset(cell, paged_kernel=True)
eng = cell.workload["engine"]
S, bs, NB, L = eng["slots"], eng["kv_block_size"], eng["kv_blocks"], eng["max_seq_len"]
sh = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
z = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
sds = lambda tree: jax.tree_util.tree_map(lambda x: z(x.shape, x.dtype), tree)
params = sds(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
cache = sds(jax.eval_shape(lambda: init_paged_cache(cfg, S, NB, bs, L // bs, dtype=jnp.bfloat16)))
E, r = 3, cell.workload["adapters"]["rank"]  # base + two adapters
lora = ({"layers": {key: {t: {"a": z((n, E, dims[t][0], r), jnp.bfloat16),
                              "b": z((n, E, r, dims[t][1]), jnp.bfloat16)}
                          for t in cell.workload["adapters"]["targets"] if t in dims}
                    for key, n, dims in lora_groups(cfg)}}, z((E,), jnp.float32))
progs = _Programs(cfg, L, None, epilogue="kernel")
state = sum(cache[k].size * cache[k].dtype.itemsize for k in state_leaf_keys(cache))
row = z((1, 256), jnp.int32)
cases = {
    "decode": lambda: progs.decode.lower(
        params, lora, cache, z((S, cfg.vocab_size), jnp.float32), z((S,), jnp.int32),
        z((S,), jnp.int32), z((S,), jnp.bool_), z((S, 2), jnp.uint32), z((S,), jnp.float32),
        z((S,), jnp.float32), z((S, MAX_STOP), jnp.int32), z((S,), jnp.int32),
        K=eng["decode_chunk"], mode="greedy"),
    "prefill_chunk_256": lambda: progs.prefill_chunk.lower(
        params, lora, cache, z((), jnp.int32), row, row, row, z((), jnp.int32), chunk_len=256),
}
out = {"state_bytes": state}
ssm_leaf = r" = f32\[%s\]" % ",".join(map(str, cache["state_ssm"].shape)) if "state_ssm" in cache else "no such leaf"
pool_leaf = r" = bf16\[%s\]" % ",".join(map(str, cache["k_mla"].shape)) if "k_mla" in cache else "no such leaf"
idx_leaf = r" = bf16\[%s\]" % ",".join(map(str, cache["k_idx"].shape)) if "k_idx" in cache else "no such leaf"
out["pool_bytes"] = sum(cache[k].size * cache[k].dtype.itemsize for k in cache if k[:2] == "k_")
for name, lower in cases.items():
    c = lower().compile()
    m, text = c.memory_analysis(), c.as_text()
    out[name] = {"live": m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes,
                 "alias": m.alias_size_in_bytes, "arguments": m.argument_size_in_bytes,
                 "temporaries": m.temp_size_in_bytes, "ragged": text.count("%ragged-dot"),
                 "gmm": text.count("%dtx_moe_gmm"),
                 # the attention layers' token step: the paged decode kernel's call sites (under
                 # their scope), and what still copies a gathered view of every slot's table
                 "paged_decode_in_scope": len(re.findall(r"%dtx_paged_decode[.\w]* = .*dtx\.attn", text)),
                 "view_copies": len(re.findall(r" = bf16\[%d,%d,[^ ]* copy\(" % (S, L), text)),
                 # the state-space token step: its kernel's call sites (under their scope), and
                 # what else still produces or copies the whole state leaf
                 "ssm_step": len(re.findall(r"%dtx_ssm_step[.\w]* = ", text)),
                 "ssm_step_in_scope": len(re.findall(r"%dtx_ssm_step[.\w]* = .*dtx\.ssm_state", text)),
                 "ssm_leaf_fusions": len(re.findall(ssm_leaf + r"[^ ]* fusion\(", text)),
                 "ssm_leaf_copies": len(re.findall(ssm_leaf + r"[^ ]* copy\(", text)),
                 # the selection: the index scores' exact top-k is a stable sort of every lane of
                 # a slot's view; the latent pool is never re-laid at the program's edge
                 "dsa_sorts": len(re.findall(r" sort\(.*dtx\.dsa_select", text)),
                 "dsa_scopes": [s for s in ("dtx.dsa_index", "dtx.dsa_select", "dtx.dsa_gather")
                                if s in text],
                 "pool_copies": len(re.findall(pool_leaf + r"[^ ]* copy\(", text))
                 + len(re.findall(idx_leaf + r"[^ ]* copy\(", text)),
                 # a latent kind's chunk: one conditional a run of layers, a branch of static
                 # width a count of steps; the widths of its attention's float32 logits
                 "select_conds": [len(m.split(",")) for m in re.findall(
                     r" conditional\(.*branch_computations=\{([^}]*)\}.*dtx\.layers", text)],
                 "logit_widths": sorted({int(w) for w in re.findall(
                     r" = f32\[1,(?:1,)?%d,256,(\d+)\]" % cfg.num_heads, text)} - {cfg.kv_lora_rank}),
                 "scopes": [s for s in ("dtx.kda_conv", "dtx.kda_state", "dtx.kda_out",
                                        "dtx.mla_absorb", "dtx.moe_shared", "dtx.ssm_conv",
                                        "dtx.ssm_state", "dtx.ssm_out") if s in text]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def ling_doc():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    return _run_probe(_CELL_PROBE, timeout=900, DTX_CELL="ling-serve-decode")


@pytest.mark.parametrize("program", ["decode", "prefill_chunk_256"])
def test_ling_cell_programs_compile_for_v5e_at_published_widths(program, ling_doc):
    got = ling_doc[program]
    assert ling_doc["state_bytes"] == 6 * 128 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert got["live"] < 13e9, got  # one chip holds 16 GB; the engine keeps logits and adapters beside it
    assert got["alias"] >= ling_doc["state_bytes"], got  # the donated state is written in place
    # gate with up, and down, in two runs of expert layers; no ragged-dot left
    assert got["gmm"] >= 4 and got["ragged"] == 0, got
    assert got["scopes"] == ["dtx.kda_conv", "dtx.kda_state", "dtx.kda_out",
                             "dtx.mla_absorb", "dtx.moe_shared"], got
    # the one latent layer's chunk attends over the lanes its context reaches (ops/mla.py:view_steps):
    # a step of 1,024 lanes or the table's 1,536; the token step reads its table-wide view
    assert got["logit_widths"] == ([1024, 1536] if program == "prefill_chunk_256" else []), got


@pytest.fixture(scope="module")
def granite_doc():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    return _run_probe(_CELL_PROBE, timeout=900, DTX_CELL="granite-serve-chat")


@pytest.mark.parametrize("program,arguments,temporaries", [
    ("decode", 11.9e9, 1.6e9), ("prefill_chunk_256", 11.9e9, 0.15e9)])  # read: 11.869 + 1.531, 11.843 + 0.116 GB
def test_granite_cell_programs_compile_for_v5e_at_full_depth(program, arguments, temporaries, granite_doc):
    """All 40 layers at the published widths, 64 slots: the numbers quoted in
    ``benchmarks/workloads/granite-serve-chat.json``'s ``engine_notes``."""
    got = granite_doc[program]
    # 36 Mamba-2 layers x 64 slots x (64 x 64 x 128 float32 + 3 x 4,352 bf16): 76.4 MB a slot
    assert granite_doc["state_bytes"] == 36 * 64 * (2097152 + 26112) == 64 * 76437504
    assert got["arguments"] < arguments and got["temporaries"] < temporaries, got
    # one chip holds 16 GB. The decode program reads 13.400 GB, as before its token step was a Mosaic
    # call: without the barrier in front of the call (ops/pallas_ssm.py) XLA kept the adapters'
    # stacks in its default layout inside the loops and copied them there (13.570 GB, 1.701 of
    # temporaries)
    assert got["live"] < 13.5e9, got
    assert got["alias"] >= granite_doc["state_bytes"], got  # the donated state is written in place
    assert got["gmm"] == 0 and got["ragged"] == 0, got  # no routed experts at all
    assert got["scopes"] == ["dtx.ssm_conv", "dtx.ssm_state", "dtx.ssm_out"], got
    if program == "decode":
        # the token step is ``dtx_ssm_step``, one call site a run of Mamba-2 layers (5, 9, 9, 9, 4),
        # under its scope, and it steps the leaf in place inside the layer scan and the 8-step loop:
        # no fusion still produces the leaf (XLA's step was a select_dynamic-update-slice fusion a
        # run, and a second fusion read the old state again) and nothing copies it
        assert got["ssm_step"] == got["ssm_step_in_scope"] == 5, got
        assert got["ssm_leaf_fusions"] == 0 and got["ssm_leaf_copies"] == 0, got
        # the four attention layers (each a run of its own) read their blocks in place through
        # ``dtx_paged_decode``: no view [64, 1024, 8, 64] of a pool is gathered and re-tiled (the
        # gather path had 8 such copies and 1.531 GB of temporaries; this reads 1.341)
        assert got["paged_decode_in_scope"] == 4 and got["view_copies"] == 0, got
        assert got["temporaries"] < 1.4e9, got
    else:  # a chunk of prompt tokens takes ``ssm.chunk_states`` and the gathered view, as before
        assert got["ssm_step"] == 0 and got["ssm_leaf_copies"] == 0, got
        assert got["paged_decode_in_scope"] == 0, got


@pytest.fixture(scope="module")
def glm_doc():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    return _run_probe(_CELL_PROBE, timeout=900, DTX_CELL="glm-serve-docs")


@pytest.mark.parametrize("program,temporaries,paths,sorts", [
    ("decode", 0.7e9, ["dtx.dsa_index", "dtx.dsa_select", "dtx.dsa_gather"], 2),  # read: 8.901 + 0.616 GB
    # read: 8.899 + 0.596 GB; 0.607 before the chunk's view followed its reach (its widest branch)
    ("prefill_chunk_256", 0.61e9, ["dtx.dsa_index", "dtx.dsa_select"], 0)])
def test_glm_cell_programs_compile_for_v5e_at_published_widths(program, temporaries, paths, sorts, glm_doc):
    """Five layers at the published widths, 16 slots of 8,704 tokens: the
    numbers quoted in ``benchmarks/workloads/glm-serve-docs.json``'s
    ``engine_notes``."""
    got = glm_doc[program]
    # two pools: 8,704 blocks x 16 tokens x 5 layers x (640 + 128) bf16 values
    assert glm_doc["pool_bytes"] == 8704 * 16 * 5 * (640 + 128) * 2 and glm_doc["state_bytes"] == 0
    assert got["arguments"] < 9.0e9 and got["temporaries"] < temporaries, got
    assert got["live"] < 9.7e9, got  # one chip holds 16 GB
    assert got["alias"] >= glm_doc["pool_bytes"], got  # both donated pools are written in place
    # the latent pool is never converted at the program's edge: with rows of 576 lanes (4.5 lane
    # tiles) XLA kept it in a layout of its own inside the loops and copied all 0.8 GB in and
    # out at every dispatch (2 copies, temporaries 1.505 GB); rows of whole lane tiles cure it
    assert got["pool_copies"] == 0, got
    assert got["gmm"] >= 2 and got["ragged"] == 0, got
    # a token step sorts a slot's index scores (one call site a run of layers) and gathers its
    # chosen rows; a chunk masks the view it already reads and finds its mask with no sort
    assert got["dsa_scopes"] == paths and got["dsa_sorts"] == sorts, got
    assert got["scopes"] == ["dtx.mla_absorb", "dtx.moe_shared"], got
    # a chunk scores, ranks and attends over the lanes its context reaches: ONE conditional a run
    # of layers, a branch of static width a count of steps (2,048 / 4,096 / 6,144 / 8,192 / 8,704
    # lanes; the first picks all it sees); the branches read the pools as operands (no copy above)
    # and return the attention's output, so the temporaries are the widest branch's: no more than
    # the table-wide chunk's 0.607 GB. The token step: no conditional
    if program == "decode":
        assert got["select_conds"] == [], got
    else:
        assert got["select_conds"] == [5, 5], got
        assert got["logit_widths"] == [2048, 4096, 6144, 8192, 8704], got

@pytest.fixture(scope="module")
def kimi_doc():
    pytest.importorskip("libtpu")  # the TPU compiler; absent from jax[cpu]
    return _run_probe(_CELL_PROBE, timeout=900, DTX_CELL="kimi-serve-agent")


@pytest.mark.parametrize("program,temporaries,logits", [
    ("decode", 0.6e9, []),                     # read: 8.891 + 0.572 GB
    # read: 8.889 + 0.824 GB; 0.829 before the chunk's view followed its reach (its widest branch)
    ("prefill_chunk_256", 0.85e9, list(range(1024, 13312, 1024)))])
def test_kimi_cell_programs_compile_for_v5e_at_published_widths(program, temporaries, logits, kimi_doc):
    """Five layers at the published widths, 16 slots of 12,288 tokens over a
    pool of 18,432 blocks: the numbers quoted in
    ``benchmarks/workloads/kimi-serve-agent.json``'s ``engine_notes``."""
    got = kimi_doc[program]
    # one pool: 18,432 blocks x 16 tokens x 5 layers x 640 bf16 values (576 in whole lane tiles)
    assert kimi_doc["pool_bytes"] == 18432 * 16 * 5 * 640 * 2 and kimi_doc["state_bytes"] == 0
    assert got["arguments"] < 8.95e9 and got["temporaries"] < temporaries, got
    assert got["live"] < 9.8e9, got  # one chip holds 16 GB
    assert got["alias"] >= kimi_doc["pool_bytes"], got  # the donated pool is written in place
    # neither program re-lays the pool: with rows of 576 lanes both copied all 1.7 GB in and out
    # (2 copies each, temporaries 2.268 and 1.936 GB: compiled once with ``MlaKind.pools`` patched)
    assert got["pool_copies"] == 0, got
    assert got["gmm"] >= 2 and got["ragged"] == 0, got
    # dense latent attention: no indexer, no sort. A chunk attends over the lanes its context
    # reaches: ONE conditional a run of layers, a branch of static width a count of steps of
    # 1,024 lanes (ops/mla.py:view_steps), each plain latent attention over its width; the
    # branches read the pool as an operand (no copy above) and return the attention's output,
    # so the temporaries are the widest branch's, the table-wide chunk's. The token step: no
    # conditional, and no float32 logits a chunk wide
    assert got["dsa_scopes"] == [] and got["dsa_sorts"] == 0, got
    assert got["select_conds"] == ([12, 12] if logits else []), got
    assert got["logit_widths"] == logits and got["paged_decode_in_scope"] == 0, got
    assert got["scopes"] == ["dtx.mla_absorb", "dtx.moe_shared"], got


@pytest.mark.slow
def test_aot_pipeline_compiles_for_v5e_target():
    assert _run_probe(_PROBE, timeout=900)["ok"] is True
