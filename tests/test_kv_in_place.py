"""The KV cache is written in place: the layer scan of ``models/llama.py``
carries the stacked leaves instead of slicing a layer out and stacking it
back, and every program of ``serving/batched_engine.py:_Programs`` that
returns the cache consumes the one it is given.

- structure: the compiled decode and prefill-chunk programs alias every KV
  leaf of their cache argument to their result, and no ``copy``,
  ``dynamic-slice`` or ``dynamic-update-slice`` in them moves a whole leaf or
  a whole layer of one;
- parity: a prefill chunk and K decode steps through the carried forward give
  the tokens and, bitwise on the gather path, the cache of a per-layer
  reference kept here (the form the forward had before: slice a layer, write,
  restack);
- training: with no cache the traced program is the one it was before the
  cache moved into the carry;
- the engine: a chunk call that fails while it is traced leaves the engine's
  cache usable, and ``dtxlint``'s donation rule finds nothing in ``serving/``.
"""

import dataclasses
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datatunerx_tpu.models.config import PRESETS
from datatunerx_tpu.models.llama import forward, init_cache, init_params, rms_norm
from datatunerx_tpu.ops.attention import (
    cache_positions_update,
    kv_cache_width,
    kv_dequantize,
    kv_quantize,
    make_causal_bias,
    xla_attention,
)
from datatunerx_tpu.ops.paged_attention import init_paged_cache, kv_leaf_keys
from datatunerx_tpu.ops.rope import apply_rope, rope_cos_sin

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# sizes chosen so that the element count of a KV leaf and of one layer of it
# is no other array's in the program: 11 blocks of 16 tokens (a layer of k is
# 11 * 16 * 32 = 5,632 elements, its scales 352), 3 dense rows of 40 lanes
SLOTS, BLOCK, NBPS, BLOCKS = 2, 16, 4, 11
DENSE_ROWS, DENSE_LANES = 3, 40

KINDS = {
    "paged-bf16": dict(paged=True, quantize=None),
    "paged-int8": dict(paged=True, quantize="int8"),
    "dense-per-slot": dict(paged=False, quantize=None),
}


def _cache(cfg, kind, dtype=jnp.bfloat16):
    spec = KINDS[kind]
    if spec["paged"]:
        cache = init_paged_cache(cfg, SLOTS, BLOCKS, BLOCK, NBPS, dtype=dtype,
                                 quantize=spec["quantize"])
        # slot 0 holds blocks 1, 4, 7, 10 and slot 1 blocks 0, 3, 6: not in order
        cache["block_tables"] = jnp.asarray(
            [[1, 4, 7, 10], [0, 3, 6, -1]], jnp.int32)
        return cache
    return init_cache(cfg, DENSE_ROWS, DENSE_LANES, dtype=dtype, per_slot=True,
                      quantize=spec["quantize"])


# ------------------------------------------------------------------ structure

def _compiled(kind, program, kernels, sharding=None):
    """(cache, compiled HLO text) of one program of the engine at debug size;
    ``sharding`` compiles for a described device instead of this process's
    (tests/test_aot_certify.py: the chip's compiler, Mosaic kernels)."""
    from datatunerx_tpu.serving.batched_engine import MAX_STOP, _Programs

    cfg = dataclasses.replace(PRESETS["debug"], paged_kernel=kernels)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    cache = _cache(cfg, kind)
    progs = _Programs(cfg, NBPS * BLOCK, KINDS[kind]["quantize"],
                      epilogue="xla")
    S = cache["len"].shape[0]
    if program == "decode":
        args = (params, None, cache,
                jnp.zeros((S, cfg.vocab_size), jnp.float32),
                jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.int32),
                jnp.ones((S,), bool), jnp.zeros((S, 2), jnp.uint32),
                jnp.ones((S,), jnp.float32), jnp.ones((S,), jnp.float32),
                jnp.full((S, MAX_STOP), -1, jnp.int32),
                jnp.zeros((S,), jnp.int32))
        fn, static = progs.decode, dict(K=3, mode="greedy")
    else:
        row = jnp.zeros((1, 32), jnp.int32)
        args = (params, None, cache, jnp.asarray(0, jnp.int32), row, row + 1,
                row, jnp.asarray(0, jnp.int32))
        fn, static = progs.prefill_chunk, dict(chunk_len=32)
    if sharding is not None:
        args = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), args)
    return cache, fn.lower(*args, **static).compile().as_text()


_SHAPE = r"(\w+)\[([\d,]*)\]"


def _elements(dims: str) -> int:
    return int(np.prod([int(n) for n in dims.split(",") if n] or [1]))


def _shapes_by_name(hlo: str) -> dict:
    """{instruction or parameter name: (dtype, elements)} of a module's text."""
    out = {}
    for name, dtype, dims in re.findall(
            r"%?([\w.\-]+)(?: =|:) " + _SHAPE, hlo):
        out[name] = (dtype, _elements(dims))
    return out


def _computations(hlo: str):
    """The text of each computation of a module."""
    return re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\{\n)", hlo)


def _whole_pool_moves(hlo: str, leaf_sizes: set, layer_sizes: set,
                      layer_is_read_whole: bool) -> list:
    """Instructions that move a whole leaf or a whole layer of one: a
    ``copy`` or ``dynamic-slice`` with such a result, a
    ``dynamic-update-slice`` with such an UPDATE (operand 0 is the buffer it
    writes into, in place). The sizes are (dtype, elements). A dense layer is
    read whole by attention (every row, full width), so there a layer-sized
    ``dynamic-slice`` or ``copy`` IS the read and only a layer-sized update
    or a leaf-sized anything is a fault; a paged layer is only ever read
    through the block tables. On the CPU a Pallas kernel is emulated by loops
    that copy their operands: computations of the emulation are passed over
    (the compile for the chip, tests/test_aot_certify.py, has the kernel as
    one custom call)."""
    shapes = _shapes_by_name(hlo)
    found = []
    for text in _computations(hlo):
        if re.search(r'op_name="[^"]*/dtx_paged_(decode|multitoken)/', text):
            continue
        for line in text.splitlines():
            m = re.search(
                r"%?([\w.\-]+) = " + _SHAPE + r"\S* "
                r"(copy|dynamic-slice|dynamic-update-slice)\(([^)]*)\)", line)
            if not m:
                continue
            name, dtype, dims, op, operands = m.groups()
            moved = (dtype, _elements(dims))
            if op == "dynamic-update-slice":
                update = operands.split(",")[1].strip().lstrip("%")
                moved = shapes.get(update, (None, 0))
            elif layer_is_read_whole and moved in layer_sizes:
                continue
            if moved in leaf_sizes | layer_sizes:
                found.append(f"{op} {name} moves {moved[0]}[{moved[1]}]")
    return found


STRUCTURE_CASES = [
    (kind, program, kernels)
    for kind in KINDS for program in ("decode", "prefill_chunk")
    for kernels in (False, True)
    if KINDS[kind]["paged"] or (program == "decode" and not kernels)
]


@pytest.mark.parametrize("kind,program,kernels", STRUCTURE_CASES)
def test_program_aliases_every_kv_leaf_and_moves_no_whole_layer(
        kind, program, kernels):
    cache, hlo = _compiled(kind, program, kernels)
    _assert_in_place(cache, hlo, layer_is_read_whole=not KINDS[kind]["paged"])


def _assert_in_place(cache, hlo, layer_is_read_whole):
    leaves = [cache[key] for key in kv_leaf_keys(cache)]
    L = leaves[0].shape[0]
    dt = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}

    # every KV leaf of the argument is the result's buffer
    header = hlo[:hlo.index("\n")]
    aliased = {int(n) for n in re.findall(
        r"\{\d+(?:, \d+)*\}: \((\d+), \{\}", header.split(
            "input_output_alias=")[1])}
    entry = hlo[hlo.index("ENTRY "):]
    params = {int(n): (dtype, dims) for dtype, dims, n in re.findall(
        _SHAPE + r"\S* parameter\((\d+)\)", entry)}
    for leaf in leaves:
        want = (dt[str(leaf.dtype)], ",".join(str(n) for n in leaf.shape))
        have = [n for n in aliased if params.get(n) == want]
        need = sum(x.shape == leaf.shape and x.dtype == leaf.dtype
                   for x in leaves)
        assert len(have) >= need, (want, sorted(aliased), params)

    # and nothing moves a leaf or a layer of one
    moves = _whole_pool_moves(
        hlo, {(dt[str(x.dtype)], x.size) for x in leaves},
        {(dt[str(x.dtype)], x.size // L) for x in leaves},
        layer_is_read_whole=layer_is_read_whole)
    assert not moves, moves


# --------------------------------------------------------------------- parity

def _reference_forward(params, tokens, cfg, positions, attention_mask, cache):
    """``models/llama.py:forward`` over a cache as it was before the leaves
    moved into the scan's carry, in plain ``jax.numpy``: every layer slices
    its ``[rows, lanes, ...]`` out of the stacked leaves, writes its tokens
    into the slice, reads the slice back, and the layers' slices are stacked
    into new leaves."""
    B, T = tokens.shape
    L, KV, d = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    x = params["embed_tokens"]["embedding"][tokens]
    cos, sin = rope_cos_sin(
        positions, d, theta=cfg.rope_theta,
        scaling_type=cfg.rope_scaling_type,
        scaling_factor=cfg.rope_scaling_factor, max_seq_len=cfg.max_seq_len,
        seq_len=kv_cache_width(cache))
    cache_pos, kv_positions = cache_positions_update(
        cache, positions, attention_mask)
    bias = make_causal_bias(positions, kv_positions, None)
    lens = cache["len"]
    idx = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if "block_tables" in cache:
        tables = cache["block_tables"]
        num_blocks, bs = cache["pos"].shape
        blk = idx // bs
        entry = jnp.take_along_axis(
            tables, jnp.clip(blk, 0, tables.shape[1] - 1), axis=1)
        rows = jnp.where((blk < tables.shape[1]) & (entry >= 0), entry,
                         num_blocks)  # out of bounds: the scatter drops it
        cols = idx % bs
        gather = jnp.where(tables >= 0, tables, 0)

        def view(layer):
            got = layer[gather]  # [B, n, bs, ...]
            return got.reshape((B, -1) + got.shape[3:])
    else:
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        cols = idx

        def view(layer):
            return layer

    quant = "k_scale" in cache
    stacked = {key: [] for key in kv_leaf_keys(cache)}
    for layer_idx in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[layer_idx], params["layers"])

        def proj(h, name):
            return h @ lp[name]["kernel"].astype(h.dtype)

        h = rms_norm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
        q = apply_rope(proj(h, "q_proj").reshape(B, T, cfg.num_heads, d), cos, sin)
        k = apply_rope(proj(h, "k_proj").reshape(B, T, KV, d), cos, sin)
        v = proj(h, "v_proj").reshape(B, T, KV, d)
        att = {}
        for name, new in (("k", k), ("v", v)):
            layer = cache[name][layer_idx]            # slice
            if quant:
                new, scale = kv_quantize(new)
                scales = cache[name + "_scale"][layer_idx].at[rows, cols].set(scale)
                stacked[name + "_scale"].append(scales)
            layer = layer.at[rows, cols].set(           # write
                new.astype(layer.dtype).reshape(B, T, KV * d))
            stacked[name].append(layer)
            read = view(layer)
            read = read.reshape(read.shape[:2] + (KV, d))
            att[name] = (kv_dequantize(read, view(scales), x.dtype)
                         if quant else read.astype(x.dtype))
        attn = xla_attention(q, att["k"], att["v"], bias)
        x = x + proj(attn.reshape(B, T, cfg.q_dim), "o_proj")
        h = rms_norm(x, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
        x = x + proj(jax.nn.silu(proj(h, "gate_proj")) * proj(h, "up_proj"),
                     "down_proj")
    x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = (x @ params["lm_head"]["kernel"].astype(x.dtype)).astype(jnp.float32)
    new = {key: jnp.stack(layers) for key, layers in stacked.items()}  # restack
    new.update(len=lens + T, pos=cache_pos)
    if "block_tables" in cache:
        new["block_tables"] = cache["block_tables"]
    return logits, new


def _serve(step, params, cfg, cache, prompt, mask, steps):
    """One ragged prefill chunk, then ``steps`` greedy token steps: the tokens
    and the cache after them."""
    B, T = prompt.shape
    pos = jnp.cumsum(mask, axis=1) - 1
    logits, cache = step(params, prompt, cfg, jnp.maximum(pos, 0), mask, cache)
    at = pos[:, -1] + 1
    out = []
    for _ in range(steps):
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
        logits, cache = step(params, tok[:, None], cfg, at[:, None],
                             jnp.ones((B, 1), jnp.int32), cache)
        at = at + 1
    return np.stack(out), cache


@pytest.mark.parametrize("kind,kernels", [
    (kind, kernels) for kind in KINDS for kernels in (False, True)
    if KINDS[kind]["paged"] or not kernels])  # the kernels read a paged cache
def test_carried_forward_matches_the_per_layer_reference(kind, kernels):
    # float32 throughout: XLA's CPU backend computes a fused chain of bf16
    # ops in float32 and rounds where the fusion ends, so two programs of
    # other structure agree bitwise in float32 only
    cfg = PRESETS["debug"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = _cache(cfg, kind, dtype=jnp.float32)
    B = cache["len"].shape[0]
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, 24), 0,
                                cfg.vocab_size, jnp.int32)
    # rows of different depths, pads at the left as the engine lays them
    depth = jnp.asarray([24, 9, 17][:B])[:, None]
    mask = (jnp.arange(24)[None, :] >= 24 - depth).astype(jnp.int32)
    run_cfg = dataclasses.replace(cfg, paged_kernel=kernels)

    @jax.jit
    def carried(params, tokens, positions, mask, cache):
        return forward(params, tokens, run_cfg, positions=positions,
                       attention_mask=mask, cache=cache)

    @jax.jit
    def reference(params, tokens, positions, mask, cache):
        return _reference_forward(params, tokens, cfg, positions, mask, cache)

    toks, got = _serve(lambda p, t, c, pos, m, ca: carried(p, t, pos, m, ca),
                       params, cfg, cache, prompt, mask, steps=4)
    want_toks, want = _serve(
        lambda p, t, c, pos, m, ca: reference(p, t, pos, m, ca),
        params, cfg, cache, prompt, mask, steps=4)
    np.testing.assert_array_equal(toks, want_toks)
    assert sorted(got) == sorted(want)
    for key in want:
        a, b = np.asarray(got[key]), np.asarray(want[key])
        if not kernels or key not in kv_leaf_keys(want):
            np.testing.assert_array_equal(a, b, err_msg=key)
            continue
        # the kernels sum a softmax's terms in another order than the gather
        # path (1e-7 relative in float32): what layer 0 writes does not pass
        # through attention and is bitwise the reference's, deeper layers
        # agree to that order (an int8 value may round the other way)
        np.testing.assert_array_equal(a[0], b[0], err_msg=key)
        np.testing.assert_allclose(
            a.astype(np.float32), b.astype(np.float32), rtol=1e-4,
            atol=1 if a.dtype == np.int8 else 1e-6, err_msg=key)


# ------------------------------------------------------------------- training

# sha256 of ``str(jax.make_jaxpr(grad(loss)))`` on preset:debug as the parent
# of the PR that moved the cache into the carry (a43349c) traced it. A PR that
# changes the training forward on purpose replaces them.
TRAINING_JAXPR = {"full": "a04fc3d65c2e78b2", "none": "c48625e32baa6b9b"}


@pytest.mark.parametrize("remat", sorted(TRAINING_JAXPR))
def test_forward_without_a_cache_traces_the_program_it_did(remat):
    cfg = dataclasses.replace(PRESETS["debug"], remat=remat)
    params = init_params(cfg, jax.random.PRNGKey(0))
    L = cfg.num_layers
    lora = {"layers": {
        t: {"a": jnp.zeros((L, cfg.hidden_size, 4)), "b": jnp.zeros((L, 4, d))}
        for t, d in (("q_proj", cfg.q_dim), ("v_proj", cfg.kv_dim))}}
    toks = jnp.zeros((2, 16), jnp.int32)

    def loss(lora, params, toks):
        logits, _ = forward(
            params, toks, cfg, lora=(lora, 2.0),
            segment_ids=jnp.ones_like(toks), lora_dropout=0.1,
            dropout_rng=jax.random.PRNGKey(1), compute_dtype=jnp.bfloat16)
        return logits.sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(lora, params, toks)
    # the layer scan carries the hidden state alone
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert scans and scans[0].params["num_carry"] == 1
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
    assert digest == TRAINING_JAXPR[remat]


# --------------------------------------------------------------------- engine

def test_chunk_call_that_fails_while_traced_leaves_the_cache_usable(monkeypatch):
    """A chunk program that raises while it is traced has consumed nothing:
    the engine fails that request, keeps its cache, and serves the next."""
    from datatunerx_tpu.serving import batched_engine
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine("preset:debug", template="vanilla", max_seq_len=128,
                        slots=2, decode_chunk=2, kv_block_size=16,
                        prefill_chunk=64)
    try:
        prompt = eng.tokenizer.encode("the quick brown fox jumps")
        want = eng.generate(prompt, max_new_tokens=4)

        real = batched_engine.forward

        def refuse(*a, **kw):
            raise ValueError("refused while tracing")

        # a fresh jit of the same impl, so that the call traces again
        monkeypatch.setattr(batched_engine, "forward", refuse)
        progs = batched_engine._Programs(eng.cfg, eng.max_seq_len, None,
                                         eng._epilogue_impl)
        good, eng._prefill_chunk_fn = eng._prefill_chunk_fn, progs.prefill_chunk
        with pytest.raises(RuntimeError, match="refused while tracing"):
            eng.generate(prompt, max_new_tokens=4)
        monkeypatch.setattr(batched_engine, "forward", real)
        eng._prefill_chunk_fn = good

        for leaf in jax.tree_util.tree_leaves(eng._cache):
            assert not leaf.is_deleted()
        assert eng.generate(prompt, max_new_tokens=4) == want
    finally:
        eng.close()


def test_program_that_fails_while_it_runs_stops_the_engine_cleanly():
    """A fault of the running chunk program takes the donated pool with it:
    the request in flight is failed, the scheduler stops, a later submit is
    refused; nothing hangs."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine("preset:debug", template="vanilla", max_seq_len=128,
                        slots=2, decode_chunk=2, kv_block_size=16,
                        prefill_chunk=64)
    try:
        prompt = eng.tokenizer.encode("the quick brown fox jumps")

        def fault(params, lora, cache, *a, **kw):
            for leaf in jax.tree_util.tree_leaves(cache):
                leaf.delete()  # what a failed execution leaves of a donation
            raise RuntimeError("device fault")

        eng._prefill_chunk_fn = fault
        with pytest.raises(RuntimeError, match="KV cache lost"):
            eng.generate(prompt, max_new_tokens=4)
        eng._thread.join(timeout=10)
        assert not eng._thread.is_alive()
        with pytest.raises(RuntimeError, match="shut down"):
            eng.submit(prompt, max_new_tokens=4)
    finally:
        eng.close()


def test_dtxlint_donation_rule_finds_nothing_in_serving():
    from datatunerx_tpu.analysis.core import lint_paths
    from datatunerx_tpu.analysis.rules import all_rules

    result = lint_paths(
        [os.path.join(REPO, "datatunerx_tpu", "serving")],
        rules=[r for r in all_rules() if r.id == "DTX010"])
    assert result.files and not result.findings, result.findings
