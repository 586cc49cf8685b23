"""Fused on-chip sampling epilogue for the decode fast path.

Every decoded token used to pay a full-vocab sampling round-trip after
unembed: ``_sample_jit`` argsorts the whole ``[S, vocab]`` logits row,
softmaxes, cumsums, and draws with ``jax.random.categorical`` — even for
greedy rows, and even though only ONE token id per row leaves the step.
This module consumes the unembed output where it lives and emits just the
``[S]`` token ids, as three static per-batch modes so mixed batches never
materialize the ``[S, vocab]`` distribution on host:

  greedy — plain argmax (one max pass, no exp/sort/cumsum at all).
  simple — temperature sampling, ``top_p == 1`` for every sampled row:
           inverse-CDF over ``softmax(logits / max(t, 1e-6))`` via an
           online max pass + normalizer pass + CDF-crossing pass. Exactly
           the distribution ``sampling_probs(..., top_p=1)`` describes, so
           the speculative rejection rule's exactness is untouched.
  topp   — the ``exact_topp`` nucleus path. Needs a full-vocab sort, which
           Mosaic has no primitive for, so this mode always runs the XLA
           path below (sorted-space inverse-CDF) — still avoiding the
           host round-trip, but not the sort.

Two implementations share one tile walk:

  impl="kernel" — a Pallas kernel (grid ``(S, phases, vocab-tiles)``,
      per-row SMEM carries) for greedy/simple. Engaged on real TPU
      backends; interpret mode emulates it for CPU tests.
  impl="xla"    — a blocked XLA twin that mirrors the kernel's tile walk
      op-for-op (same tile width, same sequential carry adds, same
      first-max-wins / first-crossing tie rules). It is the PARITY ORACLE
      (PR 13 pattern): greedy tokens agree with the kernel bitwise by
      construction (max/compare are order-exact), and sampled tokens agree
      under a fixed seed because both sides consume the same precomputed
      per-row uniforms over the identical tile schedule — asserted by
      tests/test_pallas_sampling.py. It is also a genuine CPU win over
      ``_sample_jit``: no full-vocab argsort per decoded token.

The residual/acceptance math in ``serving/speculative.py`` keeps its full
device-resident ``q = sampling_probs(...)`` distributions (a top-k
approximation would break the exactness guarantee); what this module
removes is the per-token sort + host-visible ``[S, vocab]`` epilogue.

``DTX_SAMPLING_EPILOGUE_KERNEL=1`` forces impl="kernel" (interpret off
TPU), ``=0`` forces impl="xla"; unset defers to the backend — the same
contract ``DTX_PALLAS_INTERPRET`` gives the attention kernels.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from datatunerx_tpu.ops._pallas import interpret_default, pick_block_n

NEG_INF = -1e30
_BLOCK_CAP = 512

MODES = ("greedy", "simple", "topp")


def _interpret() -> bool:
    return interpret_default()


def default_impl() -> str:
    """Resolve the kernel/XLA split for this process: the Pallas kernel on
    real TPU backends, the blocked-XLA twin elsewhere.
    ``DTX_SAMPLING_EPILOGUE_KERNEL`` overrides (1 → kernel, 0 → xla) so
    tests can pin either side."""
    env = (os.environ.get("DTX_SAMPLING_EPILOGUE_KERNEL") or "").strip()
    if env:
        return "xla" if env.lower() in ("0", "false", "no") else "kernel"
    return "kernel" if jax.default_backend() == "tpu" else "xla"


def _tile_cumsum(e, lane, roll):
    """Inclusive prefix sum along the lanes of one ``[rows, bn]`` tile as a
    log-step shift-and-add scan (any ``bn``). Mosaic has no ``cumsum``
    lowering; it does have a lane rotate and a masked add. Kernel and XLA
    twin call this with their own ``roll`` so both add the same operands in
    the same order and the CDF they compare against ``u·Z`` is the same f32
    value."""
    bn = e.shape[-1]
    shift = 1
    while shift < bn:
        e = e + jnp.where(lane >= shift, roll(e, shift), 0.0)
        shift *= 2
    return e


def _prep(logits, temps, *, mode):
    """Shared pre-scale + lane-pad: both impls consume the SAME padded
    array, so scaling can never diverge between them. Padding is NEG_INF
    *after* scaling — dead lanes lose every argmax and contribute
    ``exp(NEG_INF - m) == 0`` to the normalizer and CDF."""
    x = logits.astype(jnp.float32)
    if mode != "greedy":
        x = x / jnp.maximum(temps, 1e-6).astype(jnp.float32)[:, None]
    v = x.shape[-1]
    vp = -(-v // 128) * 128
    if vp != v:
        x = jnp.pad(x, ((0, 0), (0, vp - v)), constant_values=NEG_INF)
    return x, pick_block_n(vp, _BLOCK_CAP)


# --------------------------------------------------------------- kernel

def _sample_kernel(temps_ref, us_ref, x_ref, tok_ref, fbuf, ibuf, *,
                   bn, nt, greedy):
    """One (row, phase, tile) step. SMEM carries per row:
    fbuf = [running max m, normalizer Z, CDF cursor c]
    ibuf = [argmax, sampled token, crossing-found flag]
    Phase 0 finds m/argmax; phase 1 accumulates Z = sum exp(x - m);
    phase 2 finds the first index whose running cumsum crosses u·Z.
    Greedy mode runs phase 0 only (the wrapper shrinks the grid)."""
    i = pl.program_id(0)
    p = pl.program_id(1)
    t = pl.program_id(2)
    tile = x_ref[0]  # (1, bn) f32 — row i's tile t of the [S, 1, vp] view
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)

    @pl.when((p == 0) & (t == 0))
    def _init_max():
        fbuf[0] = NEG_INF
        ibuf[0] = 0

    @pl.when(p == 0)
    def _phase_max():
        tmax = jnp.max(tile)
        # first-max-wins inside the tile (min index among maxima) plus a
        # strict > across tiles == jnp.argmax's first-occurrence rule
        targ = jnp.min(jnp.where(tile == tmax, lane, bn))
        better = tmax > fbuf[0]

        @pl.when(better)
        def _():
            fbuf[0] = tmax
            ibuf[0] = t * bn + targ

    if greedy:
        @pl.when((p == 0) & (t == nt - 1))
        def _emit_greedy():
            tok_ref[i] = ibuf[0]
        return

    @pl.when((p == 1) & (t == 0))
    def _init_z():
        fbuf[1] = 0.0

    @pl.when(p == 1)
    def _phase_z():
        fbuf[1] = fbuf[1] + jnp.sum(jnp.exp(tile - fbuf[0]))

    @pl.when((p == 2) & (t == 0))
    def _init_cdf():
        fbuf[2] = 0.0
        ibuf[1] = 0
        ibuf[2] = 0

    @pl.when(p == 2)
    def _phase_cdf():
        e = jnp.exp(tile - fbuf[0])
        cum = fbuf[2] + _tile_cumsum(
            e, lane, lambda a, k: pltpu.roll(a, k, 1))
        thresh = us_ref[i] * fbuf[1]
        hit = cum > thresh
        first = jnp.min(jnp.where(hit, lane, bn))
        take = (first < bn) & (ibuf[2] == 0)

        @pl.when(take)
        def _():
            ibuf[1] = t * bn + first
            ibuf[2] = 1
        fbuf[2] = fbuf[2] + jnp.sum(e)

        @pl.when(t == nt - 1)
        def _emit():
            # no crossing (u·Z at/after the float tail) falls back to the
            # argmax; rows with temp <= 0 are greedy regardless of draw
            sampled = jnp.where(ibuf[2] == 1, ibuf[1], ibuf[0])
            tok_ref[i] = jnp.where(temps_ref[i] <= 0.0, ibuf[0], sampled)


def _kernel_sample(x, temps, us, *, bn, greedy, interpret):
    s, vp = x.shape
    nt = vp // bn
    phases = 1 if greedy else 3
    return pl.pallas_call(
        functools.partial(_sample_kernel, bn=bn, nt=nt, greedy=greedy),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, phases, nt),
            # rows ride a unit MIDDLE dim so the block's trailing dims are
            # (1 == array dim, lane-aligned bn): a (1, bn) window of an
            # [S, vp] array is refused by Mosaic for every S > 1
            in_specs=[pl.BlockSpec((1, 1, bn),
                                   lambda i, p, t, *_: (i, 0, t))],
            # the whole [S] token vector stays resident in SMEM across the
            # grid (row i writes element i): a per-row (1, 1) output block
            # is as illegal a tiling as the per-row input block was
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[
                pltpu.SMEM((4,), jnp.float32),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s,), jnp.int32),
        interpret=_interpret() if interpret is None else interpret,
        name="dtx_fused_sample",
    )(temps.astype(jnp.float32), us.astype(jnp.float32), x[:, None, :])


# ----------------------------------------------------------- XLA oracle

def _xla_sample(x, temps, us, *, bn, greedy):
    """Blocked XLA twin: the kernel's tile walk verbatim (python loop over
    the same bn-wide tiles, sequential carry adds, identical tie rules) —
    the parity oracle AND the CPU fast path."""
    s, vp = x.shape
    nt = vp // bn
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    m = jnp.full((s,), NEG_INF, jnp.float32)
    idx = jnp.zeros((s,), jnp.int32)
    for t in range(nt):
        tile = x[:, t * bn:(t + 1) * bn]
        tmax = jnp.max(tile, axis=1)
        targ = jnp.min(jnp.where(tile == tmax[:, None], lane, bn), axis=1)
        better = tmax > m
        idx = jnp.where(better, t * bn + targ, idx)
        m = jnp.where(better, tmax, m)
    if greedy:
        return idx
    z = jnp.zeros((s,), jnp.float32)
    for t in range(nt):
        tile = x[:, t * bn:(t + 1) * bn]
        z = z + jnp.sum(jnp.exp(tile - m[:, None]), axis=1)
    thresh = us.astype(jnp.float32) * z
    c = jnp.zeros((s,), jnp.float32)
    token = jnp.zeros((s,), jnp.int32)
    found = jnp.zeros((s,), bool)
    for t in range(nt):
        tile = x[:, t * bn:(t + 1) * bn]
        e = jnp.exp(tile - m[:, None])
        cum = c[:, None] + _tile_cumsum(
            e, lane, lambda a, k: jnp.roll(a, k, axis=1))
        hit = cum > thresh[:, None]
        first = jnp.min(jnp.where(hit, lane, bn), axis=1)
        got = first < bn
        take = got & ~found
        token = jnp.where(take, t * bn + first, token)
        found = found | got
        c = c + jnp.sum(e, axis=1)
    sampled = jnp.where(found, token, idx)
    return jnp.where(temps.astype(jnp.float32) <= 0.0, idx, sampled)


def _topp_sample(logits, temps, top_ps, us):
    """The exact_topp nucleus path (speculative.sampling_probs semantics):
    sorted-space inverse-CDF over the truncated distribution. XLA-only —
    there is no Mosaic full-vocab sort — but still epilogue-shaped: one
    token id per row leaves, never the [S, vocab] probs."""
    temps = temps.astype(jnp.float32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    order = jnp.argsort(scaled, axis=-1)[:, ::-1]
    svals = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(svals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut = (cum - probs > top_ps.astype(jnp.float32)[:, None]) \
        & (top_ps.astype(jnp.float32)[:, None] < 1.0)
    probs = jnp.where(cut, 0.0, probs)
    total = jnp.sum(probs, axis=-1)
    cdf = jnp.cumsum(probs, axis=-1)
    hit = cdf > (us.astype(jnp.float32) * total)[:, None]
    # all-False can only mean the float tail; argmax(False row) = 0 falls
    # back to the sorted-top token, which is always in the nucleus
    first = jnp.argmax(hit, axis=-1)
    tok = jnp.take_along_axis(order, first[:, None], axis=-1)[:, 0]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, tok.astype(jnp.int32))


# ------------------------------------------------------------------ API

def fused_sample(logits, temps, top_ps, keys, *, mode, impl="xla",
                 interpret=None):
    """Sample one token per row from ``logits [S, V]``. ``mode`` is the
    static per-batch mode ("greedy" | "simple" | "topp"); ``keys`` are
    per-row PRNG keys ``[S, 2]`` (ignored — may be None — for greedy).
    Returns token ids ``[S] int32``. ``impl`` picks kernel vs the blocked
    XLA twin for greedy/simple; topp always takes the XLA nucleus path."""
    if mode not in MODES:
        raise ValueError(f"unknown sampling mode {mode!r} (want {MODES})")
    temps = jnp.asarray(temps)
    if mode == "greedy":
        x, bn = _prep(logits, temps, mode=mode)
        if impl == "kernel":
            us = jnp.zeros((logits.shape[0],), jnp.float32)
            return _kernel_sample(x, temps, us, bn=bn, greedy=True,
                                  interpret=interpret)
        return _xla_sample(x, temps, None, bn=bn, greedy=True)
    us = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
    if mode == "topp":
        return _topp_sample(logits, temps, jnp.asarray(top_ps), us)
    x, bn = _prep(logits, temps, mode=mode)
    if impl == "kernel":
        return _kernel_sample(x, temps, us, bn=bn, greedy=False,
                              interpret=interpret)
    return _xla_sample(x, temps, us, bn=bn, greedy=False)


def sample_rows(logits, temps, top_ps, rng, *, mode, impl="xla",
                interpret=None):
    """Drop-in for the ``vmap(split) + vmap(_sample_jit)`` pair: splits
    each row's key exactly like the legacy path (slot 0 kept, slot 1
    consumed) so the per-slot PRNG stream — the one the KV-migration
    payload carries — evolves identically, then samples via the epilogue.
    Returns ``(tokens [S] int32, new_rng [S, 2])``."""
    split = jax.vmap(jax.random.split)(rng)
    new_rng, sub = split[:, 0], split[:, 1]
    toks = fused_sample(logits, temps, top_ps, sub, mode=mode, impl=impl,
                        interpret=interpret)
    return toks, new_rng
