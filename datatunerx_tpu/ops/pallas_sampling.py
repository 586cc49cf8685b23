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

  impl="kernel" — a Pallas kernel for greedy/simple. Engaged on real TPU
      backends; interpret mode emulates it for CPU tests.
  impl="xla"    — a blocked XLA twin that runs the SAME per-tile step
      functions (``_max_step``, ``_tile_total``, ``_first_crossing``) over
      the same tiles in the same order. It is the PARITY ORACLE (PR 13
      pattern): greedy tokens agree with the kernel bitwise by construction
      (max/compare are order-exact), and sampled tokens agree under a fixed
      seed because both sides consume the same precomputed per-row uniforms
      and add the same f32 operands in the same order — asserted by
      tests/test_pallas_sampling.py and, on the chip, by chip_smoke.py. It
      is also a genuine CPU win over ``_sample_jit``: no full-vocab argsort
      per decoded token.

The walk. One grid step handles EVERY row of one wide vocabulary tile:
grid ``(phases, tiles)``, no row axis (a grid step costs about 0.4 us
whatever it moves; the walk this replaced took one row and 128 lanes a
step, 57,000 steps at 16 x 151,936, and was all step cost). The logits
operand stays ``f32[S, 1, V]`` (rows on a unit middle dim, so every row is
contiguous in HBM and a block's trailing dims ``(1, bn)`` are legal for
any S); each step's block is ``(S, 1, bn)`` and the kernel reads it as one
dense ``(S, bn)`` value, rows on sublanes.

  Width   ``_tile_width(rows, vp)``: the largest power-of-two multiple of
          128 lanes whose dense f32 tile (rows rounded up to 8 sublanes)
          fits ``_TILE_BYTES``, and no wider than the padded vocabulary.
          Two pipeline buffers plus the handful of tile-sized temporaries
          of the CDF scan then stay well inside Mosaic's 16 MiB scoped
          VMEM. 16 rows -> 8,192 lanes (19 tiles a phase at 151,936, 4 at
          32,000); 4 rows -> 16,384. It need not divide the vocabulary.
  Ragged  the grid is ``cdiv(vp, bn)`` and the last tile hangs over the
          end. What a block holds out of bounds is unspecified, so every
          tile is masked by index (``where(col < vp, tile, NEG_INF)``),
          never by arithmetic. ``_prep`` pads to a multiple of 128 only.
  Carries vectors over the rows in VMEM scratch: ``fbuf`` = running max m,
          normaliser Z, CDF cursor c; ``ibuf`` = argmax, sampled token,
          found flag. The ``[S]`` tokens leave once, at the last step.
  Phases  0: m and the first argmax (min index among a tile's maxima, a
          strict > across tiles == ``jnp.argmax``). 1: Z as the running
          sum of tile totals. 2: the first index whose CDF crosses u*Z.
          Greedy runs phase 0 only.
  Sums    a tile's total is an explicit tree: halves folded onto each
          other down to 128 lanes (vreg-aligned adds), then a 7-step
          rotate-and-add butterfly. Neither Mosaic nor XLA picks the
          order, so both get the same f32 value. Phases 1 and 2 add the
          same totals in the same order, so the cursor ends at Z exactly
          and every u*Z < Z has a crossing tile.
  Scan    a row's crossing lies in the one tile where c <= u*Z < c + total,
          so the log-step prefix scan over the tile's lanes (``_tile_cumsum``,
          13 steps at 8,192) runs only in tiles where some row crosses, at
          most once a row. If rounding leaves no lane of that tile above
          u*Z (the scan's last partial sum and the tree's total group their
          adds differently), or no tile crosses at all (u*Z == Z), the row
          falls back to its argmax.

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

from datatunerx_tpu.ops._pallas import interpret_default

NEG_INF = -1e30
# one dense (rows, bn) f32 tile; see "Width" above
_TILE_BYTES = 512 * 1024
# "no lane": above every vocabulary index, so a min over lanes ignores it
_NO_COL = 2 ** 30

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


def _tile_width(rows: int, vp: int) -> int:
    """Lanes of one vocabulary tile for ``rows`` rows of a ``vp``-wide
    (128-aligned) vocabulary: a function of the shapes alone."""
    rows8 = -(-rows // 8) * 8
    lanes = max(128, min(_TILE_BYTES // (4 * rows8), vp))
    return 128 << ((lanes // 128).bit_length() - 1)


# ------------------------------------------------- per-tile steps (shared)
# Kernel and twin call these on the same ``[rows, bn]`` tiles with their own
# ``roll`` (``pltpu.roll`` / ``jnp.roll``), so both add the same operands in
# the same order. Every per-row quantity is a ``[rows, 1]`` column.

def _max_step(tile, col, m, idx):
    """Fold one tile into the running max ``m`` and its first index."""
    tmax = jnp.max(tile, axis=1, keepdims=True)
    targ = jnp.min(jnp.where(tile == tmax, col, _NO_COL), axis=1,
                   keepdims=True)
    better = tmax > m
    return jnp.where(better, tmax, m), jnp.where(better, targ, idx)


def _tile_total(e, roll):
    """Row sums of one tile in an order nobody else chooses: fold halves
    down to 128 lanes, then a rotate-and-add butterfly (each lane ends with
    the same total, bit for bit, because f32 add commutes)."""
    while e.shape[-1] > 128:
        half = e.shape[-1] // 2
        e = e[:, :half] + e[:, half:]
    shift = 64
    while shift:
        e = e + roll(e, shift)
        shift //= 2
    return e[:, :1]


def _tile_cumsum(e, lane, roll):
    """Inclusive prefix sum along the lanes of one ``[rows, bn]`` tile as a
    log-step shift-and-add scan (any ``bn``). Mosaic has no ``cumsum``
    lowering; it does have a lane rotate and a masked add."""
    bn = e.shape[-1]
    shift = 1
    while shift < bn:
        e = e + jnp.where(lane >= shift, roll(e, shift), 0.0)
        shift *= 2
    return e


def _first_crossing(e, lane, col, c, thresh, roll):
    """First vocabulary index of the tile whose CDF (cursor ``c`` plus the
    tile's prefix sums) passes ``thresh``; ``_NO_COL`` where none does."""
    hit = c + _tile_cumsum(e, lane, roll) > thresh
    return jnp.min(jnp.where(hit, col, _NO_COL), axis=1, keepdims=True)


def _cdf_pick(cross, first, idx, tok):
    """Rows that cross in this tile take its first crossing lane (their
    argmax where rounding left none); the others keep what they have."""
    return jnp.where(cross, jnp.where(first < _NO_COL, first, idx), tok)


def _emit(temps, idx, tok, found):
    """No crossing falls back to the argmax; rows with temp <= 0 are greedy
    regardless of the draw."""
    return jnp.where(temps <= 0.0, idx, jnp.where(found > 0, tok, idx))


def _prep(logits, temps, *, mode):
    """Shared pre-scale + lane-pad: both impls consume the SAME padded
    array, so scaling can never diverge between them. Padding is NEG_INF
    *after* scaling — dead lanes lose every argmax and contribute
    ``exp(NEG_INF - m) == 0`` to the normalizer and CDF. The pad is to a
    multiple of 128 and no more: the tile width need not divide it."""
    x = logits.astype(jnp.float32)
    if mode != "greedy":
        x = x / jnp.maximum(temps, 1e-6).astype(jnp.float32)[:, None]
    v = x.shape[-1]
    vp = -(-v // 128) * 128
    if vp != v:
        x = jnp.pad(x, ((0, 0), (0, vp - v)), constant_values=NEG_INF)
    return x, _tile_width(x.shape[0], vp)


# --------------------------------------------------------------- kernel

def _sample_kernel(temps_ref, us_ref, x_ref, tok_ref, fbuf, ibuf, *,
                   bn, nt, vp, greedy):
    """One (phase, tile) step over every row. VMEM carries, ``[rows, 1]``
    each: fbuf = [running max m, normalizer Z, CDF cursor c];
    ibuf = [argmax, sampled token, crossing-found flag]."""
    p = pl.program_id(0)
    t = pl.program_id(1)
    rows = x_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, bn), 1)
    col = t * bn + lane
    # the (rows, 1, bn) block read densely, rows on sublanes; the last
    # tile's out-of-bounds lanes hold anything, so mask by index
    tile = jnp.where(col < vp, x_ref[:, 0, :], NEG_INF)

    def roll(a, k):
        return pltpu.roll(a, k, 1)

    @pl.when((p == 0) & (t == 0))
    def _init():
        fbuf[...] = jnp.zeros(fbuf.shape, jnp.float32)
        fbuf[0] = jnp.full((rows, 1), NEG_INF, jnp.float32)
        ibuf[...] = jnp.zeros(ibuf.shape, jnp.int32)

    @pl.when(p == 0)
    def _phase_max():
        fbuf[0], ibuf[0] = _max_step(tile, col, fbuf[0], ibuf[0])

    if greedy:
        @pl.when(t == nt - 1)
        def _emit_greedy():
            tok_ref[...] = ibuf[0]
        return

    @pl.when(p == 1)
    def _phase_z():
        fbuf[1] = fbuf[1] + _tile_total(jnp.exp(tile - fbuf[0]), roll)

    @pl.when(p == 2)
    def _phase_cdf():
        e = jnp.exp(tile - fbuf[0])
        c, thresh = fbuf[2], us_ref[...] * fbuf[1]
        c_next = c + _tile_total(e, roll)
        cross = (ibuf[2] == 0) & (c_next > thresh)

        @pl.when(jnp.max(cross.astype(jnp.int32)) > 0)
        def _():
            first = _first_crossing(e, lane, col, c, thresh, roll)
            ibuf[1] = _cdf_pick(cross, first, ibuf[0], ibuf[1])
        ibuf[2] = jnp.where(cross, 1, ibuf[2])
        fbuf[2] = c_next

        @pl.when(t == nt - 1)
        def _emit_sampled():
            tok_ref[...] = _emit(temps_ref[...], ibuf[0], ibuf[1], ibuf[2])


def _kernel_sample(x, temps, us, *, bn, greedy, interpret):
    s, vp = x.shape
    nt = pl.cdiv(vp, bn)
    col_spec = pl.BlockSpec((s, 1), lambda p, t: (0, 0))
    toks = pl.pallas_call(
        functools.partial(_sample_kernel, bn=bn, nt=nt, vp=vp,
                          greedy=greedy),
        grid=(1 if greedy else 3, nt),
        in_specs=[
            col_spec, col_spec,
            # rows ride a unit MIDDLE dim so the block's trailing dims are
            # (1 == array dim, lane-aligned bn): legal for every S, and
            # each row's bn lanes are one contiguous run in HBM
            pl.BlockSpec((s, 1, bn), lambda p, t: (0, 0, t)),
        ],
        out_specs=col_spec,
        scratch_shapes=[
            pltpu.VMEM((3, s, 1), jnp.float32),
            pltpu.VMEM((3, s, 1), jnp.int32),
        ],
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.int32),
        interpret=_interpret() if interpret is None else interpret,
        name="dtx_fused_sample",
    )(temps.astype(jnp.float32)[:, None], us.astype(jnp.float32)[:, None],
      x[:, None, :])
    return toks[:, 0]


# ----------------------------------------------------------- XLA oracle

def _xla_sample(x, temps, us, *, bn, greedy):
    """Blocked XLA twin: the kernel's tile walk verbatim (a loop over the
    same bn-wide tiles, the same step functions, identical tie rules) —
    the parity oracle AND the CPU fast path. The loops are ``fori_loop``s,
    so the program's size does not grow with the number of tiles."""
    s, vp = x.shape
    nt = pl.cdiv(vp, bn)
    # the kernel masks its ragged last tile to NEG_INF by index; pad to the
    # same values
    x = jnp.pad(x, ((0, 0), (0, nt * bn - vp)), constant_values=NEG_INF)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s, bn), 1)

    def roll(a, k):
        return jnp.roll(a, k, axis=1)

    def tile_at(t):
        return jax.lax.dynamic_slice_in_dim(x, t * bn, bn, axis=1)

    def max_body(t, carry):
        return _max_step(tile_at(t), t * bn + lane, *carry)

    m, idx = jax.lax.fori_loop(
        0, nt, max_body,
        (jnp.full((s, 1), NEG_INF, jnp.float32), jnp.zeros((s, 1), jnp.int32)))
    if greedy:
        return idx[:, 0]

    def z_body(t, z):
        return z + _tile_total(jnp.exp(tile_at(t) - m), roll)

    z = jax.lax.fori_loop(0, nt, z_body, jnp.zeros((s, 1), jnp.float32))
    thresh = us.astype(jnp.float32)[:, None] * z

    def cdf_body(t, carry):
        c, tok, found = carry
        e = jnp.exp(tile_at(t) - m)
        c_next = c + _tile_total(e, roll)
        cross = (found == 0) & (c_next > thresh)
        # the kernel skips the scan where no row crosses; a row that does
        # not cross keeps its token either way
        first = _first_crossing(e, lane, t * bn + lane, c, thresh, roll)
        return (c_next, _cdf_pick(cross, first, idx, tok),
                jnp.where(cross, 1, found))

    _, tok, found = jax.lax.fori_loop(
        0, nt, cdf_body,
        (jnp.zeros((s, 1), jnp.float32), jnp.zeros((s, 1), jnp.int32),
         jnp.zeros((s, 1), jnp.int32)))
    return _emit(temps.astype(jnp.float32)[:, None], idx, tok, found)[:, 0]


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
