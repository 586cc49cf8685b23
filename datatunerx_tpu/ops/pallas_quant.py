"""Pallas TPU kernels: fused quantized matmuls (int8 w8a16, nf4 QLoRA).

The bitsandbytes replacement's hot path (SURVEY.md §2.4, §7.4#2): the XLA
reference implementations live in ops/quant.py; these kernels fuse
unpack → codebook → scale → MXU dot per tile, so the dequantized weights never
round-trip through HBM. Correctness is pinned to the XLA path in
tests/test_quant.py (interpret mode on CPU; compiled on TPU).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from datatunerx_tpu.ops.quant import NF4_CODE


def _interpret() -> bool:
    from datatunerx_tpu.ops._pallas import interpret_default

    return interpret_default()


def _pad_rows(x2d: jnp.ndarray, bm: int) -> Tuple[jnp.ndarray, int]:
    m = x2d.shape[0]
    pad = (-m) % bm
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, m


# ----------------------------------------------------------------- int8

_INT8_BLOCK_K = 2048  # K tile: whole-K blocks overflow scoped VMEM at 7B


def _int8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int):
    # One [bm, bk] x [bk, bn] MXU pass per grid step, accumulated in f32
    # across the K grid dim; the per-channel scale applies once at the end.
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        x_ref[:], q_ref[:].astype(x_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == nk - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] * s_ref[:].astype(jnp.float32)).astype(
            o_ref.dtype)


def pallas_matmul_int8(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray,
                       block_m: int = 256, block_n: int = 256) -> jnp.ndarray:
    """Differentiable wrapper: forward rides the fused kernel; backward is
    dx = (g·scale) @ qᵀ through XLA (q/scale are a frozen quantized base —
    QLoRA never needs their gradients; pallas_call has no jvp rule, so
    without this the TRAINING path couldn't use the kernel at all)."""
    return _int8_mm((block_m, block_n), x, q, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int8_mm(blocks, x, q, scale):
    return _pallas_matmul_int8_impl(x, q, scale, *blocks)


def _int8_fwd(blocks, x, q, scale):
    return _pallas_matmul_int8_impl(x, q, scale, *blocks), (q, scale)


def _int8_bwd(blocks, res, g):
    q, scale = res
    gs = g.astype(jnp.float32) * scale.astype(jnp.float32)  # [..., N] * [N]
    dx = jnp.einsum("...n,kn->...k", gs.astype(g.dtype),
                    q.astype(g.dtype),
                    preferred_element_type=jnp.float32).astype(g.dtype)
    return (dx, np.zeros(q.shape, jax.dtypes.float0), jnp.zeros_like(scale))


_int8_mm.defvjp(_int8_fwd, _int8_bwd)


def _pallas_matmul_int8_impl(
    x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray,
    block_m: int = 256, block_n: int = 256,
) -> jnp.ndarray:
    """x: [..., K] @ q: int8 [K, N] * scale [N] → [..., N]."""
    *lead, K = x.shape
    N = q.shape[1]
    x2d = x.reshape(-1, K)
    x2d, m_real = _pad_rows(x2d, block_m)
    M = x2d.shape[0]
    from datatunerx_tpu.ops._pallas import pick_block_n

    bn = pick_block_n(N, block_n)
    # K is tiled once it no longer fits one block: an x[bm, K] + q[K, bn]
    # pair (plus q's in-kernel bf16 copy) at K = 11008 (llama2-7b down_proj)
    # needs 16.4 MB of the 16 MB of scoped VMEM Mosaic grants a kernel. A K
    # within the tile stays one whole-K step, which is legal for ANY K
    # (block dim == array dim); real models' larger K are 128-multiples.
    bk = K if K <= _INT8_BLOCK_K else pick_block_n(K, _INT8_BLOCK_K)
    nk = K // bk

    out = pl.pallas_call(
        functools.partial(_int8_kernel, nk=nk),
        grid=(M // block_m, N // bn, nk),
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, bn), jnp.float32)],
        interpret=_interpret(),
        name="dtx_quant_int8",
    )(x2d, q, scale.reshape(1, N))
    return out[:m_real].reshape(*lead, N)


# ------------------------------------------------------------------ nf4

def _nf4_kernel(x_ref, packed_ref, scales_ref, o_ref, w_vmem, acc_ref,
                *, block_size: int, nk: int):
    # One K-chunk of ck = nb·block weights per grid step (chunk-major inputs:
    # x_ref [1, bm, ck], packed_ref [1, bn, nb, block/2] planar nibbles,
    # scales_ref [1, bn, nb]).
    #
    # Mosaic has no >2D gather and no sublane→lane shape casts, so the unpack
    # never materializes [bn, nb, block]: each block is dequantized in 2D
    # ([bn, block/2] per nibble plane, 16-term select-sum codebook) and stored
    # into its static 64-lane slice of a [bn, ck] VMEM scratch; the MXU then
    # runs one full-depth dot per chunk, accumulating across the K grid dim.
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    packed = packed_ref[0]
    bn, nb, half = packed.shape
    code = np.asarray(NF4_CODE, np.float32)
    for b in range(nb):
        # widen before the shift: Mosaic can't legalize shrui on i8 vectors
        pb = packed[:, b, :].astype(jnp.int32)            # [bn, block/2]
        lo = pb & 0x0F
        hi = (pb >> 4) & 0x0F
        idx = jnp.concatenate([lo, hi], axis=-1)          # [bn, block] planar
        w = jnp.zeros(idx.shape, jnp.float32)
        for c, val in enumerate(code):
            w = jnp.where(idx == c, jnp.float32(val), w)
        w_vmem[:, b * block_size:(b + 1) * block_size] = (
            w * scales_ref[0][:, b:b + 1])

    acc_ref[:] += jax.lax.dot_general(
        x_ref[0], w_vmem[:].astype(x_ref.dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == nk - 1)
    def _finish():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _pick_chunk(nb_total: int, block_size: int, cap_nb: int = 16,
                lane_aligned: bool = False) -> int:
    """Largest divisor of nb_total ≤ cap_nb (chunk = that many nf4 blocks).

    For chunk-major OPERANDS any divisor is Mosaic-legal: the chunk axis is
    hoisted to a leading array dim on the host, so every BlockSpec's
    last-two dims EQUAL their array dims regardless of nb. A chunk that
    tiles an array's LANE dim in place (the transposed kernel's [M, K]
    output) must be a 128-multiple: ``lane_aligned`` prefers such divisors
    (K = 5632 → 8 blocks = 512 lanes instead of 11 = 704, which Mosaic
    refuses) and exists for every K that is itself a 128-multiple."""
    best = aligned = 0
    for d in range(1, cap_nb + 1):
        if nb_total % d == 0:
            best = d
            if (d * block_size) % 128 == 0:
                aligned = d
    return ((aligned or best) if lane_aligned else best) * block_size


def pallas_matmul_nf4(x: jnp.ndarray, qw: Dict[str, jnp.ndarray],
                      shape: Tuple[int, int], block_m: int = 256,
                      block_n: int = 256) -> jnp.ndarray:
    """Differentiable wrapper (see pallas_matmul_int8): forward = fused
    kernel, backward = dx = g @ Wᵀ with W dequantized by the XLA reference
    path (frozen base ⇒ no weight grads)."""
    return _nf4_mm((shape, block_m, block_n), x,
                   qw["packed"], qw["scale_q"], qw["meta"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nf4_mm(static, x, packed, scale_q, meta):
    shape, block_m, block_n = static
    return _pallas_matmul_nf4_impl(
        x, {"packed": packed, "scale_q": scale_q, "meta": meta}, shape,
        block_m=block_m, block_n=block_n)


def _nf4_mm_fwd(static, x, packed, scale_q, meta):
    shape, block_m, block_n = static
    out = _pallas_matmul_nf4_impl(
        x, {"packed": packed, "scale_q": scale_q, "meta": meta}, shape,
        block_m=block_m, block_n=block_n)
    return out, (packed, scale_q, meta)


def _nf4_mm_bwd(static, res, g):
    packed, scale_q, meta = res
    shape, _, _ = static
    # dx = g @ Wᵀ through the fused transposed kernel: the weights stay
    # packed in HBM (0.5 byte/weight read, dequant per-tile in VMEM). The
    # round-2 XLA fallback here materialized the full [K, N] bf16 dequant
    # per matmul per step — at 7B with remat that is ~3 × 13.5 GB of HBM
    # writes per step and the reason the nf4 path sat at 14.6% MFU.
    dx = _pallas_matmul_nf4_t_impl(
        g, {"packed": packed, "scale_q": scale_q, "meta": meta}, shape)
    return (dx,
            np.zeros(packed.shape, jax.dtypes.float0),
            np.zeros(scale_q.shape, jax.dtypes.float0),
            jnp.zeros_like(meta))


_nf4_mm.defvjp(_nf4_mm_fwd, _nf4_mm_bwd)


def _nf4_t_kernel(g_ref, packed_ref, scales_ref, o_ref, w_vmem, acc_ref,
                  *, block_size: int, nn: int):
    # Transposed product dx[M, K] = g[M, N] @ V[N, K] (V = Wᵀ): contraction
    # runs over the N grid dim; each step dequantizes an [bn, ck] weight tile
    # (bn output channels × ck of their K-contiguous weights — the SAME
    # per-block 2D unpack as the forward kernel) and feeds the MXU with
    # g_tile[bm, bn] @ w[bn, ck], accumulating over nj.
    nj = pl.program_id(2)

    @pl.when(nj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    packed = packed_ref[0]
    bn, nb, half = packed.shape
    code = np.asarray(NF4_CODE, np.float32)
    for b in range(nb):
        pb = packed[:, b, :].astype(jnp.int32)            # [bn, block/2]
        lo = pb & 0x0F
        hi = (pb >> 4) & 0x0F
        idx = jnp.concatenate([lo, hi], axis=-1)          # [bn, block] planar
        w = jnp.zeros(idx.shape, jnp.float32)
        for c, val in enumerate(code):
            w = jnp.where(idx == c, jnp.float32(val), w)
        w_vmem[:, b * block_size:(b + 1) * block_size] = (
            w * scales_ref[0][:, b:b + 1])

    acc_ref[:] += jax.lax.dot_general(
        g_ref[0], w_vmem[:].astype(g_ref.dtype),
        (((1,), (0,)), ((), ())),                         # contract bn
        preferred_element_type=jnp.float32,
    )

    @pl.when(nj == nn - 1)
    def _finish():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _pallas_matmul_nf4_t_impl(
    g: jnp.ndarray, qw: Dict[str, jnp.ndarray], shape: Tuple[int, int],
    block_m: int = 256, block_n: int = 256, block_size: int = 64,
) -> jnp.ndarray:
    """g: [..., N] @ dequant(packed)ᵀ → [..., K] (the QLoRA dx product).

    Reuses the forward layout as-is: packed rows are output channels n with
    their K weights contiguous, which for the transposed product is exactly
    V[N, K] row-major — so the only difference from the forward kernel is
    which operand axis the grid contracts over."""
    K, N = shape
    *lead, N2 = g.shape
    assert N2 == N, (N2, N)
    nb_per_channel = K // block_size
    ck = _pick_chunk(nb_per_channel, block_size, lane_aligned=True)
    nb_chunk = ck // block_size
    nk = K // ck
    half = block_size // 2

    g2d = g.reshape(-1, N)
    g2d, m_real = _pad_rows(g2d, block_m)
    M = g2d.shape[0]
    from datatunerx_tpu.ops._pallas import pick_block_n

    bn = pick_block_n(N, block_n)
    nn = N // bn

    packedk = qw["packed"].reshape(N, nk, nb_chunk, half)
    scales = (qw["scale_q"].astype(jnp.float32) * qw["meta"][0]).reshape(
        N, nk, nb_chunk
    )

    out = pl.pallas_call(
        functools.partial(_nf4_t_kernel, block_size=block_size, nn=nn),
        grid=(M // block_m, nk, nn),
        in_specs=[
            pl.BlockSpec((1, block_m, bn), lambda i, kk, nj: (nj, i, 0)),
            pl.BlockSpec((1, bn, nb_chunk, half),
                         lambda i, kk, nj: (kk, nj, 0, 0)),
            pl.BlockSpec((1, bn, nb_chunk), lambda i, kk, nj: (kk, nj, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, ck), lambda i, kk, nj: (i, kk)),
        out_shape=jax.ShapeDtypeStruct((M, K), g.dtype),
        scratch_shapes=[
            pltpu.VMEM((bn, ck), jnp.float32),
            pltpu.VMEM((block_m, ck), jnp.float32),
        ],
        interpret=_interpret(),
        name="dtx_quant_nf4_t",
    )(
        g2d.reshape(M, nn, bn).transpose(1, 0, 2),        # [nn, M, bn]
        packedk.transpose(1, 0, 2, 3),                    # [nk, N, nbc, half]
        scales.transpose(1, 0, 2),                        # [nk, N, nb_chunk]
    )
    return out[:m_real].reshape(*lead, K)


def _pallas_matmul_nf4_impl(
    x: jnp.ndarray, qw: Dict[str, jnp.ndarray], shape: Tuple[int, int],
    block_m: int = 256, block_n: int = 256, block_size: int = 64,
) -> jnp.ndarray:
    """x: [..., K] @ nf4-packed weights (ops/quant.py layout) → [..., N].

    Inputs are rearranged chunk-major on the host ([nk, …, ck-sized tail]) so
    the K-grid BlockSpecs index a leading dim and keep lane/sublane block
    dims equal to the array dims — the only tiling that is legal for EVERY
    real-model K (5632, 11008, … are not 128·64-multiples)."""
    K, N = shape
    *lead, K2 = x.shape
    assert K2 == K, (K2, K)
    nb_per_channel = K // block_size
    ck = _pick_chunk(nb_per_channel, block_size)
    nb_chunk = ck // block_size
    nk = K // ck
    half = block_size // 2

    x2d = x.reshape(-1, K)
    x2d, m_real = _pad_rows(x2d, block_m)
    M = x2d.shape[0]
    from datatunerx_tpu.ops._pallas import pick_block_n

    bn = pick_block_n(N, block_n)

    xk = x2d.reshape(M, nk, ck).transpose(1, 0, 2)  # [nk, M, ck]
    packedk = qw["packed"].reshape(N, nk, nb_chunk, half).transpose(1, 0, 2, 3)
    scales = (qw["scale_q"].astype(jnp.float32) * qw["meta"][0]).reshape(
        N, nk, nb_chunk
    )
    scalesk = scales.transpose(1, 0, 2)  # [nk, N, nb_chunk]

    out = pl.pallas_call(
        functools.partial(_nf4_kernel, block_size=block_size, nk=nk),
        grid=(M // block_m, N // bn, nk),
        in_specs=[
            pl.BlockSpec((1, block_m, ck), lambda i, j, kk: (kk, i, 0)),
            pl.BlockSpec((1, bn, nb_chunk, half),
                         lambda i, j, kk: (kk, j, 0, 0)),
            pl.BlockSpec((1, bn, nb_chunk), lambda i, j, kk: (kk, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bn, ck), jnp.float32),
            pltpu.VMEM((block_m, bn), jnp.float32),
        ],
        interpret=_interpret(),
        name="dtx_quant_nf4",
    )(xk, packedk, scalesk)
    return out[:m_real].reshape(*lead, N)
