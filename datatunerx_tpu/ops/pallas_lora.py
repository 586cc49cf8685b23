"""Pallas fused LoRA matmul: y = x·W + (x·A)·B·scale in one kernel.

The SURVEY-mandated native replacement for peft's separate adapter matmuls
(SURVEY.md §2.4(a)): the adapter delta is computed per output tile while the
base tile is already resident in VMEM, so the [M, N] intermediate from the
adapter branch never round-trips through HBM. The rank-r contraction (r ≤ 64)
rides the same MXU pass.

XLA reference path: models/llama._proj; parity test tests/test_pallas_lora.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interpret() -> bool:
    from datatunerx_tpu.ops._pallas import interpret_default

    return interpret_default()


def _lora_kernel(x_ref, w_ref, a_ref, b_ref, o_ref, *, scale: float):
    x = x_ref[:]
    acc = jnp.dot(x, w_ref[:].astype(x.dtype),
                  preferred_element_type=jnp.float32)
    xa = jnp.dot(x, a_ref[:].astype(x.dtype),
                 preferred_element_type=jnp.float32)  # [bm, r]
    acc += jnp.dot(xa.astype(x.dtype), b_ref[:].astype(x.dtype),
                   preferred_element_type=jnp.float32) * scale
    o_ref[:] = acc.astype(o_ref.dtype)


def pallas_lora_matmul(
    x: jnp.ndarray,        # [..., K]
    w: jnp.ndarray,        # [K, N]
    a: jnp.ndarray,        # [K, r]
    b: jnp.ndarray,        # [r, N]
    scale: float,
    block_m: int = 256,
    block_n: int = 256,
) -> jnp.ndarray:
    *lead, K = x.shape
    N = w.shape[1]
    x2d = x.reshape(-1, K)
    m = x2d.shape[0]
    pad = (-m) % block_m
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    M = x2d.shape[0]
    from datatunerx_tpu.ops._pallas import pick_block_n

    bn = pick_block_n(N, block_n)
    r = a.shape[1]

    out = pl.pallas_call(
        functools.partial(_lora_kernel, scale=scale),
        grid=(M // block_m, N // bn),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((K, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=_interpret(),
        name="dtx_lora_fused",
    )(x2d, w, a, b)
    return out[:m].reshape(*lead, N)
