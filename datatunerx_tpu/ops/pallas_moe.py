"""The expert layer's grouped matmul as a Pallas TPU kernel (``dtx_moe_gmm``).

``jax.lax.ragged_dot`` on the TPU is a Mosaic kernel whose row tile is 512:
it multiplies the weights of every group that has a row in the tile by all
512 rows and masks the others away. In serving a held expert has a row or
two a decode step and a handful in a prefill chunk, so that kernel is bound
by the MXU on rows it throws away (512 FLOP a weight byte; v5e's ridge is
240). This one takes its row tile from the rows a group is expected to have
(``row_tile``), and then has one job: stream each hit expert's weights once.

The walk. The rows are sorted by group. A VISIT is one (row tile, group)
pair that share a row; visits are numbered in row order, so a group's are
consecutive and so are a row tile's. ``visits`` lists them from the group
sizes with small integer ops: at most ``M / tm + groups - 1``, the static
length of the lists; the grid's visit axis is as long as the visits there
really are (a dynamic bound), so an empty group or a row tile past
``sum(sizes)`` costs no grid step and no DMA, and no row is ever dropped.
Grid ``(column tiles, visits)``: a step multiplies the visit's ``[tm, K]``
rows by the group's ``[K, tn]`` weight block (whole ``K``, f32 result) and
stores the rows that are the group's; the other rows of the tile keep what
an earlier visit of the same tile stored. Consecutive visits of one group
name the same weight block, which the pipeline then does not fetch again.

The weights are a run's stack ``[n, E, K, N]`` taken whole: ``layer`` is a
scalar-prefetch operand and the weight's index map reads block ``(layer,
group)``. Nothing is sliced or flattened, so nothing is copied.

With two weight operands the call is the front half of a SwiGLU, ``silu(x @
gate) * (x @ up)``, in one pass over the rows; with one it is a plain
grouped product. Operands multiply in the rows' dtype with float32
accumulation, as ``ragged_dot(..., preferred_element_type=float32)`` does:
only the order of a sum differs. Rows that belong to no group, and row tiles
no group touches, are left as they were allocated: nothing may read them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from datatunerx_tpu.ops._pallas import interpret_default, pick_block_n

KERNEL = "dtx_moe_gmm"
MIN_ROW_TILE = 16  # one bf16 sublane tile
XLA_ROW_TILE = 512  # ragged_dot's own; from here on its tiling is the right one
# A row tile covers this many times the rows a group expects. Swept on v5e at
# both sparse-expert cells' shapes (PERF.md, PR 35): a decode step reads the
# same from 16 to 128 rows a tile (the MXU's time is pushing the weight tiles,
# whatever the rows); a prefill chunk gains 2-6 % from 32-64 rows over 16.
ROWS_COVERED = 8
# Most bytes of one weight block. 8 MiB blocks ([4096, 1024] bf16) stream at
# 88 % of the HBM roofline there, 2 and 4 MiB ones at 78 %, 16 MiB no better.
# Two operands, double-buffered, are 32 MiB: past Mosaic's default 16 MiB of
# scoped VMEM, hence vmem_limit_bytes.
WEIGHT_BLOCK_BYTES = 8 << 20


def row_tile(rows: int, experts_total: int, d: int, f: int) -> int | None:
    """Row tile for ``rows`` sorted (token, expert) pairs spread over
    ``experts_total`` experts with ``[d, f]`` weights: the power of two from
    ``MIN_ROW_TILE`` up that covers ``ROWS_COVERED`` times a group's expected
    rows. None where that reaches ``XLA_ROW_TILE`` or a width is no multiple of
    128 lanes: the caller then uses ``jax.lax.ragged_dot``."""
    if d % 128 or f % 128:
        return None
    tm = MIN_ROW_TILE
    while tm * experts_total < ROWS_COVERED * rows:
        tm *= 2
    return tm if tm < XLA_ROW_TILE else None


def visits(sizes: jnp.ndarray, m: int, tm: int):
    """The (row tile, group) pairs that share a row, in row order. sizes [G]
    int32 over ``m`` sorted rows (``tm`` divides ``m``). Returns int32 arrays:
    the count (a scalar) and, by visit (static length ``m / tm + G - 1``, entries
    past the count repeat the last visit), the group, the row tile, and the
    group's first row and end row."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    count = visit_ends[-1]
    v = jnp.minimum(jnp.arange(m // tm + G - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    # a visit's group: how many groups' visits end at or before it (one
    # compare-and-count fusion where a searchsorted would be a loop)
    group = jnp.minimum(jnp.sum(visit_ends[None, :] <= v[:, None], axis=1), G - 1)
    tile = first[group] + v - (visit_ends - tiles)[group]
    return tuple(a.astype(jnp.int32) for a in
                 (count, group, tile, starts[group], ends[group]))


def _kernel(n_rhs: int, tm: int, layer, group, tile, lo, hi, x_ref, *refs):
    del layer, group  # the index maps' business
    w_refs, out_ref = refs[:n_rhs], refs[n_rhs]
    v = pl.program_id(1)
    x = x_ref[...]
    y = [jnp.dot(x, w[...].astype(x.dtype), preferred_element_type=jnp.float32)
         for w in w_refs]
    y = jax.nn.silu(y[0]) * y[1] if n_rhs == 2 else y[0]
    row = tile[v] * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = (row >= lo[v]) & (row < hi[v])
    out_ref[...] = jnp.where(mine, y, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def gmm(xs: jnp.ndarray, weights: tuple, meta: tuple, layer, *, tm: int,
        out_dtype) -> jnp.ndarray:
    """Grouped product of sorted rows with each group's own weights. xs [M, K]
    (``tm`` divides M); ``weights``: one or two stacks ``[n, G, K, N]`` of
    which ``layer`` picks ``[G, K, N]``; ``meta`` from ``visits``. One weight:
    ``xs @ w[group]``; two: ``silu(xs @ w0[group]) * (xs @ w1[group])``.
    Returns ``[M, N]`` in ``out_dtype``."""
    M, K = xs.shape
    N = weights[0].shape[-1]
    n_rhs = len(weights)
    wbytes = weights[0].dtype.itemsize
    tn = pick_block_n(N, max(128, WEIGHT_BLOCK_BYTES // (K * wbytes)))

    # index maps see the grid indices, then the prefetched scalars
    x_spec = pl.BlockSpec((tm, K), lambda j, v, layer, group, tile, *_: (tile[v], 0))
    w_spec = pl.BlockSpec((None, None, K, tn),
                          lambda j, v, layer, group, *_: (layer[0], group[v], 0, j))
    out_spec = pl.BlockSpec((tm, tn), lambda j, v, layer, group, tile, *_: (tile[v], j))
    blocks = (n_rhs * K * tn * wbytes + tm * K * xs.dtype.itemsize
              + tm * tn * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, n_rhs, tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, meta[0]),  # as many visits as there are
            in_specs=[x_spec] + [w_spec] * n_rhs,
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # two buffers a block, the step's f32 products, and room to spare
            vmem_limit_bytes=2 * blocks + (n_rhs + 2) * tm * tn * 4 + (8 << 20)),
        interpret=interpret_default(),
        name=KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *meta[1:], xs, *weights)


def grouped_swiglu(xs: jnp.ndarray, sizes: jnp.ndarray, gate: jnp.ndarray,
                   up: jnp.ndarray, down: jnp.ndarray, layer, tm: int):
    """``ops/moe.py:grouped_swiglu`` on the kernel at row tile ``tm``: gate, up
    and the activation in one call, down in a second. Weights ``[n, E, ...]``
    with ``layer``, or ``[E, ...]`` with ``layer`` None."""
    if layer is None:
        gate, up, down, layer = gate[None], up[None], down[None], 0
    M = xs.shape[0]
    pad = -M % tm
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    meta = visits(sizes, M + pad, tm)
    h = gmm(xs, (gate, up), meta, layer, tm=tm, out_dtype=xs.dtype)
    return gmm(h, (down,), meta, layer, tm=tm, out_dtype=jnp.float32)[:M]
