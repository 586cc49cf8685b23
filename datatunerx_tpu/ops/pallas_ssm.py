"""A Mamba-2 layer's decode token step as a Pallas TPU kernel (``dtx_ssm_step``).

``ops/ssm.py:state_step`` in XLA passes three times over a layer's state: one
fusion resets, decays, updates and writes it back, and a second reads it again
for the read-out, because XLA gives an in-place dynamic-update-slice no second,
reduced output. This kernel holds a tile of the state in VMEM between the
update and the read-out: one read and one write a layer.

It takes the cache leaf ``[layers, slots, H, P, N]`` WHOLE and writes it in
place (``input_output_aliases``): the layer index is a scalar-prefetch operand
and the state's block is ``(1, 1, th, P, N)`` at ``(layer, slot, head tile)``,
for the input and for the output. A layer sliced out and set back around the
call would be copied both ways (``ops/moe.py:grouped_swiglu`` says the same of
the experts' stack). Every other layer of the leaf is never touched.

Layout. A head's state ``[P, N]`` has ``P`` on sublanes and ``N`` on lanes.
``B`` and ``C`` ``[N]`` lie along lanes and are the same for every ``p``: a
sublane broadcast, once a group. ``dt x`` ``[P]`` must vary along SUBLANES
and be broadcast across lanes, so it comes in transposed, ``[P, th]`` a head
tile (a ``[.., P, 1]`` operand would be padded to 128 lanes and double the
kernel's reads), and a head takes its column. The read-out sums over lanes
and leaves a column ``[P, 1]``, stored into the transposed result the same
way. The decay ``exp(dA)`` is a scalar a head and a slot, read from SMEM.
What XLA does around the call is small: ``dt x`` and ``exp(dA)`` (the same
``jnp.exp`` the XLA step takes), two transposes of ``[slots, H, P]`` and the
``D x`` term.

Float32 throughout, in ``state_step``'s order: ``S = where(fresh, 0, S); S =
exp(dA) S + (dt x) (x) B; y = sum_n S C + D x``, every product on the VPU. The
read-out's sum over ``n`` runs along lanes; as ``jnp.sum`` it is a chain of
cross-lane rotations on the XLU a vreg and made the kernel compute-bound (7.5
us a slot against 6.9 us of DMA on v5e). It is taken instead as the float32
products ``S C`` times a matrix of ones on the MXU at HIGHEST precision: the
three bf16 terms of a float32 carry its 24 bits, times 1, accumulated in
float32, so it is the same sum of the same products in another order, and it
hides under the DMA. A row with ``dt`` 0 and ``dA`` 0 is rewritten as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from datatunerx_tpu.ops._pallas import interpret_default

KERNEL = "dtx_ssm_step"
# Most bytes of one state block (a head tile of one slot).
BLOCK_BYTES = 2 << 20


def step_kernel(leaf, tokens: int) -> tuple:
    """(name, head tile) of what steps a Mamba-2 layer's state through
    ``tokens`` tokens a slot, from what is static: the cache leaf ``leaf``
    ``[L, B, H, P, N]`` (anything with a ``shape`` and a ``dtype``; None where
    there is no cache). The kernel where its tiles exist: one token, a float32
    leaf, ``N`` a multiple of 128 lanes and ``P`` of 8 sublanes; its head tile
    is the largest divisor of ``H`` whose block stays within ``BLOCK_BYTES``.
    Otherwise ``("xla", None)``: ``ssm.state_step`` or ``ssm.chunk_states``."""
    if tokens != 1 or leaf is None or leaf.dtype != jnp.float32:
        return ("xla", None)
    heads, head_dim, state = leaf.shape[2:]
    if state % 128 or head_dim % 8:
        return ("xla", None)
    th = max(1, min(heads, BLOCK_BYTES // (head_dim * state * 4)))
    while heads % th:
        th -= 1
    return (KERNEL, th)


def _kernel(th: int, group_heads: int, layer, fresh, decay, dtx_ref, b_ref,
            c_ref, s_ref, y_ref, s_out_ref):
    del layer  # the index maps' business
    slot, tile = pl.program_id(0), pl.program_id(1)
    P, N = s_ref.shape[-2:]
    stale = jnp.full((P, N), fresh[slot], jnp.int32) != 0
    ones = jnp.ones((N, 128), jnp.float32)
    for h in range(th):
        g = 0 if b_ref.shape[0] == 1 else (tile * th + h) // group_heads
        Brow, Crow = b_ref[g, pl.ds(slot, 1), :], c_ref[g, pl.ds(slot, 1), :]
        S = jnp.where(stale, 0.0, s_ref[h])
        S = decay[slot, tile * th + h] * S + dtx_ref[:, h:h + 1] * Brow
        s_out_ref[h] = S
        # every lane of a row: the row's sum over n
        sums = jnp.dot(S * Crow, ones, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        y_ref[:, h:h + 1] = sums[:, h % 128:h % 128 + 1]


def ssm_step(leaf, layer, fresh, x, Bm, Cm, dt, dA, D, *, th: int):
    """One token of the recurrence for every slot, on layer ``layer`` of the
    cache leaf. leaf [L, B, H, P, N] float32; layer: int32 scalar; fresh [B]
    bool (a slot that starts from nothing, whatever the leaf holds); x [B, 1,
    H, P]; Bm, Cm [B, 1, G, N]; dt, dA [B, 1, H]; D [H]; ``th`` from
    ``step_kernel``. Returns (y [B, 1, H, P] float32, the leaf with layer
    ``layer`` stepped): what ``ssm.state_step`` returns for ``where(fresh, 0,
    leaf[layer])``.

    The small operands go in, and ``y`` comes out, with the token axis FIRST
    (``[1, B, ...]``, free at one token): a Mosaic call fixes its operands
    row-major, XLA carries that back through the layer's elementwise ops, and
    ``[1, B, C]`` row-major is the layout the layer's activations have
    anyway (``[B, 1, C]`` row-major is not: they were re-laid, and the
    ``state_ssm_conv`` leaf with them)."""
    _, B, H, P, N = leaf.shape
    G = Bm.shape[2]
    # Inside a layer scan the leaf is an element of the loop's carried tuple. A
    # Mosaic call fixes its operands' layouts, and a layout fixed directly on
    # one element of a loop body's parameter makes XLA lay the body's other
    # parameters out by default instead of as the caller holds them: the
    # adapters' stacks ``[n, E, d, r]`` went rank-minor (16 times padded), were
    # copied at every dispatch, and cell 6's token step paid 6 ms for it
    # (PERF.md, PR 40). Behind a barrier the call constrains the barrier's
    # result; the barrier itself compiles to nothing and the leaf stays aliased.
    leaf = jax.lax.optimization_barrier(leaf)
    first = lambda a: jnp.moveaxis(a, 1, 0).astype(jnp.float32)  # noqa: E731
    x = first(x)
    # [1, B, H, P] -> [1, B, H / th, P, th]: a head tile's columns
    dtx = jnp.swapaxes((first(dt)[..., None] * x).reshape(1, B, H // th, th, P), 3, 4)
    groups = lambda a: jnp.moveaxis(first(a)[0], 1, 0)  # noqa: E731  [G, B, N]

    small = pl.BlockSpec((None, None, None, P, th), lambda b, j, *_: (0, b, j, 0, 0))
    # B and C whole, fetched once and indexed by slot
    group = pl.BlockSpec((G, B, N), lambda b, j, *_: (0, 0, 0))
    block = pl.BlockSpec((None, None, th, P, N),
                         lambda b, j, layer, *_: (layer[0], b, j, 0, 0))
    y, leaf = pl.pallas_call(
        functools.partial(_kernel, th, H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // th),
            in_specs=[small, group, group, block],
            out_specs=[small, block],
        ),
        out_shape=[jax.ShapeDtypeStruct((1, B, H // th, P, th), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={6: 1},  # the leaf, counted with the prefetched scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * th * P * N * 4 + (8 << 20)),
        interpret=interpret_default(),
        name=KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1), fresh.astype(jnp.int32),
      jnp.exp(first(dA)).reshape(B, H), dtx, groups(Bm), groups(Cm), leaf)
    y = jnp.swapaxes(y, 3, 4).reshape(1, B, H, P)
    return jnp.moveaxis(y + D.astype(jnp.float32)[:, None] * x, 0, 1), leaf
