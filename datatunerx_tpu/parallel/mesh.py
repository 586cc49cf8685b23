"""Device mesh construction: the TPU-native replacement for Ray worker groups.

The reference expresses cluster shape as KubeRay head+workers with one GPU each
and scales via torch-DDP allreduce (SURVEY.md §2.4). Here the unit of scale is a
`jax.sharding.Mesh` over all addressable chips with named axes:

  dp   — pure data parallelism (params replicated)
  fsdp — data parallelism with param/optimizer sharding (ZeRO-3-style, GSPMD)
  tp   — tensor parallelism (megatron-style column/row splits)
  sp   — sequence/context parallelism for ring attention (long context)

GSPMD inserts the collectives (all-reduce / all-gather / reduce-scatter) over
ICI; nothing here talks NCCL/MPI (SURVEY.md §5.8).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

MESH_AXES = ("dp", "fsdp", "tp", "sp")


def mesh_shape_for(
    n_devices: int,
    *,
    dp: Optional[int] = None,
    fsdp: Optional[int] = None,
    tp: int = 1,
    sp: int = 1,
) -> tuple[int, int, int, int]:
    """Resolve a (dp, fsdp, tp, sp) shape filling the unspecified data axis.

    Exactly one of dp/fsdp may be None; it absorbs the remaining devices.
    """
    fixed = tp * sp
    if dp is None and fsdp is None:
        dp, fsdp = n_devices // fixed, 1
    elif dp is None:
        dp = n_devices // (fsdp * fixed)
    elif fsdp is None:
        fsdp = n_devices // (dp * fixed)
    shape = (dp, fsdp, tp, sp)
    if math.prod(shape) != n_devices:
        raise ValueError(
            f"mesh shape {dict(zip(MESH_AXES, shape))} != {n_devices} devices"
        )
    return shape


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    *,
    devices=None,
    dp: Optional[int] = None,
    fsdp: Optional[int] = None,
    tp: int = 1,
    sp: int = 1,
    dcn_dp: int = 1,
) -> Mesh:
    """Build the 4-axis mesh. Axis order puts dp/fsdp outermost so data-parallel
    replicas land on distinct ICI neighborhoods and tp rides the innermost
    (fastest) links.

    Multi-slice: ``dcn_dp`` > 1 splits the dp axis hierarchically — its MAJOR
    dimension crosses slices over DCN, everything else (fsdp/tp/sp and the
    minor dp) stays inside a slice on ICI. The axis names don't change, so
    shardings/collectives are untouched; only the device ORDER encodes slice
    locality (gradient all-reduce then decomposes into intra-slice reduce +
    one cross-slice exchange, the standard multislice recipe). On hardware
    with slice indices the hybrid mesh builder assigns devices; elsewhere
    (CPU testing) contiguous chunks of the device list emulate slices.
    """
    devices = list(devices) if devices is not None else list(jax.devices())
    if shape is None:
        shape = mesh_shape_for(len(devices), dp=dp, fsdp=fsdp, tp=tp, sp=sp)
    shape = tuple(shape)
    if len(shape) != 4:
        raise ValueError(f"expected 4-axis shape {MESH_AXES}, got {shape}")
    # Auto axis types = classic GSPMD: the compiler propagates shardings from
    # NamedSharding annotations (jax 0.9 defaults to Explicit mode otherwise)
    axis_kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 4}
    if dcn_dp <= 1:
        return jax.make_mesh(shape, MESH_AXES, devices=devices, **axis_kw)

    if shape[0] % dcn_dp != 0:
        raise ValueError(
            f"dp={shape[0]} must be divisible by dcn_dp={dcn_dp} "
            "(cross-slice parallelism rides the dp axis)"
        )
    import numpy as np

    per_slice = (shape[0] // dcn_dp, shape[1], shape[2], shape[3])
    if all(getattr(d, "slice_index", None) is not None for d in devices):
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            per_slice, (dcn_dp, 1, 1, 1), devices=devices
        )
    else:
        # no slice topology (CPU / single-slice): dp-major contiguity of the
        # flat device list already IS slice-major order, so a plain reshape
        # emulates slices — the same program shape compiles and runs
        arr = np.array(devices).reshape(shape)
    return Mesh(arr, MESH_AXES, **axis_kw)
