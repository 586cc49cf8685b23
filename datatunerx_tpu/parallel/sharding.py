"""GSPMD sharding rules for the stacked-param tree.

Replaces DeepSpeed ZeRO config (reference cmd/tuning/ds_config.json — shipped at
stage 0, i.e. no sharding at all) with first-class partition specs:

- `fsdp` shards the contraction dim of every kernel (ZeRO-3-equivalent: params,
  grads and optimizer state all sharded; XLA all-gathers just-in-time).
- `tp` shards the output dim of column-parallel kernels (q/k/v/gate/up) and the
  input dim of row-parallel kernels (o/down) — megatron layout, so each block
  needs a single psum pair inserted by GSPMD.
- Activations shard batch over (dp, fsdp) and model dim over tp.

Rules are path-based over the HF-style leaf names, so they apply equally to the
base params, LoRA adapters, gradients, and optimizer-state mirrors.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (leaf-name match, array rank) → spec builder. Stacked layer axis (leading, rank-3
# kernels) is never sharded: every device owns every layer slice it needs.
_COLUMN = {"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"}
_ROW = {"o_proj", "down_proj"}


_MODULES = _COLUMN | _ROW | {"lm_head", "embed_tokens"}


def _spec_for(path: tuple[str, ...], x: Any) -> P:
    names = [p for p in path if isinstance(p, str)]
    leaf = names[-1] if names else ""
    # the owning module may sit deeper than names[-2] (e.g.
    # layers/<proj>/quant/<leaf>) — search the path for a known module name
    module = next((n for n in names if n in _MODULES), "")
    rank = getattr(x, "ndim", len(getattr(x, "shape", ())))

    if leaf == "embedding":  # [V, D]
        return P("tp", "fsdp")
    if module == "lm_head":  # [D, V]
        return P("fsdp", "tp")
    if leaf == "a" and rank == 3:  # LoRA A [L, in, r]
        return P(None, "fsdp" if module in _COLUMN else "tp", None)
    if leaf == "b" and rank == 3:  # LoRA B [L, r, out]
        return P(None, None, "tp" if module in _COLUMN else "fsdp")
    if leaf == "kernel" and rank == 3:  # [L, in, out]
        if module in _ROW:
            return P(None, "tp", "fsdp")
        return P(None, "fsdp", "tp")
    if leaf == "q" and rank == 3:  # int8 kernel [L, in, out] (ops/quant.py)
        if module in _ROW:
            return P(None, "tp", "fsdp")
        return P(None, "fsdp", "tp")
    if leaf == "bias" and rank == 2:  # [L, out]
        return P(None, "tp" if module in _COLUMN else "fsdp")
    if leaf == "scale" and rank == 2 and (module in _COLUMN or module in _ROW):
        # int8 per-channel scales [L, out]
        return P(None, "tp" if module in _COLUMN else "fsdp")
    if leaf in ("packed", "scale_q") and rank >= 2:
        # nf4 blocks are output-channel-contiguous: shard the block axis
        return P(None, "fsdp", *([None] * (rank - 2)))
    if leaf == "scale":  # norms — tiny, replicate
        return P()
    # optimizer-state scalars (counts) and anything unrecognized: replicate
    if rank == 0:
        return P()
    return P()


def param_pspecs(tree) -> Any:
    """Pytree of PartitionSpec matching `tree` (params / lora / grads / opt state)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: _spec_for(tuple(getattr(k, "key", k) for k in path), x), tree
    )


def tree_shardings(tree, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_pspecs(tree),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_tree(tree, mesh: Mesh) -> Any:
    """device_put `tree` onto the mesh according to the param rules."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, tree_shardings(tree, mesh)
    )


def per_device_bytes(tree) -> dict:
    """Bytes of ``tree`` resident on each local device, by device id (from
    ``addressable_shards``: a replicated leaf counts once per device) — how a
    layout that silently keeps everything on device 0 shows up."""
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                key = str(shard.device.id)
                out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def batch_pspec(rank: int = 2, accum: bool = False) -> P:
    """Token batches [B, T, ...]: batch over (dp, fsdp), sequence over sp.

    With gradient accumulation the leading axis is the scan axis [A, mb, T] —
    it must stay unsharded (every device steps through all A microbatches) and
    the *microbatch* axis carries the data parallelism.
    """
    if accum:
        return P(None, ("dp", "fsdp"), "sp", *([None] * (rank - 3)))
    return P(("dp", "fsdp"), "sp", *([None] * (rank - 2)))


def batch_shardings(batch, mesh: Mesh, accum: bool = False) -> Any:
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, batch_pspec(x.ndim, accum=accum)), batch
    )


def place_batch(batch: dict, mesh: Optional[Mesh], accum: bool = False) -> dict:
    """Place one host-local batch dict onto the mesh.

    Single source of truth for batch placement — the Trainer's inline path and
    the DevicePrefetcher (data/prefetch.py) both call this, so pipelined and
    synchronous feeding are byte-identical. Batches handed in are HOST-LOCAL
    slices: single-process (host slice == global batch) uses a plain
    device_put; multi-host assembles the global array from per-process slices —
    device_put there would misread the local slice as the global array (half
    the data silently dropped)."""
    flat = {k: v for k, v in batch.items() if v is not None}
    if mesh is None:
        return flat
    sh = batch_shardings(flat, mesh, accum=accum)
    if jax.process_count() > 1:
        import numpy as np

        return {
            # v is the host-local numpy slice from the input pipeline (never
            # a device array): asarray is the no-copy coercion
            # make_array_from_process_local_data requires, not a device sync
            k: jax.make_array_from_process_local_data(sh[k], np.asarray(v))  # dtxlint: disable=DTX001 -- host numpy, no sync
            for k, v in flat.items()
        }
    return {k: jax.device_put(v, sh[k]) for k, v in flat.items()}
