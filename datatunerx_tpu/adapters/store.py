"""AdapterStore: a fixed-geometry device pool of LoRA adapter weights.

The S-LoRA/Punica unlock (PAPER.md §0 end-state: one base model serving
hundreds of tenant adapters): adapter weights become **data** instead of
engine config. The store owns, per LoRA target, a stacked pool buffer

    a: [L, P + 1, d_in, rank_max]      b: [L, P + 1, rank_max, d_out]

(P usable pool slots + the reserved all-zero base slot 0) plus a scale
vector ``[P + 1]``. The layout is exactly the stacked-adapter tree
``models/llama.forward`` already consumes via ``lora_adapter_idx`` — each
batch row gathers its own slot inside the matmul — so a pool insert is a
functional ``.at[:, slot].set`` write and the decode program never changes
shape: loading/unloading an adapter at runtime causes ZERO recompiles
(the batched engine passes the pool as a program ARGUMENT, not a closure
constant, and jax keys executables on shapes only).

Adapters with rank < rank_max are zero-padded: zero columns of A and zero
rows of B contribute nothing to h·A·B, so padding is numerically invisible
(the parity tests assert token-exactness vs the unpadded stack). Adapters
with rank > rank_max or targets outside the pool's target set are rejected
with typed errors — the geometry is the program identity and cannot grow
at runtime.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.models.config import ModelConfig
from datatunerx_tpu.models.lora import (
    DEFAULT_TARGETS,
    LORA_TARGETS,
    adapter_leaves,
    group_tree,
    lora_groups,
)


class AdapterRankError(ValueError):
    """Adapter rank exceeds the pool's rank_max (a geometry violation —
    the pool would have to recompile to hold it)."""


class AdapterTargetError(ValueError):
    """Adapter trains a target projection the pool does not carry."""


def adapter_rank(layers: dict) -> int:
    """The (max, across targets) rank of a loaded adapter layer tree."""
    return max(np.asarray(leaf["a"]).shape[-1]
               for leaf in adapter_leaves(layers))


def validate_adapter(layers: dict, rank_max: int,
                     targets: Sequence[str], name: str = "") -> int:
    """Check a loaded adapter tree against the pool geometry; returns its
    rank. Raises AdapterRankError / AdapterTargetError with the numbers an
    operator needs to fix the mismatch."""
    label = f"adapter {name!r}" if name else "adapter"
    if not layers:
        raise ValueError(f"{label}: empty lora layer tree")
    named = set()  # targets, whether the tree is flat or one group per run
    for key, value in layers.items():
        named |= {key} if "a" in value else set(value)
    extra = sorted(named - set(targets))
    if extra:
        raise AdapterTargetError(
            f"{label}: targets {extra} not in the pool's target set "
            f"{sorted(targets)}; restart the server with --adapter_targets "
            "covering them")
    rank = adapter_rank(layers)
    if rank > rank_max:
        raise AdapterRankError(
            f"{label}: rank {rank} exceeds the pool's rank_max {rank_max}; "
            "re-train at a lower rank or restart with a larger "
            "--adapter_rank_max")
    return rank


class AdapterStore:
    """Device pool buffers + slot bookkeeping. Mutations (insert/clear) are
    functional array updates that atomically republish ``self.tree`` — the
    scheduler thread reads that one attribute per tick, so a reader always
    sees a consistent (tree, scales) snapshot even while an admin thread
    loads an adapter."""

    def __init__(self, cfg: ModelConfig, pool_slots: int, rank_max: int,
                 targets: Sequence[str] = DEFAULT_TARGETS):
        if pool_slots < 1:
            raise ValueError(f"pool_slots must be >= 1, got {pool_slots}")
        if rank_max < 1:
            raise ValueError(f"rank_max must be >= 1, got {rank_max}")
        targets = tuple(sorted(set(targets)))
        bad = [t for t in targets if t not in LORA_TARGETS]
        if bad:
            raise ValueError(
                f"invalid lora targets {bad}; choices: {LORA_TARGETS}")
        self.cfg = cfg
        self.pool_slots = int(pool_slots)  # usable slots, device idx 1..P
        self.rank_max = int(rank_max)
        self.targets = targets
        E = self.pool_slots + 1  # + base zero slot 0
        # one buffer per (group of like layers, target): a model whose layers
        # are all of one kind has the single group None (models/lora.py)
        self._groups = lora_groups(cfg)
        self._buffers: Dict[tuple, Dict[str, jnp.ndarray]] = {}
        for gkey, L, dims in self._groups:
            for t in targets:
                if t not in dims:
                    continue
                d_in, d_out = dims[t]
                self._buffers[(gkey, t)] = {
                    "a": jnp.zeros((L, E, d_in, rank_max), jnp.float32),
                    "b": jnp.zeros((L, E, rank_max, d_out), jnp.float32),
                }
        held = {t for _, t in self._buffers}
        if held != set(targets):
            raise ValueError(
                f"lora targets {sorted(set(targets) - held)} exist in no layer "
                f"of model {cfg.name!r}")
        self._scales = jnp.zeros((E,), jnp.float32)
        self.tree: Tuple[dict, jnp.ndarray] = self._republish()
        # Warm the clear() update programs now (clearing slot 1 is a no-op
        # on freshly zeroed buffers): the scalar .set(0.0) traces a
        # different program than insert's array .set, and without this the
        # FIRST eviction paid that compile mid-serving — caught by the
        # SAN003 compile_budget(0) window around runtime load/unload.
        self.clear(1)

    # ------------------------------------------------------------- geometry
    def geometry(self) -> tuple:
        """The pool's program-identity tuple (what the engine memo keys
        would need if the pool were a closure constant — it is not, so this
        is documentation + stats surface)."""
        return (self.pool_slots, self.rank_max, self.targets)

    def nbytes(self) -> int:
        """Device bytes the pool holds — the HBM the operator budgeted via
        adapterPool × adapterRankMax (README 'Multi-adapter serving')."""
        total = sum(int(buf["a"].nbytes) + int(buf["b"].nbytes)
                    for buf in self._buffers.values())
        return total + int(self._scales.nbytes)

    # ------------------------------------------------------------ mutations
    def _republish(self):
        layers: dict = {}
        for (gkey, t), buf in self._buffers.items():
            group = layers if gkey is None else layers.setdefault(gkey, {})
            group[t] = dict(buf)
        self.tree = ({"layers": layers}, self._scales)
        return self.tree

    def insert(self, slot: int, layers: dict, scaling: float,
               name: str = "") -> int:
        """Pad + write one adapter into pool ``slot`` (device idx 1..P).
        Validates geometry first; a rejected adapter leaves the pool
        untouched. Returns the adapter's rank."""
        self._check_slot(slot)
        rank = validate_adapter(layers, self.rank_max, self.targets,
                                name=name)
        for (gkey, t), buf in self._buffers.items():
            L = buf["a"].shape[0]
            given = group_tree(layers, gkey)
            if t in given:
                ar = np.asarray(given[t]["a"], np.float32)  # [L, d_in, r]
                br = np.asarray(given[t]["b"], np.float32)  # [L, r, d_out]
                if ar.shape[0] != L:
                    raise ValueError(
                        f"adapter {name!r}: {t} has {ar.shape[0]} layers, "
                        f"model has {L}")
                r = ar.shape[-1]
                a_row = np.zeros(
                    (L,) + buf["a"].shape[2:], np.float32)
                b_row = np.zeros(
                    (L,) + buf["b"].shape[2:], np.float32)
                a_row[:, :, :r] = ar
                b_row[:, :r, :] = br
            else:  # target absent from this adapter: zero delta
                a_row = np.zeros((L,) + buf["a"].shape[2:], np.float32)
                b_row = np.zeros((L,) + buf["b"].shape[2:], np.float32)
            buf["a"] = buf["a"].at[:, slot].set(jnp.asarray(a_row))
            buf["b"] = buf["b"].at[:, slot].set(jnp.asarray(b_row))
        self._scales = self._scales.at[slot].set(float(scaling))
        self._republish()
        return rank

    def clear(self, slot: int):
        """Zero a slot (eviction hygiene: a stale adapter must never leak
        into a request that lands on a recycled slot before its insert)."""
        self._check_slot(slot)
        for buf in self._buffers.values():
            buf["a"] = buf["a"].at[:, slot].set(0.0)
            buf["b"] = buf["b"].at[:, slot].set(0.0)
        self._scales = self._scales.at[slot].set(0.0)
        self._republish()

    def _check_slot(self, slot: int):
        if not 1 <= slot <= self.pool_slots:
            raise ValueError(
                f"pool slot {slot} out of range 1..{self.pool_slots} "
                "(slot 0 is the reserved base adapter)")


def hbm_bytes(cfg: ModelConfig, pool_slots: int, rank_max: int,
              targets: Sequence[str] = DEFAULT_TARGETS) -> int:
    """Pool HBM for a geometry WITHOUT building it — the operator-facing
    sizing helper the README table uses."""
    E = pool_slots + 1
    total = E * 4  # scales float32
    for _, L, dims in lora_groups(cfg):
        for t in sorted(set(targets) & set(dims)):
            d_in, d_out = dims[t]
            total += 4 * L * E * rank_max * (d_in + d_out)
    return total
