"""What the per-layer metrics of a sparse-expert cell share, over ``readers.py``
and ``scope_readers.py``.

The grouped matmuls of the expert layer are ``jax.lax.ragged_dot``; XLA's TPU
compiler rewrites each into a Mosaic kernel of its own (``ragged-dot-*``
custom calls) and gives the new instructions an ``op_name`` of that name,
without the ``dtx.moe_experts`` scope they were traced under (read off the
compiled HLO, PR 26). So here an op whose ``op_name`` starts with
``ragged-dot`` belongs to ``dtx.moe_experts``; every other op's region is
``scope_readers.region_of``. ``scope_readers``' two sums take no rule, so
they are written out here with this one. What the rule cannot tell apart: a
later ``ragged_dot`` elsewhere in the decode program (a grouped LoRA matmul)
would get the same ``op_name`` and be booked to the experts. The cure is on
the program's side, a grouped matmul that is a named kernel (ROADMAP M2);
these readers belong to cells whose only ``ragged_dot`` is the expert layer's.
"""

from __future__ import annotations

import flops
import flops_moe
import readers
import scope_readers

EXPERTS = "dtx.moe_experts"
ROUTE = ("dtx.moe_route", "dtx.moe_combine")


def region_of(op_name):
    if op_name and op_name.startswith("ragged-dot"):
        return EXPERTS
    return scope_readers.region_of(op_name)


def _decode(obs):
    ops = scope_readers._decode_ops(obs)
    if not ops:  # no trace, or a trace whose programs carry no scope
        return None, None
    return ops, scope_readers._decode_runs(obs)


def decode_region_ms(obs, regions):
    """As ``scope_readers.decode_region_ms`` with the rule above."""
    ops, runs = _decode(obs)
    if not ops:
        return None
    steps = len(runs) * obs.engine_info["chunk"]
    return sum(t for op, t in ops if region_of(op) in regions) * 1e3 / steps


def decode_unscoped_share(obs):
    ops, runs = _decode(obs)
    if not ops:
        return None
    return 100.0 * sum(t for op, t in ops if region_of(op) is None) / sum(runs)


def counters(obs):
    """The engine's expert counters over the measured window, or None where the
    program has none (a program from before it had expert layers)."""
    moe = obs.engine_info.get("moe_stats") or {}
    return moe if moe.get("decode_layer_steps") else None


def rows_per_held_expert(obs):
    moe = counters(obs)
    if not moe:
        return None
    held = obs.cell.model_fields["experts_held"]
    return moe["decode_local_rows"] / (moe["decode_layer_steps"] * held)


def load_max_over_mean(obs):
    moe = counters(obs)
    if not moe or not moe["decode_local_rows"]:
        return None
    held = obs.cell.model_fields["experts_held"]
    return moe["decode_max_rows"] * held / moe["decode_local_rows"]


def experts_roofline(obs):
    """Share of its roofline that the expert layers' grouped matmuls reached in
    decode: the least seconds the chip could take for one expert layer's step
    (``flops_moe.expert_layer_step`` at the window's mean rows and experts hit
    a layer-step, from the engine's counters) over the measured device seconds
    under ``dtx.moe_experts`` a layer-step, from the trace."""
    moe, mc = counters(obs), obs.cell.model_fields
    ops, runs = _decode(obs)
    if not moe or not ops:
        return None
    measured = sum(t for op, t in ops if region_of(op) == EXPERTS)
    layer_steps = len(runs) * obs.engine_info["chunk"] * mc["ffn_types"].count("experts")
    if measured <= 0 or not layer_steps:
        return None
    work = flops_moe.expert_layer_step(
        mc, moe["decode_local_rows"] / moe["decode_layer_steps"],
        moe["decode_experts_hit"] / moe["decode_layer_steps"])
    least = flops.roofline_seconds(work, obs.peaks)["seconds"]
    return 100.0 * least / (measured / layer_steps)


def kv_behind_window_share(obs):
    return obs.engine_info.get("kv_behind_window_share")


decode_step_ms = readers.decode_step_ms
prefill_chunk_ms = readers.prefill_chunk_ms
idle_share = readers.idle_share
