"""What the per-layer metrics of a cell of DENSE latent attention under a
prefix cache share, over ``readers.py``, ``scope_readers.py``, ``moe_readers.py``
(whose ``ragged-dot`` rule holds here too) and ``flops_kimi.py``.

Scopes the program gives the mixer (``models/hybrid.py:mla_mixer``):
``dtx.qkv`` (the low-rank query, the latent row, the rotations), ``dtx.kv_write``
(the scatter of the token's row and, where the step reads a gathered view of
each slot's table, that read), ``dtx.mla_absorb`` (``kv_b_proj`` into the query
and out of the output) and ``dtx.attn`` (scores and values over the view).
Counters: the engine's ``prefix_stats`` (prompt tokens admissions took from
shared blocks against prompt tokens they prefilled), read at both edges of the
window by the traffic kind. A program without them (one from before a model of
several layer kinds took a prefix cache) gives ``shared_token_share`` nothing
to read, and it returns ``None``.
"""

from __future__ import annotations

import numpy as np

import flops
import flops_kimi
import moe_readers
import readers
import scope_readers

ATTN = ("dtx.attn", "dtx.mla_absorb")
KV_WRITE = ("dtx.kv_write",)
WEIGHTS = scope_readers.WEIGHTS + ("dtx.moe_shared",)
KV_POOL = scope_readers.KV_POOL

decode_region_ms = moe_readers.decode_region_ms
decode_unscoped_share = moe_readers.decode_unscoped_share
decode_step_ms = readers.decode_step_ms
prefill_chunk_ms = readers.prefill_chunk_ms
idle_share = readers.idle_share


def prefill_mla_ms(obs, chunk_tokens: int = 256):
    """Device time per ``chunk_tokens`` prompt tokens that the prefill-chunk
    programs spend under ``dtx.attn``, ``dtx.mla_absorb`` and ``dtx.kv_write``
    (the view of the slot's rows): their share of those programs' self time,
    times ``readers.prefill_chunk_ms``."""
    whole = prefill_chunk_ms(obs, chunk_tokens)
    ops = scope_readers.scoped_ops(obs)
    if whole is None or not ops:
        return None
    mine = [(moe_readers.region_of(op), t) for program, op, t in ops
            if readers.PREFILL_PROGRAM in program]
    total = sum(t for _, t in mine)
    if total <= 0 or not any(region in ATTN for region, _ in mine):
        return None
    return whole * sum(t for region, t in mine if region in ATTN + KV_WRITE) / total


def mla_decode_roofline(obs):
    """Share of its roofline (memory-bound) that a token step's latent
    attention reached: the least seconds the chip could take to read ONCE the
    live slots' latent rows up to each slot's cursor, write a row a slot and
    read ``kv_b_proj``, in every layer (``flops_kimi.mla_decode_step``, the
    mean over the traced window's decode dispatches of the rows live at each,
    with their contexts then), over the measured device seconds under
    ``dtx.attn``, ``dtx.mla_absorb`` and ``dtx.kv_write`` a token step."""
    measured_ms = decode_region_ms(obs, ATTN + KV_WRITE)
    if not measured_ms:
        return None
    mc, live = obs.cell.model_fields, readers.live_requests(obs)
    least = [flops.roofline_seconds(
        flops_kimi.mla_decode_step(mc, [ctx for _, ctx in readers.rows_at(obs, live, t)]),
        obs.peaks)["seconds"] for t in readers.decode_dispatches(obs)]
    if not least:
        return None
    return 100.0 * float(np.mean(least)) / (measured_ms / 1e3)


def shared_token_share(obs):
    """Prompt tokens mapped from shared blocks over prompt tokens admitted in
    the window, in percent: the engine's own counters at the window's edges."""
    stats = obs.engine_info.get("prefix_stats")
    if not stats:
        return None
    admitted = stats.get("shared_tokens", 0) + stats.get("prefilled_tokens", 0)
    return 100.0 * stats["shared_tokens"] / admitted if admitted else None
