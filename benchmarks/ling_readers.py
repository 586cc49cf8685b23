"""What the per-layer metrics of a cell with linear-attention (KDA) and latent
(MLA) layers share, over ``readers.py``, ``scope_readers.py`` and
``moe_readers.py`` (whose ``ragged-dot`` rule holds here too: the expert
layer's grouped matmuls are this program's only ``ragged_dot``).

Scopes the program gives the new mixers (``models/hybrid.py``): ``dtx.kda_conv``
(the short convolution with its state rows, the normalisation of q and k, the
decay gate), ``dtx.kda_state`` (everything that reads or writes the memory
matrix ``S``: the reset of a fresh slot, decay, delta update, read-out, the
write-back of both state leaves), ``dtx.kda_out`` (head norm and output gate),
``dtx.mla_absorb`` (the two absorbed products), ``dtx.moe_shared``. A program
without them (one from before the mixers existed) gives every reader here
nothing to read, and each returns ``None``.
"""

from __future__ import annotations

import flops
import flops_ling
import moe_readers
import readers
import scope_readers

KDA_STATE = ("dtx.kda_state", "dtx.kda_conv")
KDA_OUT = ("dtx.kda_out",)
ATTN = ("dtx.attn", "dtx.mla_absorb")
WEIGHTS = scope_readers.WEIGHTS + ("dtx.moe_shared",)
KDA_ALL = KDA_STATE + KDA_OUT

decode_region_ms = moe_readers.decode_region_ms
decode_unscoped_share = moe_readers.decode_unscoped_share
decode_step_ms = readers.decode_step_ms
prefill_chunk_ms = readers.prefill_chunk_ms
idle_share = readers.idle_share
rows_per_held_expert = moe_readers.rows_per_held_expert
load_max_over_mean = moe_readers.load_max_over_mean


def _has_kda(obs) -> bool:
    return "kda" in (obs.cell.model_fields.get("layer_types") or ())


def kda_region_ms(obs, regions):
    """``decode_region_ms`` where the program has the KDA scopes at all: a
    program without them reads nothing, not 0."""
    ms = decode_region_ms(obs, regions)
    if ms is None or not _has_kda(obs) or not decode_region_ms(obs, ("dtx.kda_state",)):
        return None
    return ms


def prefill_kda_ms(obs, chunk_tokens: int = 256):
    """Device time per ``chunk_tokens`` prompt tokens that the prefill-chunk
    programs spend in the KDA layers' own regions (convolution, the chunk form
    of the delta rule, head norm and gate): their share of those programs'
    self time, times ``readers.prefill_chunk_ms``."""
    whole = prefill_chunk_ms(obs, chunk_tokens)
    ops = scope_readers.scoped_ops(obs)
    if whole is None or not ops:
        return None
    mine = [(moe_readers.region_of(op), t) for program, op, t in ops
            if readers.PREFILL_PROGRAM in program]
    total = sum(t for _, t in mine)
    kda = sum(t for region, t in mine if region in KDA_ALL)
    if total <= 0 or kda <= 0:
        return None
    return whole * kda / total


def live_slots(obs):
    """Mean live requests per ``dtx_engine_decode`` of the traced window."""
    occ = readers.decode_occupancy(obs)
    return None if occ is None else occ / 100.0 * obs.engine_info["slots"]


def kda_state_roofline(obs):
    """Share of its roofline (memory-bound) that the recurrent-state update
    reached in decode: the least seconds the chip could take to read and write
    once the state and convolution rows of the step's LIVE slots in every KDA
    layer (``flops_ling.kda_state_step``), over the measured device seconds
    under ``dtx.kda_state`` a token step."""
    measured_ms = decode_region_ms(obs, ("dtx.kda_state",))
    live = live_slots(obs)
    if not measured_ms or not live or not _has_kda(obs):
        return None
    mc = obs.cell.model_fields
    work = flops_ling.kda_state_step(mc, live)
    least = flops.roofline_seconds(work, obs.peaks)["seconds"] * mc["layer_types"].count("kda")
    return 100.0 * least / (measured_ms / 1e3)


def rows_routed_here_share(obs):
    """Of the real rows the expert layers routed in decode, the share with at
    least one chosen expert held on this chip (program counters)."""
    moe = moe_readers.counters(obs)
    if not moe or not moe.get("decode_rows"):
        return None
    return 100.0 * moe["decode_rows_here"] / moe["decode_rows"]
