"""What the per-layer metrics of a cell with state-space (Mamba-2) layers share,
over ``readers.py``, ``scope_readers.py`` and ``moe_readers.py`` (whose sums take
``region_of``; this program has no ``ragged_dot`` at all).

Scopes the program gives the new mixer (``models/hybrid.py:ssm_mixer``):
``dtx.ssm_conv`` (the short convolution with its bias and its state rows, the
split into x, B, C, ``dt`` and the decay), ``dtx.ssm_state`` (everything that
reads or writes the state ``S``: the reset of a fresh slot, decay, update,
read-out, the write-back of both state leaves; in a chunk program the SSD
products), ``dtx.ssm_out`` (the gate and the gated norm). ``in_proj`` lies under
``dtx.qkv`` and ``out_proj`` under ``dtx.attn_out`` with the other weights. A
program without these scopes (one from before the mixer existed) gives every
reader here nothing to read, and each returns ``None``.
"""

from __future__ import annotations

import flops
import flops_granite
import ling_readers
import moe_readers
import readers
import scope_readers

SSM_STATE = ("dtx.ssm_state", "dtx.ssm_conv")
SSM_OUT = ("dtx.ssm_out",)
SSM_ALL = SSM_STATE + SSM_OUT
ATTN = ("dtx.attn",)
WEIGHTS = scope_readers.WEIGHTS
KV_POOL = scope_readers.KV_POOL

decode_region_ms = moe_readers.decode_region_ms
decode_unscoped_share = moe_readers.decode_unscoped_share
decode_step_ms = readers.decode_step_ms
prefill_chunk_ms = readers.prefill_chunk_ms
idle_share = readers.idle_share
live_slots = ling_readers.live_slots  # mean live requests per ``dtx_engine_decode`` of the traced window


def _has_ssm(obs) -> bool:
    return "ssm" in (obs.cell.model_fields.get("layer_types") or ())


def ssm_region_ms(obs, regions):
    """``decode_region_ms`` where the program has the state-space scopes at
    all: a program without them reads nothing, not 0."""
    ms = decode_region_ms(obs, regions)
    if ms is None or not _has_ssm(obs) or not decode_region_ms(obs, ("dtx.ssm_state",)):
        return None
    return ms


def prefill_ssm_ms(obs, chunk_tokens: int = 256):
    """Device time per ``chunk_tokens`` prompt tokens that the prefill-chunk
    programs spend in the Mamba-2 layers' own regions (convolution, the SSD
    chunk form, gate and norm): their share of those programs' self time,
    times ``readers.prefill_chunk_ms``."""
    whole = prefill_chunk_ms(obs, chunk_tokens)
    ops = scope_readers.scoped_ops(obs)
    if whole is None or not ops:
        return None
    mine = [(moe_readers.region_of(op), t) for program, op, t in ops
            if readers.PREFILL_PROGRAM in program]
    total = sum(t for _, t in mine)
    ssm = sum(t for region, t in mine if region in SSM_ALL)
    if total <= 0 or ssm <= 0:
        return None
    return whole * ssm / total


def ssm_state_roofline(obs):
    """Share of its roofline (memory-bound) that the recurrent-state update
    reached in decode: the least seconds the chip could take to read and write
    once the state and convolution rows of the step's LIVE slots in every
    Mamba-2 layer (``flops_granite.ssm_state_step``), over the measured device
    seconds under ``dtx.ssm_state`` a token step."""
    measured_ms = decode_region_ms(obs, ("dtx.ssm_state",))
    live = live_slots(obs)
    if not measured_ms or not live or not _has_ssm(obs):
        return None
    work = flops_granite.ssm_state_step(obs.cell.model_fields, live)
    least = flops.roofline_seconds(work, obs.peaks)["seconds"]
    return 100.0 * least / (measured_ms / 1e3)
