"""What the per-layer metrics of a cell whose queries SELECT the cached tokens
they read (learned sparse attention over latent rows) share, over ``readers.py``,
``scope_readers.py``, ``moe_readers.py`` (whose ``ragged-dot`` rule holds here
too) and ``span_stats.py``.

Scopes the program gives the mechanism (``models/hybrid.py:mla_mixer``):
``dtx.dsa_index`` (the indexer's three projections, the key's norm, the
rotation, the index scores over the slot's view of the index keys),
``dtx.dsa_select`` (the top-k, the picks' validity, a chunk's mask),
``dtx.dsa_gather`` (the chosen latent rows, through the block table);
``dtx.kv_write`` holds both scatters (and, in a chunk program, the read of the
latent rows' view); ``dtx.mla_absorb`` and ``dtx.attn`` are cell 5's. Counters:
the decode span ``dtx_engine_decode`` carries ``dsa_context`` and
``dsa_selected``, the engine's running sums of its decode rows' contexts and of
the tokens they selected (``BatchedEngine.dsa_stats``, from the device's
``cache["dsa_stats"]``). A program without them (one from before the mechanism
existed) gives every reader here nothing to read, and each returns ``None``.
"""

from __future__ import annotations

import numpy as np

import flops
import flops_glm
import moe_readers
import readers
import scope_readers
import span_stats

DSA_INDEX = ("dtx.dsa_index",)
DSA_SELECT = ("dtx.dsa_select",)
DSA_GATHER = ("dtx.dsa_gather",)
DSA = DSA_INDEX + DSA_SELECT + DSA_GATHER
ATTN = DSA_GATHER + ("dtx.attn", "dtx.mla_absorb")
DSA_AND_ATTN = DSA + ("dtx.attn",)
WEIGHTS = scope_readers.WEIGHTS + ("dtx.moe_shared",)
KV_POOL = scope_readers.KV_POOL

decode_unscoped_share = moe_readers.decode_unscoped_share
decode_step_ms = readers.decode_step_ms
prefill_chunk_ms = readers.prefill_chunk_ms
idle_share = readers.idle_share


def _selects(obs) -> bool:
    return bool(obs.cell.model_fields.get("index_topk"))


def decode_region_ms(obs, regions):
    """``moe_readers.decode_region_ms`` where the program has the selection's
    scopes at all: a program without them reads nothing, not 0."""
    if not _selects(obs) or not moe_readers.decode_region_ms(obs, DSA_INDEX):
        return None
    return moe_readers.decode_region_ms(obs, regions)


def prefill_dsa_ms(obs, chunk_tokens: int = 256):
    """Device time per ``chunk_tokens`` prompt tokens that the prefill-chunk
    programs spend under the three selection scopes and ``dtx.attn``: their
    share of those programs' self time, times ``readers.prefill_chunk_ms``."""
    whole = prefill_chunk_ms(obs, chunk_tokens)
    ops = scope_readers.scoped_ops(obs)
    if whole is None or not ops or not _selects(obs):
        return None
    mine = [(moe_readers.region_of(op), t) for program, op, t in ops
            if readers.PREFILL_PROGRAM in program]
    total = sum(t for _, t in mine)
    if total <= 0 or not any(region in DSA_INDEX for region, _ in mine):
        return None
    return whole * sum(t for region, t in mine if region in DSA_AND_ATTN) / total


def dsa_decode_roofline(obs):
    """Share of its roofline (memory-bound) that a token step's selection and
    attention reached: the least seconds the chip could take to read once the
    LIVE slots' index keys and their chosen latent rows in every layer
    (``flops_glm.dsa_decode_step``, the mean over the traced window's decode
    dispatches of the rows live at each, with their contexts then), over the
    measured device seconds under the three selection scopes and ``dtx.attn``
    a token step."""
    measured_ms = decode_region_ms(obs, DSA_AND_ATTN)
    if not measured_ms:
        return None
    mc, live = obs.cell.model_fields, readers.live_requests(obs)
    least = [flops.roofline_seconds(
        flops_glm.dsa_decode_step(mc, [ctx for _, ctx in readers.rows_at(obs, live, t)]),
        obs.peaks)["seconds"] for t in readers.decode_dispatches(obs)]
    if not least:
        return None
    return 100.0 * float(np.mean(least)) / (measured_ms / 1e3)


def context_over_topk(obs):
    """Mean live context over mean tokens selected, of the decode rows between
    the first and the last decode dispatch of the traced window: the program's
    own counters, read off the keywords of its decode spans."""
    lo, hi = obs.trace_clock or (0.0, 0.0)
    marks = [stats for name, start, dur, stats in span_stats.spans(obs)
             if name == readers.DECODE_SPAN and lo <= start and start + dur <= hi
             and "dsa_context" in stats and "dsa_selected" in stats]
    if len(marks) < 2:
        return None
    context = float(marks[-1]["dsa_context"]) - float(marks[0]["dsa_context"])
    selected = float(marks[-1]["dsa_selected"]) - float(marks[0]["dsa_selected"])
    return context / selected if selected > 0 else None
