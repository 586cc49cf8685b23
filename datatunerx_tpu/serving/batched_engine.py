"""Continuous-batching inference engine (JetStream-style decode, SURVEY §7.1).

The round-1 engine decoded one request at a time (batch=1, LoRA merged at
load). This engine runs a SINGLE jitted decode program over S cache slots and
admits new requests into free slots between decode chunks — the serving tier
the reference buys from Ray Serve (reference pkg/util/generate/
generate.go:160-329 deploys LlamaDeployment replicas), rebuilt TPU-first:

- per-slot KV cache cursors (models/llama.py ``init_cache(per_slot=True)``):
  rows sit at different depths inside one program; sentinel rope positions
  mask free/garbage slots, so no per-slot programs and no re-batching pauses;
- PAGED KV cache (``kv_block_size > 0``, ops/paged_attention.py): the cache
  is a pool of fixed-size blocks + per-slot block tables instead of dense
  ``slots × max_seq_len`` rows. Admission reserves ``ceil((prompt +
  max_new) / block_size)`` blocks from a free list — a short chat no longer
  strands a full-width row of HBM, so a smaller pool (``kv_blocks``) carries
  the same traffic, or the same pool carries more slots;
- CHUNKED PREFILL (paged mode): a cold prompt prefills directly into its
  slot's blocks in ``prefill_chunk``-token programs, interleaved with decode
  — the scheduler spends at most ``prefill_token_budget`` prefill tokens
  between decode chunks, so one long prompt can no longer stall every
  in-flight decode for its whole prefill (Sarathi-style stall-free
  scheduling; bounds TTFT and TPOT under mixed long/short load);
- decode runs in CHUNKS of K tokens per program (``lax.scan`` over the
  single-token step): K amortizes dispatch latency while keeping admission
  latency bounded at K tokens;
- UNMERGED multi-adapter LoRA: adapters are stacked ([L, E, d, r]) and each
  slot indexes its own adapter inside the matmul (models/llama.py _proj
  lora_idx) — one base model serves many tuned jobs concurrently;
- streaming: each emitted token lands on the request's queue as soon as its
  chunk completes (SSE transport in serving/server.py).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import json
import math
import queue
import sys
import threading
import time
import uuid
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.data.templates import Template, get_template
from datatunerx_tpu.obs.metrics import (
    Registry,
    adapter_load_histogram,
    serving_latency_histograms,
)
from datatunerx_tpu.obs.trace import TraceStore, build_request_span
from datatunerx_tpu.models.llama import forward, init_cache
from datatunerx_tpu.models.lora import LORA_TARGETS, lora_scaling
from datatunerx_tpu.ops import mla
from datatunerx_tpu.ops._pallas import interpret_default
from datatunerx_tpu.ops.paged_attention import (
    init_paged_cache,
    kv_leaf_keys,
    paged_copy_block,
    paged_extract_row,
    paged_insert_row,
    paged_install_table,
    row_pad,
    row_trim,
    state_insert,
    state_leaf_keys,
    state_slot,
)
from datatunerx_tpu.ops.pallas_sampling import (
    default_impl as sampling_default_impl,
    sample_rows,
)
from datatunerx_tpu.serving.engine import _sample_jit
from datatunerx_tpu.serving.kv_pool import KVPool
from datatunerx_tpu.utils.decoding import DECODE_BUCKET, prepare_prompt
from datatunerx_tpu.utils.model_loader import load_model_and_tokenizer

MAX_STOP = 8  # static per-slot stop-token capacity

# global arrival order: preemption fairness (never preempt the oldest,
# resume strictly before admitting anything younger) needs a total order
# across waiting, parked, and slot-holding requests; itertools.count is
# C-level atomic, so concurrent submit() threads need no extra lock
_REQ_SEQ = itertools.count()


class _RetryLater(Exception):
    """A migration command that can't complete THIS tick but may next one
    (adapter mid-load, no free slot, KV blocks exhausted) — the scheduler
    re-queues it until its deadline."""


class _PrefixCache:
    """Host-side LRU of prefilled single-row KV caches keyed by
    (prompt tokens, adapter). An exact hit skips prefill entirely; the longest
    strict-prefix hit turns prefill into a (shorter) suffix extension — the
    prefix-reuse tier of paged serving stacks (vLLM/JetStream), host-managed
    here because rows are full-width and slots are few.

    Lookup structure is a per-adapter token TRIE: ``longest_prefix`` walks at
    most ``len(tokens)`` nodes, so admission cost is O(prompt_len) instead of
    the round-2 O(entries × prompt_len) linear scan over all stored keys.
    The OrderedDict keeps only LRU recency + the entry payloads; the trie
    mirrors its key set (terminal nodes point back at the exact key).

    Entries: {"cache": row_cache, "logits": last-token logits,
    "cursor": cache write depth}. Stored row caches are immutable JAX
    arrays — inserting a row into a slot copies, and extension builds a new
    functional cache, so shared prefixes are safe.

    COW mode (kv_overcommit engines) stores BLOCK entries instead:
    {"blocks": [ids], "full": n, "rem": r, "cursor", "logits"} — refcounted
    physical blocks a hit maps straight into the new slot's table, no dense
    row anywhere. ``on_evict`` receives every entry leaving the cache
    (capacity eviction, same-key replacement, drop_adapter) so the engine
    can return block entries' refs to the allocator.
    """

    def __init__(self, capacity: int, on_evict=None):
        from collections import OrderedDict

        self.capacity = capacity
        self._on_evict = on_evict
        self._d: "OrderedDict[tuple, dict]" = OrderedDict()
        # adapter -> trie root; node = [children {tok: node}, terminal key]
        self._roots: Dict[int, list] = {}
        self.evictions = 0
        # the scheduler thread is the lookup/insert path, but the dynamic
        # adapter plane invalidates from admin HTTP threads (drop_adapter
        # on unload/rebind) — the lock keeps the dict+trie consistent;
        # host-side dict work, negligible next to any device call
        self._lock = threading.Lock()

    def __len__(self):
        with self._lock:
            return len(self._d)

    def get(self, key):
        with self._lock:
            ent = self._d.get(key)
            if ent is not None:
                self._d.move_to_end(key)
            return ent

    def longest_prefix(self, tokens: tuple, adapter: int):
        """Longest stored strict prefix of ``tokens`` for this adapter —
        one trie descent, deepest terminal wins."""
        with self._lock:
            node = self._roots.get(adapter)
            if node is None:
                return None, None
            best_key = None
            for i in range(len(tokens) - 1):  # strict: depth < len(tokens)
                node = node[0].get(tokens[i])
                if node is None:
                    break
                if node[1] is not None:
                    best_key = node[1]
            if best_key is None:
                return None, None
            self._d.move_to_end(best_key)
            return best_key, self._d[best_key]

    def put(self, key, ent):
        dropped = []
        with self._lock:
            is_new = key not in self._d
            if not is_new:
                # same-key replacement: the old entry's resources (COW
                # block refs) must be released like any other eviction
                dropped.append(self._d[key])
            self._d[key] = ent
            self._d.move_to_end(key)
            if is_new:
                ptoks, adapter = key
                node = self._roots.setdefault(adapter, [{}, None])
                for t in ptoks:
                    node = node[0].setdefault(t, [{}, None])
                node[1] = key
            while len(self._d) > self.capacity:
                old_key, old_ent = self._d.popitem(last=False)
                self._trie_remove(old_key)
                self.evictions += 1
                dropped.append(old_ent)
        # outside the lock: the callback frees allocator blocks (its own
        # lock) and must never nest under this one
        self._notify_evicted(dropped)

    def _notify_evicted(self, entries):
        if self._on_evict is None:
            return
        for ent in entries:
            self._on_evict(ent)

    def snapshot_entries(self):
        """MRU-first (key, entry) pairs WITHOUT touching recency — the
        fleet prefix tier's publish scan. The list is a point-in-time
        copy; entries may be evicted while the caller iterates (COW block
        entries are only freed via on_evict, so a concurrently-evicted
        entry's blocks may already be recycled — callers on the scheduler
        thread are safe, eviction happens there or under drop_adapter
        which the admin surface serializes)."""
        with self._lock:
            return list(reversed(list(self._d.items())))

    def pop_lru_block_entry(self, idle=None):
        """Evict (and return) the least-recently-used BLOCK entry (of those
        ``idle(entry)`` holds for, where given) — the
        overcommit scheduler's first reclamation tier when growth finds
        the pool empty: cached prefixes are a performance tier, live
        sessions are the product. None when no block entries remain.
        The caller owns the entry's block refs (on_evict is NOT called)."""
        with self._lock:
            for key, ent in self._d.items():
                if ent.get("blocks") and (idle is None or idle(ent)):
                    del self._d[key]
                    self._trie_remove(key)
                    self.evictions += 1
                    return ent
        return None

    def drop_adapter(self, adapter):
        """Invalidate every entry cached under one adapter identity —
        required when an adapter NAME is rebound to different weights
        (unload / re-register): cached KV rows were computed with the old
        weights and would silently poison the new binding. Called from
        admin threads; the lock covers the scheduler's concurrent use."""
        dropped = []
        with self._lock:
            for key in [k for k in self._d if k[1] == adapter]:
                dropped.append(self._d.pop(key))
                self._trie_remove(key)
        self._notify_evicted(dropped)

    def _trie_remove(self, key):
        ptoks, adapter = key
        root = self._roots.get(adapter)
        if root is None:
            return
        path, node = [root], root
        for t in ptoks:
            node = node[0].get(t)
            if node is None:
                return
            path.append(node)
        node[1] = None
        # prune now-useless nodes bottom-up so the trie never outgrows
        # capacity × prompt_len
        for i in range(len(path) - 1, 0, -1):
            n = path[i]
            if n[0] or n[1] is not None:
                break
            del path[i - 1][0][ptoks[i - 1]]
        if not root[0] and root[1] is None:
            del self._roots[adapter]


class Request:
    def __init__(self, prompt_ids: Sequence[int], max_new_tokens: int,
                 temperature: float, top_p: float, seed: int,
                 stop_ids: Sequence[int], adapter: int,
                 adapter_name: str = "", trace_id: str = "",
                 tenant: str = "", tenant_tier: str = "standard"):
        self.prompt_ids = list(prompt_ids)
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_p = top_p
        self.seed = seed
        self.stop_ids = list(stop_ids)[:MAX_STOP]
        # arrival order across every parked population (waiting queue,
        # preemption parking, slots) — the preemption policy's fairness
        # and never-preempt-the-oldest invariants compare these
        self.seq = next(_REQ_SEQ)
        # device pool/stack index; in dynamic mode -1 until admission
        # resolves (and pins) the NAME to a pool slot via the registry
        self.adapter = adapter
        self.adapter_name = adapter_name
        # tenancy plane: resolved at submit from the engine's directory
        # (header name first, adapter mapping second). "" = anonymous —
        # scheduled exactly like a pre-tenancy request. tenant_tier feeds
        # the overcommit preemption order; see _reclaim_for.
        self.tenant = tenant
        self.tenant_tier = tenant_tier
        # residency at FIRST admission attempt (None until then) — the
        # trace's loaded flag must reflect whether this request paid the
        # load, not the state after its own load completed
        self.adapter_was_resident: Optional[bool] = None
        # hit/miss stats latch: a readmission retry (pin released on
        # KV-block exhaustion) must not re-count this request's lookup
        self.adapter_stats_counted = False
        self.tokens: List[int] = []
        self.stream: "queue.Queue[Optional[int]]" = queue.Queue()
        self.done = threading.Event()
        self.error: Optional[str] = None
        # --- observability: the request's own span timeline. Stamps are
        # plain attribute writes from the scheduler thread (no locks, no
        # device reads) so recording never perturbs the decode loop.
        self.trace_id = trace_id
        self.t_submit = time.perf_counter()
        self.wall_submit_ms = time.time() * 1e3
        self.timeline: List[tuple] = []  # (perf stamp, event, detail dict)
        self.first_token_ts: Optional[float] = None
        self.last_token_ts: Optional[float] = None
        # the scheduler tick ``submit`` saw, and what an admission attempt
        # of this request's own last failed for ("blocks" / "adapter"):
        # the admit mark's waited_ticks / waited_for
        self.tick_submit = 0
        self.waited_for: Optional[str] = None

    def mark(self, event: str, **detail):
        self.timeline.append((time.perf_counter(), event, detail))

    def push(self, token: int):
        # token arrival stamps: taken right after the decode chunk's designed
        # host sync, so TTFT/TPOT derived from them are true wall numbers
        now = time.perf_counter()
        if self.first_token_ts is None:
            self.first_token_ts = now
        self.last_token_ts = now
        self.tokens.append(token)
        self.stream.put(token)

    def finish(self, error: Optional[str] = None):
        self.error = error
        self.stream.put(None)
        self.done.set()


class _Phase:
    """One phase of the scheduler, recorded twice from one place: the
    ``jax.profiler.TraceAnnotation`` of that name (a flag test with no
    profiler session open) and the phase's host seconds and a count in the
    engine's always-on table ``sched_stats[name] = [seconds, count]``
    (``dtx_serving_sched_seconds_total`` / ``_phases_total``). Both readers
    get the same boundaries, so the trace and the counters cannot disagree."""

    __slots__ = ("_row", "_span", "_t0")

    def __init__(self, stats: Dict[str, list], name: str, detail: dict):
        self._row = stats.get(name) or stats.setdefault(name, [0.0, 0])
        self._span = jax.profiler.TraceAnnotation(name, **detail)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._row[0] += time.perf_counter() - self._t0
        self._row[1] += 1
        return self._span.__exit__(*exc)


def load_checkpoint_state(checkpoint_path: str) -> dict:
    """Load an Orbax TrainState checkpoint dir (…/checkpoints[/<step>]) and
    return its raw state dict ({"lora": …} and/or {"params": …}), plus the
    recorded manifest lora scaling under "_scaling" when available."""
    import os

    import orbax.checkpoint as ocp

    from datatunerx_tpu.serving.engine import InferenceEngine

    root = checkpoint_path.rstrip("/")
    step: Optional[int] = None
    if os.path.basename(root).isdigit():
        step = int(os.path.basename(root))
        root = os.path.dirname(root)
    mngr = ocp.CheckpointManager(root)
    step = step if step is not None else mngr.latest_step()
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {checkpoint_path}")
    from datatunerx_tpu.training.checkpoint import restore_raw_state

    restored = restore_raw_state(mngr, step)
    mngr.close()
    state = restored if isinstance(restored, dict) else dict(restored)
    state["_scaling"] = InferenceEngine._manifest_lora_scaling(root)
    return state


# Bounded LRU: each entry pins the donor engine's closure (its jitted bound
# methods) + the compiled executables, so an unbounded dict would leak across
# a long-lived process cycling many distinct configs. 8 covers any realistic
# set of concurrently-live serving configs; evicted entries free their
# executables once the owning engines are gone.
_PROGRAM_MEMO: "collections.OrderedDict" = collections.OrderedDict()
_PROGRAM_MEMO_MAX = 8

# cache["moe_stats"] columns (ops/moe.py N_STATS)
MOE_STAT_NAMES = ("local_rows", "experts_hit", "max_rows", "layer_steps",
                  "rows_here", "rows")
# cache["dsa_stats"] columns (ops/dsa.py N_STATS)
DSA_STAT_NAMES = ("steps", "rows", "context", "selected")


def _program_memo_key(cfg, max_seq_len: int, kv_quant,
                      epilogue: str = "off"):
    """Hashable identity of the engine's traced programs, or None when it
    can't be established (exotic values → compile fresh). The dataclass repr
    covers every model-config field deterministically. Adapters are NOT part
    of the key: LoRA weights (a stacked tree or the dynamic pool) enter the
    programs as ARGUMENTS, so jax's own executable cache keys on their
    shapes — any adapter set with the same geometry shares one compiled
    program, and loading/unloading a pool adapter recompiles nothing.
    ``epilogue`` (the RESOLVED sampling-epilogue impl: "off" | "kernel" |
    "xla") changes what the decode program traces, so it keys too."""
    try:
        return (repr(cfg), int(max_seq_len), kv_quant, epilogue)
    except Exception:  # noqa: BLE001 — memoization is best-effort
        return None


class _Programs:
    """The engine's jitted device programs, factored OFF the engine so the
    process-wide memo pins only what tracing actually reads — the model
    config and two cache-geometry scalars — never a donor engine's full
    params, live KV pool, or adapter weights. Everything else (params,
    cache, the LoRA stack/pool, per-slot decode state) arrives as an
    argument, which is what makes the programs shareable across engines in
    the first place.

    The KV cache is CARRIED and CONSUMED: inside a program the layer scan
    carries the stacked leaves and each layer writes its tokens at its own
    index (models/llama.py, models/hybrid.py), and ``decode``,
    ``prefill_chunk``, ``insert``, ``insert_paged``, ``install_table`` and
    ``copy_block`` donate the cache they are given (``decode``, ``insert*``
    and ``activate`` the per-slot state arrays they return as well), so from
    the engine's handle down to a layer's scatter nothing copies a pool, a
    leaf or a layer of one. ``extract``, ``prefill`` and ``extend`` take no cache of the
    engine's or only read it.

    ``lora`` is ``None`` (base-only engine) or ``(tree, scales)`` with
    stacked ``[L, E, …]`` leaves; None-vs-tuple is pytree STRUCTURE, so jax
    compiles the two cases separately and, within the adapter case, per
    leaf shape — mutating pool contents in place (same shapes) hits the
    same executable."""

    def __init__(self, cfg, max_seq_len: int, kv_quant,
                 epilogue: str = "off"):
        self.cfg = cfg
        self.max_seq_len = max_seq_len
        self.kv_quant = kv_quant
        # resolved fused-sampling-epilogue impl ("off" | "kernel" | "xla");
        # "off" keeps the legacy argsort sampler — byte-identical programs
        self.epilogue = epilogue
        self.prefill = jax.jit(self._prefill_impl,
                               static_argnames=("prompt_len",))
        self.extend = jax.jit(self._extend_impl,
                              static_argnames=("suffix_len",))
        # a program that RETURNS the engine's cache (or its per-slot decode
        # state) CONSUMES the one it is given: the argument is donated, so
        # the pool is written in place, one copy of it is alive, and the
        # caller's handle is dead after the call (the engine rebinds
        # ``self._cache`` / the state arrays from the result at every call)
        self.insert = jax.jit(self._insert_impl,
                              donate_argnums=tuple(range(10)))
        self.insert_paged = jax.jit(self._insert_paged_impl,
                                    donate_argnums=tuple(range(10)))
        self.activate = jax.jit(self._activate_impl,
                                donate_argnums=tuple(range(9)))
        self.prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                     static_argnames=("chunk_len",),
                                     donate_argnums=(2,))
        # extract only reads: the slot's row is a copy, the cache stays
        self.extract = jax.jit(paged_extract_row,
                               static_argnames=("width",))
        self.copy_block = jax.jit(paged_copy_block, donate_argnums=(0,))
        self.install_table = jax.jit(paged_install_table, donate_argnums=(0,))
        self.decode = jax.jit(self._decode_impl,
                              static_argnames=("K", "mode"),
                              donate_argnums=(2, 3, 4, 5, 6, 7))

    def _prefill_impl(self, params, lora, tokens, mask, positions,
                      adapter_idx, *, prompt_len: int):
        cache = init_cache(self.cfg, 1, self.max_seq_len, dtype=jnp.bfloat16,
                           quantize=self.kv_quant)
        logits, cache = forward(
            params, tokens, self.cfg, positions=positions,
            attention_mask=mask, cache=cache, lora=lora,
            lora_adapter_idx=(adapter_idx[None]
                              if lora is not None else None),
            compute_dtype=jnp.bfloat16,
        )
        return logits[0, prompt_len - 1], cache

    def _extend_impl(self, params, lora, row_cache, tokens, mask, positions,
                     adapter_idx, *, suffix_len: int):
        """Append a (left-pad-bucketed) prompt suffix onto a cached prefix
        row: pads get sentinel rope positions so only the real tokens exist
        for attention, exactly as in full prefill."""
        logits, cache = forward(
            params, tokens, self.cfg, positions=positions,
            attention_mask=mask, cache=row_cache, lora=lora,
            lora_adapter_idx=(adapter_idx[None]
                              if lora is not None else None),
            compute_dtype=jnp.bfloat16,
        )
        return logits[0, suffix_len - 1], cache

    def _insert_impl(self, cache, logits_all, pos, remaining, active, temps,
                     top_ps, stops, adapter_idx, rng,
                     slot, row_cache, row_logits, plen, n_prompt, max_new,
                     temp, top_p, stop_row, adapter, seed):
        cache = dict(cache)
        # a dense row and a slot's recurrent state both lie at [:, slot]
        for key in kv_leaf_keys(cache) + state_leaf_keys(cache):
            cache[key] = jax.lax.dynamic_update_slice(
                cache[key], row_cache[key],
                (0, slot) + (0,) * (cache[key].ndim - 2))
        for key in ("moe_stats", "dsa_stats"):
            if key in cache and key in row_cache:
                # what the row's own prefill counted
                cache[key] = cache[key] + row_cache[key]
        cache["pos"] = jax.lax.dynamic_update_slice(
            cache["pos"], row_cache["pos"], (slot, 0))
        cache["len"] = cache["len"].at[slot].set(plen)
        return (
            cache,
            logits_all.at[slot].set(row_logits),
            pos.at[slot].set(n_prompt),
            remaining.at[slot].set(max_new),
            active.at[slot].set(True),
            temps.at[slot].set(temp),
            top_ps.at[slot].set(top_p),
            stops.at[slot].set(stop_row),
            adapter_idx.at[slot].set(adapter),
            rng.at[slot].set(jax.random.PRNGKey(seed)),
        )

    def _insert_paged_impl(self, cache, logits_all, pos, remaining, active,
                           temps, top_ps, stops, adapter_idx, rng,
                           slot, table_row, row_cache, row_logits, cursor,
                           n_prompt, max_new, temp, top_p, stop_row, adapter,
                           seed):
        """Paged twin of ``_insert_impl``: scatter a dense prefill/prefix row
        into the slot's allocated blocks (installing its block table) and arm
        the slot's decode state."""
        cache = paged_insert_row(cache, slot, table_row, row_cache)
        for key in ("moe_stats", "dsa_stats"):
            if key in cache and key in row_cache:
                # what the row's own prefill counted
                cache[key] = cache[key] + row_cache[key]
        cache["len"] = jax.lax.dynamic_update_slice(
            cache["len"], cursor[None], (slot,))
        return (
            cache,
            logits_all.at[slot].set(row_logits),
            pos.at[slot].set(n_prompt),
            remaining.at[slot].set(max_new),
            active.at[slot].set(True),
            temps.at[slot].set(temp),
            top_ps.at[slot].set(top_p),
            stops.at[slot].set(stop_row),
            adapter_idx.at[slot].set(adapter),
            rng.at[slot].set(jax.random.PRNGKey(seed)),
        )

    def _activate_impl(self, logits_all, pos, remaining, active, temps,
                       top_ps, stops, adapter_idx, rng,
                       slot, row_logits, n_prompt, max_new, temp, top_p,
                       stop_row, adapter, seed):
        """Arm a slot whose prompt was already chunk-prefilled in place (its
        KV lives in the slot's blocks; only the decode state needs setting)."""
        return (
            logits_all.at[slot].set(row_logits),
            pos.at[slot].set(n_prompt),
            remaining.at[slot].set(max_new),
            active.at[slot].set(True),
            temps.at[slot].set(temp),
            top_ps.at[slot].set(top_p),
            stops.at[slot].set(stop_row),
            adapter_idx.at[slot].set(adapter),
            rng.at[slot].set(jax.random.PRNGKey(seed)),
        )

    def _prefill_chunk_impl(self, params, lora, cache, slot, tokens, mask,
                            positions, adapter_idx, *, chunk_len: int):
        """One ``chunk_len``-token prefill program writing straight into one
        slot's blocks of the SHARED pool — the chunk-bounded generalisation of
        ``_prefill_impl``/``_extend_impl``. Returns the chunk's last-token
        logits (only the final chunk's are consumed) and the updated cache."""
        nbps = cache["block_tables"].shape[1]
        view = dict(cache)
        view["len"] = jax.lax.dynamic_slice(cache["len"], (slot,), (1,))
        view["block_tables"] = jax.lax.dynamic_slice(
            cache["block_tables"], (slot, 0), (1, nbps))
        # pools are shared and found through the slot's table; recurrent
        # state is the slot's own entry, sliced as ``len`` is
        for key in state_leaf_keys(cache):
            view[key] = state_slot(cache[key], slot)
        logits, new = forward(
            params, tokens, self.cfg, positions=positions,
            attention_mask=mask, cache=view, lora=lora,
            lora_adapter_idx=(adapter_idx[None]
                              if lora is not None else None),
            compute_dtype=jnp.bfloat16,
        )
        out = dict(cache)
        for key in kv_leaf_keys(out) + [
                key for key in ("moe_stats", "dsa_stats") if key in out]:
            out[key] = new[key]
        for key in state_leaf_keys(out):
            out[key] = state_insert(cache[key], slot, new[key])
        out["pos"] = new["pos"]
        out["len"] = jax.lax.dynamic_update_slice(
            cache["len"], new["len"], (slot,))
        return logits[0, chunk_len - 1], out

    def _decode_impl(self, params, lora, cache, logits, pos, remaining,
                     active, rng, temps, top_ps, stops, adapter_idx, *,
                     K: int, mode: str = "off"):
        """``mode`` is the engine's static per-batch sampling mode when the
        fused epilogue is on ("greedy" | "simple" | "topp"), or the
        ``"off"`` sentinel — ONE compiled variant running the legacy
        argsort sampler, byte-identical to the pre-epilogue program."""
        def step(carry, _):
            logits, cache, pos, remaining, active, rng = carry
            with jax.named_scope("dtx.sample"):
                if mode == "off" or self.epilogue == "off":
                    split = jax.vmap(jax.random.split)(rng)
                    rng, sub = split[:, 0], split[:, 1]
                    nxt = jax.vmap(_sample_jit)(logits, temps, top_ps, sub)
                else:
                    nxt, rng = sample_rows(logits, temps, top_ps, rng,
                                           mode=mode, impl=self.epilogue)
            is_stop = jnp.any(nxt[:, None] == stops, axis=1)
            emit = active & ~is_stop & (remaining > 0)
            emitted = jnp.where(emit, nxt, -1)
            new_active = emit & (remaining > 1)
            remaining = remaining - emit.astype(jnp.int32)

            prev_len = cache["len"]
            tok = jnp.where(emit, nxt, 0)[:, None]
            logits2, cache = forward(
                params, tok, self.cfg, positions=pos[:, None],
                attention_mask=emit[:, None].astype(jnp.int32), cache=cache,
                lora=lora,
                lora_adapter_idx=(adapter_idx
                                  if lora is not None else None),
                compute_dtype=jnp.bfloat16,
            )
            # forward advances every cursor; only emitting slots really moved
            cache = dict(cache)
            cache["len"] = prev_len + emit.astype(jnp.int32)
            pos = pos + emit.astype(jnp.int32)
            return (logits2[:, -1], cache, pos, remaining, new_active, rng), emitted

        (logits, cache, pos, remaining, active, rng), emitted = jax.lax.scan(
            step, (logits, cache, pos, remaining, active, rng), None, length=K
        )
        return emitted, logits, cache, pos, remaining, active, rng


class BatchedEngine:
    def __init__(
        self,
        model_path: str,
        checkpoint_path: Optional[str] = None,
        adapters: Optional[Dict[str, str]] = None,  # name -> checkpoint path
        adapter_pool: int = 0,  # >0: dynamic pooled-adapter mode (P slots)
        adapter_rank_max: int = 8,  # pool rank ceiling (ranks < are padded)
        adapter_targets: Optional[Sequence[str]] = None,  # pool target set
        template: str = "llama2",
        max_seq_len: int = 1024,
        slots: int = 4,
        decode_chunk: int = 8,
        dtype=jnp.bfloat16,
        kv_quant: Optional[str] = None,  # "int8" halves cache HBM
        prefix_cache: int = 0,  # LRU entries of reusable prefilled prefixes
        kv_block_size: int = 0,  # >0: paged block-pool cache (elastic HBM)
        kv_blocks: Optional[int] = None,  # pool size; default = dense parity
        kv_overcommit: str = "off",  # on: lazy block growth + COW + preempt
        # for tests and the parity oracle; deployments get "auto" (the
        # kernel on a TPU backend, the XLA gather elsewhere): auto|on|off
        paged_kernel: str = "auto",
        spec_draft: Optional[str] = None,  # draft model: path|preset:|take:N
        spec_k: int = 4,  # proposals per verify step (adaptive ceiling)
        spec_mode: str = "auto",  # auto (adaptive) | on (pinned) | off
        spec_tree: Optional[str] = None,  # "WxD" tree drafts (None = chain)
        # for tests and the parity oracle; deployments get "auto" (fused
        # on-chip sampling on a TPU backend, the host sampler elsewhere)
        sampling_epilogue: str = "auto",
        prefill_chunk: int = 256,  # chunked-prefill program length (paged)
        prefill_token_budget: int = 0,  # prefill tokens per tick (0 = all)
        registry: Optional[Registry] = None,  # shared /metrics registry
        tracing: bool = True,  # per-request span timelines + trace ring
        trace_ring: int = 256,  # completed traces kept for /debug/trace
        trace_log_path: Optional[str] = None,  # optional JSONL span log
        tenants=None,  # TenantDirectory / dict / path / inline JSON
        host_adapter_cache_mb: float = 0.0,  # host-RAM adapter tier budget
    ):
        # serving is single-program: clear any mesh a Trainer left in the
        # process-global flash context before the engine's jits first trace
        from datatunerx_tpu.ops.flash_attention import set_flash_context

        set_flash_context(None)
        self.cfg, self.params, self.tokenizer = load_model_and_tokenizer(
            model_path, dtype=dtype
        )
        self.template: Template = get_template(template, self.tokenizer)
        self.max_seq_len = min(max_seq_len, self.cfg.max_seq_len)
        self.slots = slots
        self.chunk = max(1, decode_chunk)
        if self.cfg.hybrid:
            # layers of several kinds (models/hybrid.py): the draft/verify
            # programs carve windows of their own; a prefix-cache extension
            # puts a row's pads mid-row, which only kinds that mask by
            # position over the whole table read rightly
            if spec_draft:
                raise NotImplementedError(
                    f"model {self.cfg.name!r} has layers of several "
                    f"kinds: --spec_draft does not handle it yet")
            if prefix_cache > 0:
                self._refuse_hybrid_prefix_cache(kv_block_size, kv_overcommit)
        # a prefix hit, a rejected draft, a preempted or a migrating session
        # all restart a slot at a cursor it has passed: rows are trimmed
        # there, a layer's recurrent state cannot be
        from datatunerx_tpu.models.config import refuse_recurrent_state

        for flag, on in (("prefix_cache", prefix_cache > 0),
                         ("spec_draft", bool(spec_draft)),
                         ("kv_overcommit", str(kv_overcommit).lower() == "on")):
            if on:
                refuse_recurrent_state(self.cfg, f"--{flag}")

        # ---- adapters: checkpoint_path becomes adapter "default" (unmerged);
        # full-param checkpoints swap the base instead
        named: Dict[str, str] = dict(adapters or {})
        if checkpoint_path:
            state = load_checkpoint_state(checkpoint_path)
            if state.get("lora"):
                named.setdefault("default", checkpoint_path)
            elif state.get("params"):
                self.params = jax.device_put(state["params"])
        self._static_adapter_ids: Dict[str, int] = {"": 0}  # 0 = base
        self.lora_stack: Optional[tuple] = None
        # multi-tenant QoS plane (datatunerx_tpu/tenancy/): tenant → tier /
        # adapter set / share / KV quota. None (the default) keeps every
        # path below — eviction order, preemption order, /metrics bytes —
        # identical to a tenancy-less build (the PR 15/16 gating pattern).
        from datatunerx_tpu.tenancy import load_tenants

        self.tenants = load_tenants(tenants)
        self.host_adapter_cache_mb = float(host_adapter_cache_mb or 0.0)
        # per-tenant usage counters (dtx_serving_tenant_*); capped like
        # adapter_requests so tenant churn can't grow the exposition
        self._tenant_lock = threading.Lock()
        self.tenant_stats: Dict[str, Dict[str, int]] = {}
        self._tenant_stats_cap = 1024
        # dynamic pooled mode (adapter_pool > 0): adapters are DATA — a
        # fixed-geometry device pool + host registry with load-on-miss /
        # LRU eviction / refcount pinning (datatunerx_tpu/adapters/).
        # Constructor adapters are registered lazily; the first request (or
        # an /admin/adapters preload) materialises them into pool slots.
        self.adapter_registry = None
        self.adapter_store = None
        if adapter_pool > 0:
            from datatunerx_tpu.adapters import AdapterRegistry, AdapterStore
            from datatunerx_tpu.models.lora import DEFAULT_TARGETS

            self.adapter_store = AdapterStore(
                self.cfg, pool_slots=int(adapter_pool),
                rank_max=int(adapter_rank_max) or 8,
                targets=tuple(adapter_targets or DEFAULT_TARGETS))
            host_tier = None
            if self.host_adapter_cache_mb > 0:
                from datatunerx_tpu.tenancy import HostAdapterTier

                host_tier = HostAdapterTier(
                    int(self.host_adapter_cache_mb * 1024 * 1024))
            self.adapter_registry = AdapterRegistry(
                self.adapter_store,
                # lazy closures: both attributes exist before any load runs
                load_observer=lambda ms: self._h_adapter_load.observe(ms),
                # an async load resolving wakes the scheduler so the
                # FIFO-head admits immediately instead of on the next poll
                on_load_done=lambda: self._wake.set(),
                host_tier=host_tier)
            for aname, path in named.items():
                self.adapter_registry.register(aname, path)
            if self.tenants is not None:
                self.adapter_registry.set_pinned(
                    self.tenants.pinned_adapters())
        elif named:
            self._build_adapter_stack(named)
        # per-adapter request counters (dtx_serving_adapter_requests_total).
        # Capped, and pruned on unload: every key becomes a Prometheus
        # series, and tenant churn over weeks must not grow the exposition
        # without bound (names here passed submit's membership check, but
        # the registered population itself churns unboundedly).
        self._adapter_req_lock = threading.Lock()
        self.adapter_requests: Dict[str, int] = {}
        self._adapter_requests_cap = 1024

        self.kv_quant = kv_quant or None
        self.paged = kv_block_size > 0
        self.block_size = int(kv_block_size)
        # KV overcommit plane: admission reserves only the prompt's blocks
        # plus one tick's growth headroom, the scheduler appends blocks at
        # each slot's cursor as decode advances, prefix-cache hits map
        # SHARED refcounted blocks (copy-on-write tail), and exhaustion
        # preempts youngest-first (sessions park host-side as dtx-kv-session
        # payloads and resume token-exactly when blocks free). "off" is
        # byte-identical to the eager-reserve engine.
        oc_mode = (kv_overcommit if isinstance(kv_overcommit, str)
                   else ("on" if kv_overcommit else "off"))
        oc_mode = (oc_mode or "off").strip().lower()
        if oc_mode not in ("on", "off"):
            raise ValueError(
                f"kv_overcommit must be on|off, got {kv_overcommit!r}")
        if oc_mode == "on" and not self.paged:
            raise ValueError(
                "--kv_overcommit on requires the paged KV cache "
                "(--kv_block_size > 0)")
        self.overcommit = self.paged and oc_mode == "on"
        # Pallas in-place decode kernel (ops/pallas_paged_attention.py):
        # "auto" engages it on a real TPU backend and keeps the XLA gather
        # elsewhere (interpret-mode emulation would only slow CPU smoke
        # runs); "on" forces it anywhere — CPU tests/bench run the kernel
        # through the interpret gate — and "off" pins the gather oracle.
        # The resolved bool rides the model config so the jitted programs
        # (and the process-wide program memo key) see it.
        mode = paged_kernel if isinstance(paged_kernel, str) else \
            ("on" if paged_kernel else "off")
        mode = (mode or "auto").strip().lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"paged_kernel must be auto|on|off, got {paged_kernel!r}")
        if mode == "on" and not self.paged:
            raise ValueError(
                "--paged_kernel on requires the paged KV cache "
                "(--kv_block_size > 0)")
        self.paged_kernel = self.paged and (
            mode == "on"
            or (mode == "auto" and jax.default_backend() == "tpu"))
        if self.paged_kernel:
            import dataclasses

            self.cfg = dataclasses.replace(self.cfg, paged_kernel=True)
        # Fused on-chip sampling epilogue (ops/pallas_sampling.py): the
        # jitted decode/spec programs sample inside the traced computation
        # (greedy / temperature / exact-top-p as STATIC per-batch modes)
        # instead of handing each step's [S, vocab] logits to the legacy
        # argsort sampler. "auto" engages it on a real TPU backend only —
        # mirroring paged_kernel — "on" forces it anywhere (non-TPU runs
        # use the XLA tile-walk oracle: same math, same tokens), "off"
        # pins the legacy sampler with traced programs byte-identical to a
        # pre-epilogue build. The resolved impl keys the program memo.
        emode = (sampling_epilogue if isinstance(sampling_epilogue, str)
                 else ("on" if sampling_epilogue else "off"))
        emode = (emode or "auto").strip().lower()
        if emode not in ("auto", "on", "off"):
            raise ValueError(
                "sampling_epilogue must be auto|on|off, "
                f"got {sampling_epilogue!r}")
        self.sampling_epilogue = "on" if (
            emode == "on"
            or (emode == "auto" and jax.default_backend() == "tpu")
        ) else "off"
        self._epilogue_impl = (sampling_default_impl()
                               if self.sampling_epilogue == "on" else "off")
        # fused-path observability (dtx_serving_sampling_*): decode ticks
        # that ran a fused-epilogue program vs the legacy sampler; written
        # by the scheduler thread only, like spec_stats
        self.sampling_stats = {"fused_steps": 0, "legacy_steps": 0}
        # what the expert layers counted (dtx_serving_moe_*), decode steps
        # and prefill steps apart: rows routed to the experts held here, held
        # experts that got a row, most rows on one expert, expert-layer steps.
        # The programs accumulate them on the device (cache["moe_stats"],
        # wrapping); _note_counters adds up differences at the decode tick's
        # sync. dsa_stats (dtx_serving_dsa_*) likewise, of a model whose
        # queries select the cached tokens they read: steps, live rows, the
        # sum of their contexts, the sum of the tokens they selected.
        self.moe_stats = {f"{phase}_{name}": 0
                          for phase in ("decode", "prefill")
                          for name in MOE_STAT_NAMES}
        self.dsa_stats = {f"{phase}_{name}": 0
                          for phase in ("decode", "prefill")
                          for name in DSA_STAT_NAMES}
        # and, counted here at dispatch, the lanes the prefill chunks of a model
        # with a latent kind viewed (as far as the slot's context reached, in
        # whole steps) beside the lanes of the table they would have viewed
        self.dsa_stats.update(prefill_view_lanes=0, prefill_table_lanes=0)
        self._counters_seen = {}  # device counters at the last read, by leaf
        self._slot_cursor = None  # each slot's linear cursor then
        # tokens handed to finished requests (dtx_serving_generated_tokens_
        # total): added once per request in _complete, never per token
        self.generated_tokens = 0
        # ---- speculative decoding (serving/speculative.py): a draft model
        # proposes k tokens, one verify-k target forward accepts a prefix.
        # No draft configured → every spec structure stays None and the
        # scheduler takes the exact pre-spec decode path (--spec_mode off
        # is byte-identical to not having the feature).
        smode = (spec_mode or "auto").strip().lower()
        if smode not in ("auto", "on", "off"):
            raise ValueError(f"spec_mode must be auto|on|off, got {spec_mode!r}")
        if smode == "on" and not spec_draft:
            raise ValueError("--spec_mode on requires --spec_draft_config")
        self.spec_mode = smode
        self.spec_k = max(1, int(spec_k))
        self.spec = None
        self.spec_tree = None
        if spec_tree and smode != "off":
            from datatunerx_tpu.serving import speculative as spec_mod

            if not spec_draft:
                raise ValueError("--spec_tree requires --spec_draft_config")
            self.spec_tree = spec_mod.parse_spec_tree(spec_tree)
            if self.spec_tree.step_tokens >= self.max_seq_len:
                raise ValueError(
                    f"spec_tree {self.spec_tree} writes "
                    f"{self.spec_tree.step_tokens} tokens per step — does "
                    f"not fit max_seq_len {self.max_seq_len}")
        # one verify step writes up to step-token-count tokens past a row's
        # cursor (chain: pending + k proposals; tree: pending + W*D nodes);
        # paged admission reserves that overshoot so every verify write
        # stays physical (ops.paged_attention.blocks_for_depth caps at the
        # table width). Sizing it from the ACTUAL per-step token count —
        # not a chain-shaped spec_k+1 — is what keeps tree mode from
        # under-reserving blocks. 0 when spec is off — reserve math
        # byte-identical to today.
        self._spec_step_tokens = (self.spec_tree.step_tokens
                                  if self.spec_tree else self.spec_k + 1)
        self._spec_overshoot = (self._spec_step_tokens
                                if spec_draft and smode != "off" else 0)
        # the most cache lanes one scheduler tick can consume per slot (a
        # plain decode chunk, or a verify step — chain or tree): with the
        # overshoot, what the overcommit grower keeps ahead of every cursor
        self._tick_advance = (max(self.chunk, self._spec_step_tokens)
                              if self._spec_overshoot else self.chunk)
        self._pool: Optional[KVPool] = None
        if self.paged:
            self._pool = KVPool(
                slots, self.max_seq_len, self.block_size, kv_blocks,
                overshoot=self._spec_overshoot,
                advance=self._tick_advance if self.overcommit else None,
                # weakly: a cycle would keep a dropped engine's cache in HBM
                # until the collector runs
                cache=functools.partial(getattr, weakref.proxy(self), "_cache"))
            self._cache = init_paged_cache(
                self.cfg, slots, self._pool.total, self.block_size,
                self._pool.blocks_per_slot, dtype=jnp.bfloat16,
                quantize=self.kv_quant)
        else:
            self._cache = init_cache(self.cfg, slots, self.max_seq_len,
                                     dtype=jnp.bfloat16, per_slot=True,
                                     quantize=self.kv_quant)
        # chunked prefill runs in bucket-multiple programs so the compile
        # count stays bounded (chunk lengths ∈ multiples of DECODE_BUCKET)
        self.prefill_chunk = max(
            DECODE_BUCKET, -(-int(prefill_chunk) // DECODE_BUCKET) * DECODE_BUCKET)
        # which grouped matmul the expert layers of the two serving programs
        # run and at what row tile (dtx_serving_moe_row_tile): static per
        # program, from shapes alone (prefill: a whole chunk's; a prompt's
        # shorter last chunk may get a smaller tile); empty without experts
        self.moe_kernel = {}
        if "experts" in (self.cfg.ffn_types or ()):
            from datatunerx_tpu.ops.moe import grouped_matmul

            self.moe_kernel = {
                phase: grouped_matmul(
                    rows, top_k=self.cfg.experts_per_token,
                    experts_total=self.cfg.experts_total, d=self.cfg.hidden_size,
                    f=self.cfg.expert_intermediate_size)
                for phase, rows in (("decode", slots), ("prefill", self.prefill_chunk))}
        # what steps the state-space layers' state a decode token
        # (dtx_serving_state_head_tile): the Pallas kernel at its head tile
        # where the leaf's shapes give it tiles, else ops/ssm.py's XLA step;
        # static per program, from shapes alone; empty without such layers
        self.state_kernel = {}
        if "state_ssm" in self._cache:
            from datatunerx_tpu.ops.pallas_ssm import step_kernel

            self.state_kernel = {"decode": step_kernel(self._cache["state_ssm"], 1)}
        # the budget is a HARD bound (prefill chunks are clamped to the
        # remaining budget each tick), so round it up to the bucket quantum —
        # a sub-bucket budget could never admit a chunk and would starve
        # prefill outright
        budget = max(0, int(prefill_token_budget))
        self.prefill_token_budget = (
            -(-budget // DECODE_BUCKET) * DECODE_BUCKET if budget else 0)
        V = self.cfg.vocab_size
        self._logits = jnp.zeros((slots, V), jnp.float32)
        self._pos = jnp.zeros((slots,), jnp.int32)
        self._remaining = jnp.zeros((slots,), jnp.int32)
        self._active = jnp.zeros((slots,), bool)
        self._rng = jnp.stack([jax.random.PRNGKey(i) for i in range(slots)])
        self._temps = jnp.zeros((slots,), jnp.float32)
        self._top_ps = jnp.ones((slots,), jnp.float32)
        self._stops = jnp.full((slots, MAX_STOP), -1, jnp.int32)
        self._adapter_idx = jnp.zeros((slots,), jnp.int32)

        if spec_draft and smode != "off":
            from datatunerx_tpu.serving import speculative as spec_mod

            dcfg, dparams = spec_mod.build_draft(spec_draft, self.cfg,
                                                 self.params)
            self.spec = {
                "draft": spec_draft,
                "dcfg": dcfg,
                "dparams": dparams,
                # compact per-slot dense cache for the draft — rides the
                # same ops/attention.py cache interface as the target's
                "dcache": init_cache(dcfg, slots, self.max_seq_len,
                                     dtype=jnp.bfloat16, per_slot=True),
                "programs": spec_mod.spec_programs(
                    self.cfg, dcfg, self.max_seq_len, self.kv_quant,
                    epilogue=self._epilogue_impl),
            }
            # learned tree shapes (AdaptiveTree): per-depth width selection
            # from acceptance EMAs + draft-side early exit on a decisive
            # root margin; chain drafts keep the adaptive-k controller
            ctrl_cls = (spec_mod.AdaptiveTree if self.spec_tree is not None
                        else spec_mod.AdaptiveK)
            self.spec_ctrl = ctrl_cls(self.spec_k, mode=smode,
                                      tree=self.spec_tree)
            self._spec_pending = jnp.zeros((slots,), jnp.int32)
            self._spec_form = [False] * slots   # slot is in pending form
            self._spec_primed = [False] * slots  # draft row holds the context
            # counters behind dtx_serving_spec_{proposed,accepted}_total and
            # the step-mix; written by the scheduler thread only
            self.spec_stats = {"proposed": 0, "accepted": 0,
                               "row_steps": 0,  # per-row verify events
                               "spec_steps": 0, "plain_steps": 0,
                               "tree_steps": 0}
            # per-adapter acceptance EMA ('' = base) for /metrics + routing
            self._spec_adapter_ema: Dict[str, float] = {}
            # per-slot accepted-path-length EMA (tree mode): pruned on
            # release like the slot acceptance EMAs, capped on export
            self._spec_tree_slot_path: Dict[int, float] = {}
            self._h_accept_len = None  # bound after the registry exists

        # ---- overcommit scheduler state: preempted sessions, parked
        # host-side as dtx-kv-session payloads (raw-numpy bodies — no b64 for
        # in-process parking), oldest first; owned by the scheduler thread
        self._preempted: List[dict] = []
        # dtx_serving_preemptions_total{outcome} source (scheduler-only
        # writes; scraped racily like every other stats dict)
        self.preempt_stats: Dict[str, int] = {}
        # capacity observability: the high-water mark of concurrently
        # admitted sessions and the pool's record of finished sessions' blocks
        self.kv_stats = {"peak_sessions": 0,
                         "session_blocks": self._pool.session_blocks
                         if self._pool else collections.deque()}
        # chat-encode LRU (see _encode_chat): HTTP threads share it
        self._encode_memo: "collections.OrderedDict[str, tuple]" = \
            collections.OrderedDict()
        self._encode_memo_lock = threading.Lock()
        self._slot_req: List[Optional[Request]] = [None] * slots
        # dynamic mode: the adapter NAME each slot pins (released with the
        # slot, so LRU eviction can never pull weights out from under an
        # in-flight decode)
        self._slot_adapter: List[Optional[str]] = [None] * slots
        self._decode_ready: List[bool] = [False] * slots
        # slot → in-progress chunked-prefill state, in admission order
        self._pending: "collections.OrderedDict[int, dict]" = \
            collections.OrderedDict()
        self._waiting: "queue.Queue[Request]" = queue.Queue()
        # requests that must admit BEFORE anything in _waiting (FIFO order
        # preserved): the block-starved head, and adapter-loading requests
        # parked while their checkpoint reads run on loader threads
        self._waiting_front: "collections.deque[Request]" = collections.deque()
        self._last_adapter_wait: Optional[str] = None  # wait-trace dedupe
        self._admit_wait_reason = ""  # why the last _admit returned False
        # the scheduler's pass counter (dtx_engine_tick's ``tick=``, every
        # Request.mark's ``tick``) and the last pass that ended on a head
        # the FIFO order holds everyone behind, with what the head lacked
        self._tick_no = 0
        self._blocked_tick = 0
        self._blocked_cause = "blocks"
        # always-on phase table, {span name: [host seconds, count]}; written
        # by the scheduler thread alone (see _Phase)
        self.sched_stats: Dict[str, list] = {}
        self._wake = threading.Event()
        self._shutdown = threading.Event()
        # KV migration fabric (serving/migration.py): export/import commands
        # from admin HTTP threads, serviced by the scheduler between decode
        # chunks — the scheduler owns every piece of slot state, so commands
        # queue to it instead of locking it. Imports facing a transient
        # shortage (free slot, KV blocks, adapter mid-load) park in
        # _mig_retry and re-run next tick until their deadline.
        self._mig_q: "queue.Queue[dict]" = queue.Queue()
        self._mig_retry: List[dict] = []
        # outcome counters behind dtx_serving_session_{export,import}_total
        self.session_stats: Dict[str, Dict[str, int]] = {
            "export": {}, "import": {}}
        # scheduler-tick trace, for tests and TTFT/TPOT forensics:
        # ("admit", slot, plen, mode) / ("prefill", slot, ntokens) /
        # ("activate", slot) / ("decode", K) / ("finish", slot); fed by
        # _event, the one recording call, beside Request.timeline
        self.sched_trace: "collections.deque[tuple]" = \
            collections.deque(maxlen=4096)

        # Process-wide program memo (the Trainer step-memo pattern,
        # training/train_lib.py): engines built from an equal (model config,
        # max_seq_len, kv_quant) trace identical programs — everything else
        # the jitted fns touch arrives as an argument, and dense/paged/
        # slot-count/ADAPTER variation lives in argument shapes/structure
        # jax already keys on — so they share one _Programs holder and with
        # it jax's in-memory executable cache. Side-by-side paged/dense
        # engines (parity tests, the serve bench's paged-vs-dense runs,
        # blue/green replica swaps in one process) compile each program once
        # instead of once per engine.
        # Adapters no longer enter the key at all: the stacked tree / pool
        # is a program ARGUMENT, so engines with any adapter mapping share
        # programs, and the dynamic pool serves load/unload with ZERO
        # recompiles (the geometry fixes every leaf shape up front).
        key = _program_memo_key(self.cfg, self.max_seq_len, self.kv_quant,
                                self._epilogue_impl)
        progs = None if key is None else _PROGRAM_MEMO.get(key)
        if progs is None:
            progs = _Programs(self.cfg, self.max_seq_len, self.kv_quant,
                              self._epilogue_impl)
            if key is not None:
                _PROGRAM_MEMO[key] = progs
                while len(_PROGRAM_MEMO) > _PROGRAM_MEMO_MAX:
                    _PROGRAM_MEMO.popitem(last=False)
        else:
            _PROGRAM_MEMO.move_to_end(key)
        self._prefill = progs.prefill
        self._extend = progs.extend
        self._insert = progs.insert
        self._insert_paged = progs.insert_paged
        self._activate = progs.activate
        self._prefill_chunk_fn = progs.prefill_chunk
        self._extract = progs.extract
        self._copy_block = progs.copy_block
        self._install_table = progs.install_table
        self._decode = progs.decode

        self._prefix = _PrefixCache(
            prefix_cache, on_evict=self._free_prefix_entry
        ) if prefix_cache > 0 else None
        # COW prefix blocks: overcommit engines with a prefix cache store
        # refcounted BLOCK entries — hits map shared physical blocks into
        # the new slot's table instead of the dense-row copy + re-insert
        self.cow = self.overcommit and self._prefix is not None
        # observability: how admissions were served (tests + /metrics)
        self.prefill_stats = {"full": 0, "reuse": 0, "extend": 0}
        # what the prefix cache gave admissions (dtx_serving_prefix_*_total):
        # prompt tokens mapped from shared blocks or a cached row against
        # prompt tokens prefilled; ``prefix_stats`` adds the evictions
        self._prefix_stats = {"hits": 0, "extensions": 0, "cold": 0,
                              "shared_tokens": 0, "prefilled_tokens": 0,
                              "blocks_reclaimed_at_admission": 0}
        # Shared-registry latency histograms. Recording is BUFFERED off the
        # hot path: token stamps are plain attribute writes in Request.push;
        # the observes below fire once per completed request (TTFT/TPOT) —
        # never per token.
        self.registry = registry or Registry()
        self._h_ttft, self._h_tpot = serving_latency_histograms(self.registry)
        self._h_adapter_load = adapter_load_histogram(self.registry)
        if self.spec is not None:
            from datatunerx_tpu.obs.metrics import spec_accept_len_histogram

            self._h_accept_len = spec_accept_len_histogram(self.registry)
        # Per-request span timelines (the PR 5 sched_trace deque, promoted):
        # completed requests land in a bounded trace ring keyed by trace id,
        # served by GET /debug/trace/<id> on the serving server and merged
        # into the gateway's trace view.
        self.tracing = tracing
        self.trace_store = TraceStore(capacity=trace_ring,
                                      jsonl_path=trace_log_path)

        # the choices "auto" resolved to, once, where a caller outside the
        # process can read them (chip_smoke.py asserts on this line)
        self.engine_line = {
            "decode_path": self.decode_path,
            "decode_paths": self.decode_paths,
            "decode_window": self.decode_window,
            "sampling_epilogue": self.sampling_epilogue,
            "epilogue_impl": self._epilogue_impl,
            "pallas_interpret": interpret_default(),
            "slots": slots,
            "kv_block_size": self.block_size,
            "prefill_chunk": self.prefill_chunk,
            "moe_kernel": self.moe_kernel,
            "state_kernel": self.state_kernel,
            "state_bytes": self.state_bytes(),
            "index_topk": self.cfg.index_topk,
            "index_pool_bytes": self.index_pool_bytes(),
            # lanes a latent kind's prefill chunk adds to its view a step of
            # its context (0: every chunk views its whole table)
            "prefill_view_step": self.prefill_view_step(),
        }
        print("[engine] " + json.dumps(self.engine_line, sort_keys=True),
              file=sys.stderr, flush=True)
        if self.decode_path == "pallas" and self.cfg.sliding_window:
            window, width = self.cfg.sliding_window, self.max_seq_len
            print(f"[engine] sliding_window={window} "
                  + (f">= cache width {width}: dropped"
                     if self.decode_window is None else
                     f"< cache width {width}: the decode kernel walks a "
                     "slot's table from the window's first trip"),
                  file=sys.stderr, flush=True)

        self._thread = threading.Thread(target=self._scheduler, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ block pool
    @property
    def decode_paths(self) -> dict:
        """{attending kind: how its token step reads the KV cache}:
        ``pallas`` (in-place block-table kernel), ``gather`` (paged XLA
        oracle) or ``dense``. The path forward() takes, not the flag: of a
        model of several layer kinds, the kinds models/hybrid.py hands to the
        kernel (a windowed model's prefill chunks and verify columns read a
        gathered view too; its token step does not)."""
        from datatunerx_tpu.models.config import mixer_kinds
        from datatunerx_tpu.models.hybrid import in_place_kinds

        kinds = [name for name, kind in mixer_kinds(self.cfg).items()
                 if kind.pools()]
        if not self.paged:
            return dict.fromkeys(kinds, "dense")
        if self.cfg.hybrid:  # shapes only: a donated leaf still has its shape
            in_place = in_place_kinds(self.cfg, self._cache, 1)
        else:
            in_place = kinds if self.paged_kernel else ()
        return {name: "pallas" if name in in_place else "gather"
                for name in kinds}

    @property
    def decode_path(self) -> str:
        """``decode_paths`` in a word: ``pallas``, ``gather`` or ``dense``,
        or ``gather+pallas`` where a model's kinds differ."""
        return "+".join(sorted(set(self.decode_paths.values())))

    @property
    def decode_window(self) -> Optional[int]:
        """The sliding window the decode kernel was built with, in lanes;
        None where it has none: no kernel, no window, or one the cache (as
        wide as ``max_seq_len``) cannot exceed, which the kernel drops."""
        window = self.cfg.sliding_window
        if self.decode_path != "pallas" or not window:
            return None
        return window if window < self.max_seq_len else None

    # the pool's gauges; None on a dense engine (no block signal)
    @property
    def total_kv_blocks(self) -> Optional[int]:
        return self._pool.total if self._pool else None

    @property
    def free_kv_blocks(self) -> Optional[int]:
        return self._pool.free if self._pool else None

    @property
    def kv_blocks_reserved(self) -> Optional[int]:
        return self._pool.total - self._pool.free if self._pool else None

    @property
    def kv_overcommit_ratio(self) -> Optional[float]:
        return self._pool.overcommit_ratio if self._pool else None

    @property
    def parked_sessions(self) -> int:
        """Preemption-parked sessions awaiting local resume — what the
        fleet spill coordinator polls (via /stats) to find re-homing
        candidates. Host-side list length; safe from any thread."""
        return len(self._preempted)

    def _note_counters(self):
        """Add up what the expert layers and the selecting steps counted since
        the last read, and keep every slot's linear cursor: a few small arrays
        cross to the host at the decode tick's designed sync point."""
        tables = {"moe_stats": (self.moe_stats, MOE_STAT_NAMES),
                  "dsa_stats": (self.dsa_stats, DSA_STAT_NAMES)}
        keys = [key for key in tables if key in self._cache]
        *read, lens = jax.device_get(  # dtxlint: disable=DTX001
            [self._cache[key] for key in keys] + [self._cache["len"]])
        for key, stats in zip(keys, read):
            totals, names = tables[key]
            stats = stats.astype(np.int64)
            # the device's int32 wraps
            delta = (stats - self._counters_seen.get(key, 0)) % (1 << 32)
            for phase, row in zip(("decode", "prefill"), delta):
                for name, v in zip(names, row):
                    totals[f"{phase}_{name}"] += int(v)  # dtxlint: disable=DTX001 — host numpy
            self._counters_seen[key] = stats
        self._slot_cursor = lens

    def index_pool_bytes(self) -> int:
        """Bytes of the index-key pool (``k_idx``): what the indexer of a
        model that selects its cached tokens keeps beside the latent rows."""
        leaf = self._cache.get("k_idx")  # shape only: a donated leaf still has it
        return 0 if leaf is None else math.prod(leaf.shape) * leaf.dtype.itemsize

    def prefill_view_step(self) -> int:
        """Lanes of one step of a prefill chunk's view of a latent kind's
        pool (``ops/mla.py:view_steps``: the kind's ``index_topk`` where it
        selects, the module's constant where it does not), 0 where a chunk
        views its whole table."""
        if "k_mla" not in self._cache or not self.paged:
            return 0
        steps = mla.view_steps(self.prefill_chunk, self._cache["block_tables"].shape[1],
                               self.block_size, self.cfg.index_topk)
        return steps[0] * self.block_size if steps else 0

    def _dsa_marks(self) -> dict:
        """Keywords of the decode span of a model that selects: the running
        sums of its decode rows' contexts and of the tokens they selected, so
        that a profiler trace carries the counters (two spans' difference is
        what the steps between them did)."""
        if "dsa_stats" not in self._cache:
            return {}
        return {"dsa_context": self.dsa_stats["decode_context"],
                "dsa_selected": self.dsa_stats["decode_selected"]}

    def _dsa_chunk_marks(self, cursor: int, tokens: int) -> dict:
        """Keywords of the chunk span of a model whose chunks take a stepped
        view (``prefill_view_step``), counted as the chunk is dispatched: the
        lanes its program views at the slot's LANE cursor ``cursor``
        (``ops/mla.py:view_lanes``, the program's own rule: under the prefix
        cache a suffix starts at its shared base, pads included) and the
        lanes of the slot's table."""
        if not self.prefill_view_step():
            return {}
        columns = self._cache["block_tables"].shape[1]
        view = mla.view_lanes(cursor, tokens, self.cfg.index_topk,
                              self.block_size, columns)
        self.dsa_stats["prefill_view_lanes"] += view
        self.dsa_stats["prefill_table_lanes"] += columns * self.block_size
        return {"view": view, "table": columns * self.block_size}

    def state_bytes(self) -> int:
        """Bytes of recurrent state the cache holds (``state_*`` leaves): what
        the linear-attention and state-space layers keep per slot, resident
        whether a slot is live or idle."""
        cache = self._cache  # shapes only: a donated leaf still has its shape
        return sum(math.prod(cache[key].shape) * cache[key].dtype.itemsize
                   for key in state_leaf_keys(cache))

    def kv_window_stats(self) -> Optional[dict]:
        """Bytes of the window layers' pool that live slots hold
        (``live_bytes``) and the part of them in blocks that lie wholly behind
        every later query's window (``behind_bytes``): blocks stay allocated
        until their request ends. None where no layer has a window, the
        cache is not paged, or no decode tick has read the cursors yet."""
        from datatunerx_tpu.models.config import kind_layers, mixer_kinds

        kind = mixer_kinds(self.cfg).get("window")
        if (kind is None or not self.paged or self._slot_cursor is None
                or "k_window" not in self._cache):
            return None
        per_block = (kind_layers(self.cfg)["window"] * self.block_size
                     * kind.num_kv_heads * (kind.head_dim + kind.v_head_dim)
                     * self._cache["k_window"].dtype.itemsize)
        live = behind = 0
        for slot in range(self.slots):
            if self._slot_req[slot] is None:
                continue
            held = len(self._pool.held(slot))
            cursor = int(self._slot_cursor[slot])  # dtxlint: disable=DTX001 — host numpy
            live += held
            behind += min(held, max(0, cursor - kind.window + 1)
                          // self.block_size)
        return {"live_bytes": live * per_block,
                "behind_bytes": behind * per_block}

    def _refuse_hybrid_prefix_cache(self, kv_block_size, kv_overcommit):
        """A model of several layer kinds takes ``prefix_cache`` where every
        attending kind reads its slot's whole table masked by position and
        nothing else remembers the row: a window kind's view is placed by the
        slot's linear cursor (pads at the row's left), a selecting kind sizes
        its chunk's view and its counters by that cursor, a recurrent state
        cannot be rewound to a shared prefix. A latent kind that reads ALL it
        sees is taken, though its chunk's view is sized by a cursor too
        (``ops/mla.py:view_steps``): that is the slot's LANE cursor
        (``cache["len"]``, a suffix's pads mid-row included), so the view
        holds every lane written, nothing picks among them, and the lanes
        past them are hidden by position as the table's are. Entries are
        blocks of the kinds' pools (``kv_overcommit`` on), never dense rows."""
        from datatunerx_tpu.models.config import mixer_kinds

        why = {}
        for name, kind in mixer_kinds(self.cfg).items():
            if kind.states(self.cfg):
                why[name] = "keeps a recurrent state per slot"
            elif kind.window:
                why[name] = "reads a window-wide view placed by the slot's cursor"
            elif getattr(kind, "index_topk", 0):
                why[name] = "selects the cached tokens it reads, a view sized by the cursor"
        if why:
            raise NotImplementedError(
                f"model {self.cfg.name!r} has layers of several kinds, of "
                f"which " + "; ".join(f"{n} {w}" for n, w in sorted(why.items()))
                + ": --prefix_cache does not handle it yet")
        if not (kv_block_size > 0 and str(kv_overcommit).lower() == "on"):
            raise NotImplementedError(
                f"model {self.cfg.name!r} has layers of several kinds: "
                "--prefix_cache shares blocks of their pools and needs "
                "--kv_block_size > 0 and --kv_overcommit on")

    def _free_prefix_entry(self, ent: dict):
        """Prefix-cache eviction hook: return a COW block entry's refs to
        the allocator (dense-row entries hold no pool resources). Runs on
        whichever thread evicted (scheduler put, admin drop_adapter) —
        the allocator's own lock covers it."""
        if ent.get("blocks"):
            self._pool.free_entry(ent["blocks"])

    @property
    def prefix_stats(self) -> dict:
        evicted = self._prefix.evictions if self._prefix is not None else 0
        return dict(self._prefix_stats, entries_evicted=evicted)

    def _count_preempt(self, outcome: str):
        self.preempt_stats[outcome] = self.preempt_stats.get(outcome, 0) + 1

    # ------------------------------------------------------------- adapters
    def _build_adapter_stack(self, named: Dict[str, str]):
        """Stack named adapter checkpoints into [L, E, …] leaves (entry 0 is
        the all-zero base adapter). Mixed ranks are padded to the max rank
        (zero cols/rows leave the delta unchanged); mixed target sets take
        the union with zeros where an adapter lacks a target."""
        from datatunerx_tpu.models.lora import (
            adapter_leaves,
            group_tree,
            lora_groups,
        )

        loaded: List[Tuple[str, dict, float]] = []
        for name, path in named.items():
            state = load_checkpoint_state(path)
            lora = state.get("lora")
            if not lora:
                raise ValueError(f"adapter {name!r}: no lora tree in {path}")
            layers = lora["layers"]
            rank = adapter_leaves(layers)[0]["a"].shape[-1]
            scaling = state.get("_scaling")
            if scaling is None:
                scaling = lora_scaling(32.0, rank)
            loaded.append((name, layers, float(scaling)))

        max_rank = max(leaf["a"].shape[-1] for _, layers, _ in loaded
                       for leaf in adapter_leaves(layers))
        E = len(loaded) + 1  # + base zero adapter
        stack: Dict[str, dict] = {}
        # one group of like layers at a time (a model whose layers are all of
        # one kind is the single group None: the flat tree there always was)
        for gkey, L, dims in lora_groups(self.cfg):
            trees = [group_tree(layers, gkey) for _, layers, _ in loaded]
            targets = sorted({t for tree in trees for t in tree}
                             & set(LORA_TARGETS) & set(dims))
            group = stack if gkey is None else stack.setdefault(gkey, {})
            for t in targets:
                d_in, d_out = dims[t]
                a = np.zeros((L, E, d_in, max_rank), np.float32)
                b = np.zeros((L, E, max_rank, d_out), np.float32)
                for e, tree in enumerate(trees, start=1):
                    if t not in tree:
                        continue
                    ar = np.asarray(tree[t]["a"], np.float32)  # [L, d_in, r]
                    br = np.asarray(tree[t]["b"], np.float32)
                    r = ar.shape[-1]
                    a[:, e, :, :r] = ar
                    b[:, e, :r, :] = br
                group[t] = {"a": jnp.asarray(a), "b": jnp.asarray(b)}
        scales = jnp.asarray([0.0] + [s for _, _, s in loaded], jnp.float32)
        self.lora_stack = ({"layers": stack}, scales)
        for e, (name, _, _) in enumerate(loaded, start=1):
            self._static_adapter_ids[name] = e

    @property
    def adapter_ids(self) -> Dict[str, int]:
        """Known adapter names → device index. Static mode: the fixed
        stack's name→index binding. Dynamic mode: every REGISTERED name
        (resident → its pool slot, loadable-on-miss → -1) — membership is
        what the serving server and gateway check."""
        if self.adapter_registry is not None:
            return self.adapter_registry.id_map()
        return self._static_adapter_ids

    def _lora_arg(self):
        """The programs' ``lora`` argument: None (base-only), the static
        stacked tree, or the dynamic pool's atomically-republished
        snapshot (one attribute read — no lock on the decode hot path)."""
        if self.adapter_store is not None:
            return self.adapter_store.tree
        return self.lora_stack

    # ---- dynamic pool control plane (serving /admin/adapters backs these)
    def load_adapter(self, name: str, checkpoint_path: str,
                     preload: bool = True) -> dict:
        """Register (and by default warm) an adapter at runtime. Raises
        NotImplementedError when the engine runs a static stack,
        ValueError / AdapterRankError for a checkpoint the pool geometry
        rejects, RuntimeError on transient pool exhaustion, and
        AdapterPinnedError when re-registering a live name."""
        if self.adapter_registry is None:
            raise NotImplementedError(
                "engine runs a static adapter stack; restart with "
                "--adapter_pool to load adapters at runtime")
        existed = name in self.adapter_registry.names()
        if existed:
            rebound = (self.adapter_registry.describe(name)["checkpoint"]
                       != checkpoint_path)
        self.adapter_registry.register(name, checkpoint_path)
        if existed and rebound and self._prefix is not None:
            # same name, different weights: cached rows are stale
            self._prefix.drop_adapter(name)
        if preload:
            try:
                self.adapter_registry.preload(name)
            except (ValueError, FileNotFoundError):
                # a bad CHECKPOINT must not stay registered (every later
                # request would hit the same error at admission) — but
                # only roll back a registration THIS call created;
                # transient failures (pool exhausted) never unregister
                if not existed:
                    self.adapter_registry.unregister(name)
                raise
        return self.adapter_registry.describe(name)

    def unload_adapter(self, name: str) -> bool:
        """Evict + unregister. AdapterPinnedError while in-flight requests
        still decode with it (the admin plane answers 409)."""
        if self.adapter_registry is None:
            raise NotImplementedError("engine runs a static adapter stack")
        gone = self.adapter_registry.unregister(name)
        if gone:
            if self._prefix is not None:
                # the name may be re-registered with different weights
                # later — rows cached under it must not survive the
                # unbinding
                self._prefix.drop_adapter(name)
            with self._adapter_req_lock:
                # the tenant is gone; its counter series goes with it
                self.adapter_requests.pop(name, None)
        return gone

    def adapter_occupancy(self) -> Optional[dict]:
        """Pool occupancy + registry stats for stats()//metrics; None on
        static/base engines (no pool to report)."""
        if self.adapter_registry is None:
            return None
        occ = self.adapter_registry.occupancy()
        occ["resident_adapters"] = sorted(self.adapter_registry.resident())
        occ["registered_adapters"] = self.adapter_registry.names()
        occ["load_ms"] = list(self.adapter_registry.load_ms)
        with self._adapter_req_lock:
            occ["requests"] = dict(self.adapter_requests)
        return occ

    @property
    def resident_adapters(self) -> Optional[Dict[str, int]]:
        if self.adapter_registry is None:
            return None
        return self.adapter_registry.resident()

    # ------------------------------------------------------------- tenancy
    def _tenant_count(self, tenant: str, key: str, n: int):
        """Bump a per-tenant usage counter under the cap (the PR 10
        adapter_requests pattern): known tenants always count, new label
        values stop landing once 1024 distinct tenants exist — a client-
        controlled header must not grow the exposition unboundedly."""
        with self._tenant_lock:
            row = self.tenant_stats.get(tenant)
            if row is None:
                if len(self.tenant_stats) >= self._tenant_stats_cap:
                    return
                row = self.tenant_stats[tenant] = {
                    "requests": 0, "tokens_in": 0, "tokens_out": 0}
            row[key] = row.get(key, 0) + n

    def tenant_usage(self) -> Optional[dict]:
        """Per-tenant usage + live occupancy for stats()//metrics, or None
        when the tenancy plane is off (consumers gate their exposition on
        this, keeping the no-config scrape byte-identical)."""
        if self.tenants is None:
            return None
        with self._tenant_lock:
            usage = {t: dict(row) for t, row in self.tenant_stats.items()}
        # live KV blocks per tenant: racy slot-list reads, same contract
        # as every other scrape-path stats read
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None or not getattr(req, "tenant", ""):
                continue
            row = usage.setdefault(
                req.tenant,
                {"requests": 0, "tokens_in": 0, "tokens_out": 0})
            row["kv_blocks"] = (row.get("kv_blocks", 0) + (
                len(self._pool.held(s)) if self._pool else 0))
        # adapter residency per tenant (how many of the tenant's adapters
        # are pool-resident right now)
        resident = set(self.adapter_registry.resident()) \
            if self.adapter_registry is not None else set()
        for name in self.tenants.names():
            spec = self.tenants.get(name)
            if spec is None:
                continue
            row = usage.setdefault(
                name, {"requests": 0, "tokens_in": 0, "tokens_out": 0})
            row["tier"] = spec.tier
            row["adapters_resident"] = len(resident & set(spec.adapters))
        return usage

    def refresh_tenant_pins(self):
        """Re-sync the registry's pin set after a directory change (the
        serving admin plane calls this on tenant upserts)."""
        if self.tenants is not None and self.adapter_registry is not None:
            self.adapter_registry.set_pinned(self.tenants.pinned_adapters())

    # ------------------------------------------------------------ scheduler
    def _prefix_key(self, ids, plen, n_prompt, akey):
        return (tuple(ids[plen - n_prompt:]), akey)

    def _adapter_cache_key(self, req: Request):
        """Prefix-cache adapter identity. Dynamic mode keys by NAME: a pool
        slot index is recycled across evict/reload (same name can land on a
        different slot, different name on the same slot), but cached KV rows
        depend only on the adapter's weights — the name is the stable
        identity. Static mode keeps the stack index (bijective with the
        name for the engine's lifetime)."""
        if self.adapter_registry is not None:
            return req.adapter_name
        return req.adapter

    def _prefill_row_cached(self, ids, plen, n_prompt, adapter, akey,
                            budget_needed: int):
        """Prefix-cache paths only: (logits, dense row, cursor) on an exact
        hit (no compute) or a strict-prefix hit (suffix-only extension);
        None on miss or when the cache is disabled. ``adapter`` is the
        device pool/stack index, ``akey`` the cache-key identity.

        Reuse must never change the response: a cached row whose cursor sits
        deeper than this request's own plen (extension padding accumulates)
        is only used when it still leaves ``budget_needed`` decode room —
        otherwise the cold path runs, so budget and output match a cache-cold
        server exactly."""
        if self._prefix is None:
            return None
        used, _ = key = self._prefix_key(ids, plen, n_prompt, akey)
        # the decode room the cold path would provide; reuse may not shrink
        # the effective budget below min(requested, cold)
        need = min(budget_needed, self.max_seq_len - plen)
        ent = self._prefix.get(key)
        # no_reuse entries (logits-free tier imports) carry no activation
        # logits — they serve strict-prefix extension only, never the
        # exact-hit fast path
        if (ent is not None and not ent.get("no_reuse")
                and self.max_seq_len - ent["cursor"] >= need):
            self._count_prefill("reuse", shared=len(used))
            return ent["logits"], ent["cache"], ent["cursor"]
        pkey, pent = self._prefix.longest_prefix(used, akey)
        if pent is not None:
            n_pref = len(pkey[0])
            suffix = list(used[n_pref:])
            pad = (-len(suffix)) % DECODE_BUCKET
            stoks = [self.tokenizer.eos_token_id or 0] * pad + suffix
            smask = [0] * pad + [1] * len(suffix)
            spos = [0] * pad + list(range(n_pref, len(used)))
            cursor = pent["cursor"] + len(stoks)
            if self.max_seq_len - cursor >= need:
                row_logits, row_cache = self._extend(
                    self.params, self._lora_arg(),
                    row_pad(pent["cache"], self.max_seq_len),
                    jnp.asarray([stoks], jnp.int32),
                    jnp.asarray([smask], jnp.int32),
                    jnp.asarray([spos], jnp.int32),
                    jnp.asarray(adapter, jnp.int32),
                    suffix_len=len(stoks),
                )
                self._count_prefill("extend", shared=n_pref,
                                    prefilled=len(suffix))
                self._prefix.put(key, {"cache": row_cache,
                                       "logits": row_logits,
                                       "cursor": cursor})
                return row_logits, row_cache, cursor
        return None

    def _prefill_row(self, ids, mask, positions, plen, n_prompt, adapter,
                     akey, budget_needed: int = 1):
        """Produce (last-token logits, row cache, cache cursor) for a prompt,
        going through the prefix cache when enabled: exact hit = no compute,
        prefix hit = suffix-only extension, miss = full prefill (+ store)."""
        hit = self._prefill_row_cached(ids, plen, n_prompt, adapter, akey,
                                       budget_needed)
        if hit is not None:
            return hit
        row_logits, row_cache = self._prefill(
            self.params, self._lora_arg(), jnp.asarray([ids], jnp.int32),
            jnp.asarray([mask], jnp.int32), jnp.asarray([positions], jnp.int32),
            jnp.asarray(adapter, jnp.int32), prompt_len=plen,
        )
        self._count_prefill("full", prefilled=n_prompt)
        if self._prefix is not None:
            self._prefix.put(self._prefix_key(ids, plen, n_prompt, akey),
                             {"cache": row_cache, "logits": row_logits,
                              "cursor": plen})
        return row_logits, row_cache, plen

    @staticmethod
    def _stop_row(req: Request) -> np.ndarray:
        row = np.full((MAX_STOP,), -1, np.int32)
        row[: len(req.stop_ids)] = req.stop_ids
        return row

    def _arm_args(self, req: Request, n_prompt: int, max_new: int):
        """The per-slot decode-state scalars _insert/_insert_paged/_activate
        all share."""
        return (
            jnp.asarray(n_prompt, jnp.int32), jnp.asarray(max_new, jnp.int32),
            jnp.asarray(req.temperature, jnp.float32),
            jnp.asarray(req.top_p, jnp.float32),
            jnp.asarray(self._stop_row(req)),
            jnp.asarray(req.adapter, jnp.int32),
            jnp.asarray(req.seed, jnp.uint32),
        )

    def _insert_row(self, slot: int, table, row: Dict, row_logits, cursor,
                    arm_args: tuple, rng=None):
        """Put a dense single-row cache into ``slot`` (through the row
        ``table`` of its blocks where the cache is paged) and arm it to decode
        from ``cursor``, the row's real KV depth. ``rng``: a migrated or
        resumed session's LIVE stream, in place of the seed-derived key."""
        program, at = ((self._insert_paged, (table,)) if self.paged
                       else (self._insert, ()))
        (self._cache, self._logits, self._pos, self._remaining,
         self._active, self._temps, self._top_ps, self._stops,
         self._adapter_idx, self._rng) = program(
            self._cache, self._logits, self._pos, self._remaining,
            self._active, self._temps, self._top_ps, self._stops,
            self._adapter_idx, self._rng,
            jnp.asarray(slot, jnp.int32), *at, row, row_logits,
            jnp.asarray(cursor, jnp.int32), *arm_args)
        if rng is not None:
            self._rng = self._rng.at[slot].set(jnp.asarray(rng, jnp.uint32))

    def _admit(self, req: Request, slot: int) -> bool:
        """Occupy ``slot`` with ``req``, resolving (and PINNING) its
        adapter first in dynamic mode — load-on-miss runs here, and a
        fully-pinned pool FIFO-waits exactly like KV-block exhaustion.
        False = some pool (adapter slots or KV blocks) is exhausted; the
        request stays queued with nothing held."""
        pinned = False
        self._admit_wait_reason = "blocks"
        if self.adapter_registry is not None and req.adapter_name:
            if req.adapter_was_resident is None:
                req.adapter_was_resident = (
                    req.adapter_name in self.adapter_registry.resident())
            # non-blocking: a miss kicks an ASYNC load and returns None —
            # decode keeps ticking while the checkpoint reads; the request
            # parks at its FIFO position until the load resolves
            with self._phase("dtx_engine_adapter_acquire"):
                idx = self.adapter_registry.acquire(
                    req.adapter_name,
                    count_hit=not req.adapter_stats_counted)
            if idx is not None:
                req.adapter_stats_counted = True
            if idx is None:
                loading = self.adapter_registry.describe(
                    req.adapter_name).get("loading", False)
                # mid-load → "adapter": younger requests may bypass (the
                # head's pool slot is already reserved). Pool fully pinned
                # → strict FIFO like blocks: bypassers could re-pin
                # residents forever and starve the head's eviction.
                self._admit_wait_reason = ("adapter" if loading
                                           else "adapter_pool")
                if self._last_adapter_wait != req.adapter_name:
                    # dedupe: one trace entry per wait episode, not one
                    # per scheduler retry tick (would flood the ring)
                    self._event("adapter_wait", req.adapter_name)
                    self._last_adapter_wait = req.adapter_name
                return False
            self._last_adapter_wait = None
            pinned = True
            req.adapter = idx
            self._mark(req, "adapter", name=req.adapter_name, slot=idx,
                       loaded=not req.adapter_was_resident)
        try:
            ok = self._admit_slot(req, slot)
        except Exception:
            if pinned:
                self.adapter_registry.release(req.adapter_name)
            raise
        if ok:
            if pinned:
                self._slot_adapter[slot] = req.adapter_name
        elif pinned:
            self.adapter_registry.release(req.adapter_name)
        return ok

    def _admit_slot(self, req: Request, slot: int) -> bool:
        """Occupy ``slot`` with ``req``. Dense mode prefills monolithically
        and arms the slot at once. Paged mode reserves blocks first (False =
        pool exhausted; the request stays queued), serves prefix-cache hits
        by scattering the row into the blocks, and registers everything else
        for chunked prefill interleaved with decode."""
        ids, mask, positions, plen, n_prompt, max_new, _ = prepare_prompt(
            req.prompt_ids, self.tokenizer.eos_token_id,
            self.max_seq_len, req.max_new_tokens,
        )
        # the real (un-padded) kept prompt: what the draft model prefills
        # when this slot later joins speculative decoding
        req.spec_prime_ids = ids[plen - n_prompt:]
        akey = self._adapter_cache_key(req)
        if not self.paged:
            row_logits, row_cache, cursor = self._prefill_row(
                ids, mask, positions, plen, n_prompt, req.adapter, akey,
                budget_needed=max_new)
            max_new = max(1, min(max_new, self.max_seq_len - cursor))
            # the slot's write cursor continues from the row's real KV
            # depth (prefix reuse can sit deeper than this request's plen)
            self._insert_row(slot, None, row_cache, row_logits, cursor,
                             self._arm_args(req, n_prompt, max_new))
            self._seat(slot, req)
            self._admitted(req, slot, plen, "dense")
            return True

        if self.cow:
            # COW prefix blocks: a cache hit maps SHARED physical blocks
            # into this slot's table (copying only the partial tail block)
            # instead of the dense-row copy + re-insert below. None = no
            # usable entry — fall through to the cold chunked path.
            handled = self._admit_cow(req, slot, ids, plen, n_prompt,
                                      max_new, akey)
            if handled is not None:
                return handled
        else:
            hit = self._prefill_row_cached(ids, plen, n_prompt, req.adapter,
                                           akey, budget_needed=max_new)
            if hit is not None:
                row_logits, row_cache, cursor = hit
                max_new = max(1, min(max_new, self.max_seq_len - cursor))
                blocks = self._pool.reserve(cursor, max_new)
                if blocks is None:
                    return False
                with self._pool.occupy(slot, blocks,
                                       cursor + max_new) as table:
                    # scrub first: stored rows are TRIMMED to their live
                    # cursor now, so the insert no longer doubles as the
                    # whole-table recycled-position scrub
                    self._pool.scrub(blocks)
                    self._insert_row(slot, table, row_cache, row_logits,
                                     cursor,
                                     self._arm_args(req, n_prompt, max_new))
                self._seat(slot, req)
                self._admitted(req, slot, plen, "cache")
                return True

        blocks = self._pool.reserve(plen, max_new)
        if blocks is None:
            return False
        with self._pool.occupy(slot, blocks, plen + max_new) as table:
            # install the table, scrub the blocks' recycled positions to the
            # sentinel (chunked prefill reveals the whole table to attention
            # before every lane is written), and rewind the slot cursor
            self._cache = self._install_table(
                self._cache, jnp.asarray(slot, jnp.int32), table)
        self._seat(slot, req, {
            "ids": ids, "mask": mask, "positions": positions, "plen": plen,
            "n_prompt": n_prompt, "max_new": max_new, "base": 0,
            "key": self._prefix_key(ids, plen, n_prompt, akey)})
        self._admitted(req, slot, plen, "chunked")
        return True

    def _seat(self, slot: int, req: Request, pending: Optional[dict] = None):
        """``req`` holds ``slot`` from here on: ready to decode, or with the
        prompt tail ``pending`` describes left to chunk-prefill first."""
        self._slot_req[slot] = req
        self._decode_ready[slot] = pending is None
        if pending is not None:
            self._pending[slot] = {"req": req, "adapter": req.adapter,
                                   "done": 0, **pending}
        live = sum(1 for r in self._slot_req if r is not None)
        if live > self.kv_stats["peak_sessions"]:
            self.kv_stats["peak_sessions"] = live

    # ------------------------------------------------- COW prefix blocks
    def _admit_cow(self, req: Request, slot: int, ids, plen: int,
                   n_prompt: int, max_new: int, akey) -> Optional[bool]:
        """Overcommit admission through the prefix cache: an exact hit
        maps the entry's refcounted blocks into this slot's table and arms
        decode directly (no prefill, no dense-row traffic); a strict-prefix
        hit maps the shared prefix and chunk-prefills only the suffix in
        place. Returns True (admitted) / False (blocks exhausted — the
        FIFO head waits) / None (no usable entry — cold path). The same
        decode-room gates as the dense-row path apply, so reuse never
        shrinks the budget below what a cache-cold server would grant."""
        used, _ = key = self._prefix_key(ids, plen, n_prompt, akey)
        need = min(max_new, self.max_seq_len - plen)
        ent = self._prefix.get(key)
        if (ent is not None and ent.get("blocks") is not None
                and not ent.get("no_reuse")
                and self.max_seq_len - ent["cursor"] >= need):
            m = max(1, min(max_new, self.max_seq_len - ent["cursor"]))
            ok = self._cow_map(req, slot, ent, n_prompt, m,
                               suffix=None, key=key)
            if ok:
                self._count_prefill("reuse", shared=len(used))
                self._admitted(req, slot, plen, "cow", shared=len(used))
            return ok
        pkey, pent = self._prefix.longest_prefix(used, akey)
        if pent is not None and pent.get("blocks") is not None:
            n_pref = len(pkey[0])
            suffix = list(used[n_pref:])
            pad = (-len(suffix)) % DECODE_BUCKET
            eos = self.tokenizer.eos_token_id or 0
            sfx = {"ids": [eos] * pad + suffix,
                   "mask": [0] * pad + [1] * len(suffix),
                   "positions": [0] * pad + list(range(n_pref, len(used)))}
            cursor = pent["cursor"] + len(sfx["ids"])
            if self.max_seq_len - cursor >= need:
                ok = self._cow_map(req, slot, pent, n_prompt, max_new,
                                   suffix=sfx, key=key)
                if ok:
                    self._count_prefill("extend", shared=n_pref,
                                        prefilled=len(suffix))
                    self._admitted(req, slot, plen, "cow_extend",
                                   shared=n_pref)
                return ok
        return None

    def _cow_map(self, req: Request, slot: int, ent: dict, n_prompt: int,
                 max_new: int, suffix: Optional[dict], key) -> bool:
        """Install a prefix-cache BLOCK entry into ``slot``: incref and map
        the entry's full blocks, copy its partial tail block (the at-most-
        once COW event — decode only appends at the cursor, and the cursor
        sits inside that block), allocate fresh blocks for the decode/
        suffix extent, and either arm decode (exact hit) or register the
        suffix for chunked prefill. False = pool can't cover the fresh
        blocks; nothing held."""
        base = ent["cursor"]  # host int: _cow_store stores python scalars
        full, rem = ent["full"], ent["rem"]
        suffix_len = len(suffix["ids"]) if suffix else 0
        final = base + suffix_len
        # >= 1 block of its own: max_new >= 1
        blocks = self._pool.reserve(final, max_new,
                                    shared=ent["blocks"][:full])
        if blocks is None:
            return False
        own = blocks[full:]
        with self._pool.occupy(slot, blocks, final + max_new) as table:
            self._pool.scrub(own)
            if rem:
                self._cache = self._copy_block(
                    self._cache, jnp.asarray(ent["blocks"][full], jnp.int32),
                    jnp.asarray(own[0], jnp.int32),
                    jnp.asarray(rem, jnp.int32))
            self._pool.set_row(slot, table)
            self._cache["len"] = self._cache["len"].at[slot].set(base)
            if suffix is None:
                (self._logits, self._pos, self._remaining, self._active,
                 self._temps, self._top_ps, self._stops, self._adapter_idx,
                 self._rng) = self._activate(
                    self._logits, self._pos, self._remaining, self._active,
                    self._temps, self._top_ps, self._stops,
                    self._adapter_idx, self._rng,
                    jnp.asarray(slot, jnp.int32), ent["logits"],
                    *self._arm_args(req, n_prompt, max_new),
                )
        self._seat(slot, req, None if suffix is None else {
            "ids": suffix["ids"], "mask": suffix["mask"],
            "positions": suffix["positions"], "plen": suffix_len,
            "n_prompt": n_prompt, "max_new": max_new, "key": key,
            "base": base})
        return True

    def _cow_store(self, slot: int, key, cursor: int, row_logits):
        """Publish a freshly-prefilled slot's prefix into the cache as a
        refcounted BLOCK entry: full blocks are shared as-is (their content
        and global pos-pool rows never change again — writes only happen
        at and past the cursor), the partial tail block is copied once so
        the donor's continued decode cannot leak into the entry. A pool
        too tight for the tail copy skips caching: serving beats caching."""
        full, rem = divmod(cursor, self.block_size)
        blocks = self._pool.held(slot)
        ent_blocks = self._pool.take(1 if rem else 0, shared=blocks[:full])
        if ent_blocks is None:
            return
        if rem:
            self._cache = self._copy_block(
                self._cache, jnp.asarray(blocks[full], jnp.int32),
                jnp.asarray(ent_blocks[-1], jnp.int32),
                jnp.asarray(rem, jnp.int32))
        self._prefix.put(key, {"blocks": ent_blocks, "full": full,
                               "rem": rem, "cursor": cursor,
                               "logits": row_logits})

    def _phase(self, name: str, **detail) -> _Phase:
        """``with self._phase("dtx_engine_<what>", ...)``: every span of the
        scheduler opens through here (see ``_Phase``)."""
        return _Phase(self.sched_stats, name, detail)

    def _mark(self, req: Request, event: str, **detail):
        """Stamp ``event`` on the request's own timeline, with the pass of
        the scheduler it happened in."""
        if self.tracing:
            req.mark(event, tick=self._tick_no, **detail)

    def _event(self, name: str, *ring, req: Optional[Request] = None,
               mark: Optional[str] = None, **detail):
        """Record one scheduler event, once: ``(name, *ring)`` into the
        ``sched_trace`` ring and, where it is a request's, ``detail`` onto
        that request's timeline (as ``mark`` where the timeline's word for
        the event differs from the ring's)."""
        self.sched_trace.append((name, *ring))
        if req is not None:
            self._mark(req, mark or name, **detail)

    def _count_prefill(self, kind: str, shared: int = 0, prefilled: int = 0):
        """One admission served ``full`` / ``reuse`` / ``extend``, with the
        prompt tokens it took from the prefix cache and those it prefilled."""
        self.prefill_stats[kind] += 1
        ps = self._prefix_stats
        ps[{"full": "cold", "reuse": "hits", "extend": "extensions"}[kind]] += 1
        ps["shared_tokens"] += shared
        ps["prefilled_tokens"] += prefilled

    def _admitted(self, req: Request, slot: int, plen: int, mode: str,
                  shared: int = 0):
        """The admit event, with how long the request queued and for what:
        ``tick`` (no pass of the scheduler saw it and left it: it was
        admitted within a tick of its submission), what its own last failed
        attempt lacked (``blocks``, ``adapter``), what the head the FIFO
        order held it behind lacked, or ``slot`` (every slot was taken)."""
        ticks = self._tick_no - req.tick_submit
        cause = req.waited_for
        if cause is None:
            if ticks <= 1:
                cause = "tick"
            elif self._blocked_tick > req.tick_submit:
                cause = self._blocked_cause
            else:
                cause = "slot"
        n_prompt = len(req.spec_prime_ids or ())
        # inside the pass's ``dtx_engine_admit``: which path this admission
        # took and what the prefix cache spared it
        with jax.profiler.TraceAnnotation(
                "dtx_engine_admitted", slot=slot, shared_tokens=shared,
                path=mode if mode.startswith("cow") else "cold",
                prefill_tokens=n_prompt - shared):
            self._event("admit", slot, plen, mode, req=req, slot=slot,
                        plen=plen, mode=mode, waited_ticks=ticks,
                        waited_for=cause, shared_tokens=shared)

    def _complete(self, req: Request, error: Optional[str] = None):
        """Finish a request AND flush its buffered observability: one
        TTFT/TPOT observe pair per request (never per token) and, with
        tracing on, the request's span timeline into the trace ring."""
        with self._phase("dtx_engine_complete"):
            n = len(req.tokens)
            self.generated_tokens += n
            if req.first_token_ts is not None:
                # exemplar only when tracing: the trace id is then resolvable
                # at GET /debug/trace/<id>, and the tracing-off observe stays
                # the bare-arithmetic path (token-parity test's no-overhead
                # contract)
                tid = req.trace_id if self.tracing else None
                self._h_ttft.observe(
                    (req.first_token_ts - req.t_submit) * 1e3, trace_id=tid)
                if req.last_token_ts is not None and n > 1:
                    self._h_tpot.observe(
                        (req.last_token_ts - req.first_token_ts)
                        / (n - 1) * 1e3, trace_id=tid)
            if self.tenants is not None and getattr(req, "tenant", ""):
                self._tenant_count(req.tenant, "tokens_out", n)
            if self.tracing:
                span = build_request_span(
                    req.trace_id, req.t_submit, req.timeline,
                    req.first_token_ts, req.last_token_ts, n,
                    req.wall_submit_ms, error=error,
                    attrs={"adapter": req.adapter_name or req.adapter,
                           "prompt_len": len(req.prompt_ids)},
                )
                self.trace_store.add(span)
            req.finish(error=error)

    def _wait_cause(self) -> str:
        """Why the scheduler is about to sleep with work it cannot run:
        ``preempted`` (a parked session cannot resume yet), what the queue's
        head lacks (``blocks``, ``adapter``, ``adapter_pool``), ``import`` (a
        migration command waits for room), or "" when nothing has been asked
        of the engine. A request that arrived during this pass has set
        ``_wake``: the sleep it meets has no length."""
        if self._preempted:
            return "preempted"
        if self._waiting_front:
            return self._admit_wait_reason or "blocks"
        if self._mig_retry:
            return "import"
        return ""

    def _take_waiting(self) -> Optional[Request]:
        if self._waiting_front:
            return self._waiting_front.popleft()
        try:
            return self._waiting.get_nowait()
        except queue.Empty:
            return None

    def _requeue_front(self, reqs: List[Request]):
        """Restore requests to the FRONT of the wait order, preserving
        their relative (older-first) order."""
        for req in reversed(reqs):
            self._waiting_front.appendleft(req)

    def _admit_waiting(self):
        # requests whose adapter is mid-load this pass: parked aside so
        # YOUNGER requests can fill other slots while the checkpoint reads
        # (their pool slot is already reserved by the load, so bypass
        # cannot starve them — they re-admit at their FIFO position)
        parked: List[Request] = []
        for slot in range(self.slots):
            if self._slot_req[slot] is not None:
                continue
            while True:
                req = self._take_waiting()
                if req is None:
                    self._requeue_front(parked)
                    return
                if (self._preempted
                        and self._preempted[0]["req"].seq < req.seq):
                    # strict FIFO across parked populations: a preempted
                    # session older than this cold request resumes first —
                    # admitting the younger one would hand it the very
                    # blocks the parked head is waiting for
                    self._blocked_tick = self._tick_no
                    self._blocked_cause = "blocks"
                    self._requeue_front(parked + [req])
                    return
                try:
                    ok = self._admit(req, slot)
                except Exception as e:  # noqa: BLE001 — fail request, not loop
                    self._complete(req, error=str(e))
                    continue  # try the next request for this slot
                if ok:
                    break
                if self._reclaim_idle_entry():
                    self._requeue_front([req])
                    continue  # the head tries again with what an entry held
                req.waited_for = ("blocks" if self._admit_wait_reason
                                  == "blocks" else "adapter")
                if self._admit_wait_reason == "adapter":
                    parked.append(req)
                    continue
                # KV blocks exhausted: the FIFO head waits for freed blocks
                # (younger requests must not starve it by sneaking in —
                # they'd consume the very blocks it needs)
                self._blocked_tick = self._tick_no
                self._blocked_cause = req.waited_for
                self._requeue_front(parked + [req])
                return
        self._requeue_front(parked)

    def _reclaim_idle_entry(self) -> bool:
        """The queue's head found the pool short: free the least recently
        used prefix-cache block entry that no live slot maps (an ended
        session's: a cached prefix is a performance tier, a waiting request
        is the product). An entry a live slot shares blocks with gives back
        its tail block at most and costs that session its next hit, so it
        stays. False: the cache is off, or every entry is mapped."""
        if not (self._prefix is not None and self._admit_wait_reason == "blocks"):
            return False
        live = {b for slot in range(self.slots) for b in self._pool.held(slot)}
        ent = self._prefix.pop_lru_block_entry(
            lambda e: live.isdisjoint(e["blocks"]))
        if ent is None:
            return False
        free = self._pool.free
        self._pool.free_entry(ent["blocks"])
        self._prefix_stats["blocks_reclaimed_at_admission"] += self._pool.free - free
        self._event("reclaim_entry", len(ent["blocks"]))
        return True

    def _prefill_tick(self):
        """Spend AT MOST ``prefill_token_budget`` prompt tokens on pending
        chunked prefills (admission order), then yield back to decode. The
        bound is hard: the last chunk of a tick is clamped to the remaining
        budget (all three operands — prefill_chunk, the budget, and plen,
        whose kept-prompt cap prepare_prompt floors to a bucket multiple — are
        bucket multiples, so the clamp never produces an off-bucket program).
        A budget of 0 prefills every pending prompt to completion."""
        if not self._pending:
            return
        budget = self.prefill_token_budget or float("inf")
        spent = 0
        for slot in list(self._pending.keys()):
            st = self._pending[slot]
            req = st["req"]
            while spent < budget:
                c = min(self.prefill_chunk, st["plen"] - st["done"],
                        budget - spent)
                lo = st["done"]
                try:
                    with self._phase("dtx_engine_prefill_chunk", tokens=c, slot=slot,
                                     **self._dsa_chunk_marks(st.get("base", 0) + lo, c)):
                        logits, self._cache = self._prefill_chunk_fn(
                            self.params, self._lora_arg(), self._cache,
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray([st["ids"][lo:lo + c]], jnp.int32),
                            jnp.asarray([st["mask"][lo:lo + c]], jnp.int32),
                            jnp.asarray([st["positions"][lo:lo + c]], jnp.int32),
                            jnp.asarray(st["adapter"], jnp.int32),
                            chunk_len=c,
                        )
                except Exception as e:  # noqa: BLE001 — fail request, not loop
                    # the chunk program consumes the cache it is given. An
                    # error raised while it is traced or compiled (a shape,
                    # the compiler's refusal) comes before the call takes
                    # the buffers: self._cache is intact, this request
                    # fails and the engine serves on. A fault of the
                    # RUNNING program leaves the donated leaves deleted:
                    # the scheduler then stops serving (_stop_serving)
                    # rather than go on with a pool it no longer has.
                    if self._cache_consumed():
                        raise
                    self._release_slots([slot])
                    self._complete(req, error=str(e))
                    break
                st["done"] += c
                spent += c
                self._event("prefill", slot, c, req=req, slot=slot, tokens=c)
                if st["done"] >= st["plen"]:
                    with self._phase("dtx_engine_activate", slot=slot):
                        self._finish_prefill(slot, st, logits)
                    break
            if spent >= budget:
                break

    def _finish_prefill(self, slot: int, st: dict, row_logits):
        del self._pending[slot]
        req = st["req"]
        # COW suffix prefills start at a shared-prefix base cursor; the
        # decode extent is measured from the FINAL cursor, exactly like
        # the dense extension path's clamp
        cursor = st.get("base", 0) + st["plen"]
        max_new = max(1, min(st["max_new"], self.max_seq_len - cursor))
        (self._logits, self._pos, self._remaining, self._active, self._temps,
         self._top_ps, self._stops, self._adapter_idx, self._rng) = \
            self._activate(
                self._logits, self._pos, self._remaining, self._active,
                self._temps, self._top_ps, self._stops, self._adapter_idx,
                self._rng, jnp.asarray(slot, jnp.int32), row_logits,
                *self._arm_args(req, st["n_prompt"], max_new),
            )
        self._decode_ready[slot] = True
        if not st.get("base") and st.get("key") is not None:
            # suffix extensions already counted as "extend" at admission;
            # imported mid-prefill tails (key None) are not cold prefills
            self._count_prefill("full", prefilled=st["n_prompt"])
        if self._prefix is not None and st.get("key") is not None:
            if self.cow:
                # publish refcounted blocks — no dense-row materialisation
                self._cow_store(slot, st["key"], cursor, row_logits)
            else:
                # export the slot's blocks as a dense row so later prompts
                # can reuse/extend this prefix exactly like in dense mode —
                # TRIMMED to the live cursor (PR 12 row_trim math inside
                # paged_extract_row), so short prefixes stop paying a full
                # max_seq_len gather per insert
                row = self._extract(self._cache,
                                    jnp.asarray(slot, jnp.int32),
                                    jnp.asarray(cursor, jnp.int32),
                                    width=cursor)
                self._prefix.put(st["key"], {"cache": row,
                                             "logits": row_logits,
                                             "cursor": cursor})
        self._event("activate", slot, req=req, slot=slot)

    # ------------------------------------------------- KV migration fabric
    def export_sessions(self, slots: Optional[Sequence[int]] = None,
                        wire_quant: Optional[str] = None,
                        timeout_s: float = 30.0,
                        include_prefill: bool = False) -> dict:
        """Serialize every in-flight decode session (or just ``slots``)
        into portable payloads (serving/migration.py wire format) AND
        terminate the source requests with the migrated marker — their
        streams end, and the gateway splices the imported continuation.

        Runs on the scheduler thread (state owner); this call just queues
        the command and waits. Returns {"sessions": [...], "skipped":
        [{"slot", "reason"}]} — slots mid-chunked-prefill are skipped
        (their KV is incomplete; they finish in place on the draining
        replica, the counted fallback).

        ``include_prefill=True`` ships mid-chunked-prefill slots too
        (disaggregated handoff): the payload carries the blocks written so
        far plus a ``pending`` document with the remaining prompt tail, and
        the importer resumes chunked prefill where the source stopped."""
        from datatunerx_tpu.models.config import refuse_recurrent_state

        refuse_recurrent_state(self.cfg, "session migration")
        return self._mig_call({"kind": "export",
                               "slots": (None if slots is None
                                         else [int(s) for s in slots]),
                               "wire": wire_quant,
                               "prefill": bool(include_prefill)}, timeout_s)

    def import_session(self, payload: dict, timeout_s: float = 30.0,
                       wait_s: float = 10.0) -> dict:
        """Admit an exported session: allocate blocks, scatter the KV row
        back in (``paged_insert_row`` via the same jitted insert admission
        uses), restore the decode state — including the slot's live PRNG
        key, so greedy AND fixed-seed sampled resumption are token-exact —
        and resume decode.

        Transient shortages (no free slot, KV blocks exhausted, adapter
        still loading) PARK the import and retry each scheduler tick for
        up to ``wait_s`` — a busy target admits the migrating session as
        soon as capacity frees, ahead of its cold FIFO queue — then refuse.
        Raises ValueError on refusals (including permanent ones: unknown
        adapter, incompatible model) and RuntimeError on engine faults.
        The returned meta carries ``"_request"`` (the live Request handle
        for ``resume_stream``) and ``text_so_far`` (the detokenized
        migrated tail)."""
        from datatunerx_tpu.models.config import refuse_recurrent_state

        refuse_recurrent_state(self.cfg, "session migration")
        return self._mig_call(
            {"kind": "import", "payload": payload,
             "deadline": time.monotonic() + wait_s}, timeout_s)

    def hold_parked(self, max_sessions: int = 4, hold_s: float = 10.0,
                    timeout_s: float = 30.0) -> dict:
        """Phase 1 of a peer spill: lease up to ``max_sessions``
        preemption-parked payloads to the fleet coordinator. A held entry
        will not resume locally until the hold expires (or is released) —
        and, because the parked head still gates younger cold admissions,
        FIFO fairness holds while the coordinator re-homes it. Holds are
        time-bounded so a dead coordinator never wedges resumption.
        Returns {"sessions": [{"trace_id", "seq", "cursor", "remaining",
        "payload"}], "parked": n}."""
        return self._mig_call({"kind": "hold_parked",
                               "max_sessions": int(max_sessions),
                               "hold_s": float(hold_s)}, timeout_s)

    def drop_parked(self, trace_ids: Sequence[str],
                    timeout_s: float = 30.0) -> dict:
        """Phase 2 (success): the coordinator imported these parked
        sessions onto a peer — drop them here and terminate their source
        requests with the migrated marker so the gateway splices."""
        return self._mig_call({"kind": "drop_parked",
                               "trace_ids": [str(t) for t in trace_ids]},
                              timeout_s)

    def release_parked(self, trace_ids: Sequence[str],
                       timeout_s: float = 30.0) -> dict:
        """Phase 2 (failure): the peer refused — clear the hold so the
        sessions resume locally as if the spill was never attempted."""
        return self._mig_call({"kind": "release_parked",
                               "trace_ids": [str(t) for t in trace_ids]},
                              timeout_s)

    def export_prefix_entries(self, exclude: Optional[Sequence[str]] = None,
                              max_entries: int = 4,
                              wire_quant: Optional[str] = None,
                              timeout_s: float = 30.0) -> dict:
        """Serialize up to ``max_entries`` local prefix-cache entries
        (MRU first) as ``dtx-kv-prefix`` payloads for the fleet-shared
        prefix tier, skipping fingerprints in ``exclude`` (what the
        gateway directory already holds). Non-destructive: entries stay
        cached locally. Returns {"entries": [payload, ...]}."""
        return self._mig_call({"kind": "export_prefix",
                               "exclude": (set(exclude) if exclude
                                           else set()),
                               "max_entries": int(max_entries),
                               "wire": wire_quant}, timeout_s)

    def import_prefix_entry(self, payload: dict, timeout_s: float = 30.0,
                            wait_s: float = 5.0) -> dict:
        """Install a fleet-published prefix payload into the local
        ``_PrefixCache`` so the NEXT prompt sharing that prefix admits via
        the COW hit path with zero prefill chunks. Transient block
        shortages retry until ``wait_s``; permanent mismatches (model
        signature, unknown adapter) raise ValueError."""
        return self._mig_call(
            {"kind": "import_prefix", "payload": payload,
             "deadline": time.monotonic() + wait_s}, timeout_s)

    def resume_stream(self, req: Request):
        """Continuation deltas of an imported session: text BEYOND the
        migrated tail, streamed as decode produces it (the tail itself was
        already emitted to the client by the source replica)."""
        yield from self._stream_text(
            req, list(req.tokens[: getattr(req, "resume_base", 0)]))

    def _stream_text(self, req: Request, acc: List[int]):
        """Text deltas of ``req`` beyond the tokens already in ``acc``, as
        decode produces them. Text that ends in U+FFFD is held back (the
        next token may complete the byte sequence) until the stream ends:
        a reply whose last bytes never complete is what ``chat`` returns,
        so the stream sends it too."""
        sent = text = (self.tokenizer.decode(acc, skip_special_tokens=True)
                       if acc else "")
        while True:
            t = req.stream.get()
            if t is None:
                break
            acc.append(t)
            text = self.tokenizer.decode(acc, skip_special_tokens=True)
            if len(text) > len(sent) and not text.endswith("�"):
                yield text[len(sent):]
                sent = text
        if req.error:
            # a migrated session's held tail belongs to its continuation
            raise RuntimeError(req.error)
        if len(text) > len(sent):
            yield text[len(sent):]

    def adapter_catalog(self) -> Dict[str, str]:
        """Registered adapter name → checkpoint path (dynamic pools only)
        — what a replacement replica needs to rebuild this replica's
        warm set."""
        if self.adapter_registry is None:
            return {}
        return {n: self.adapter_registry.describe(n)["checkpoint"]
                for n in self.adapter_registry.names()}

    def _mig_call(self, cmd: dict, timeout_s: float):
        if self._shutdown.is_set():
            raise RuntimeError("engine is shut down")
        cmd["_done"] = threading.Event()
        self._mig_q.put(cmd)
        self._wake.set()
        if not cmd["_done"].wait(timeout_s):
            raise TimeoutError(
                f"engine did not service session {cmd['kind']} within "
                f"{timeout_s}s")
        if cmd.get("_error"):
            if cmd.get("_refused"):
                raise ValueError(cmd["_error"])
            raise RuntimeError(cmd["_error"])
        return cmd["_result"]

    def _count_mig(self, kind: str, outcome: str):
        d = self.session_stats.setdefault(kind, {})
        d[outcome] = d.get(outcome, 0) + 1

    def _service_migrations(self):
        if not self._mig_retry and self._mig_q.empty():
            return
        pending, self._mig_retry = self._mig_retry, []
        while True:
            try:
                pending.append(self._mig_q.get_nowait())
            except queue.Empty:
                break
        for cmd in pending:
            try:
                if cmd["kind"] == "export":
                    cmd["_result"] = self._do_export(cmd)
                elif cmd["kind"] == "import":
                    cmd["_result"] = self._do_import(cmd)
                elif cmd["kind"] == "hold_parked":
                    cmd["_result"] = self._do_hold_parked(cmd)
                elif cmd["kind"] == "drop_parked":
                    cmd["_result"] = self._do_drop_parked(cmd)
                elif cmd["kind"] == "release_parked":
                    cmd["_result"] = self._do_release_parked(cmd)
                elif cmd["kind"] == "export_prefix":
                    cmd["_result"] = self._do_export_prefix(cmd)
                elif cmd["kind"] == "import_prefix":
                    cmd["_result"] = self._do_import_prefix(cmd)
                else:
                    raise ValueError(
                        f"unknown session command {cmd['kind']!r}")
            except _RetryLater as retry:
                if time.monotonic() < cmd.get("deadline", 0.0):
                    cmd["_retry_reason"] = str(retry)
                    self._mig_retry.append(cmd)
                    continue
                cmd["_error"] = str(retry)
                cmd["_refused"] = True
                self._count_mig(cmd["kind"], "refused")
            except (ValueError, KeyError) as e:
                cmd["_error"] = str(e)
                cmd["_refused"] = True
                self._count_mig(cmd["kind"], "refused")
            except Exception as e:  # noqa: BLE001 — fail the command, not the loop
                cmd["_error"] = str(e)
                cmd["_refused"] = False
                self._count_mig(cmd["kind"], "error")
            cmd["_done"].set()

    def _do_export(self, cmd: dict) -> dict:
        from datatunerx_tpu.serving.migration import (
            MIGRATED_SESSION,
            encode_payload,
        )

        want = cmd.get("slots")
        sessions: List[dict] = []
        skipped: List[dict] = []
        for slot in range(self.slots):
            if want is not None and slot not in want:
                continue
            req = self._slot_req[slot]
            if req is None:
                if want is not None:
                    skipped.append({"slot": slot, "reason": "empty"})
                continue
            st = None  # the chunked-prefill state of a slot exported mid-prompt
            if not self._decode_ready[slot]:
                st = self._pending.get(slot)
                if not (cmd.get("prefill") and st is not None and self.paged):
                    skipped.append({"slot": slot,
                                    "reason": "prefill_in_progress"})
                    self._count_mig("export", "skipped_prefill")
                    continue
            try:
                if st is not None:
                    # disaggregated handoff: ship the blocks written so
                    # far plus the remaining prompt tail — the importer
                    # resumes chunked prefill exactly where we stopped
                    payload = self._export_prefill_slot(
                        slot, st, cmd.get("wire"))
                else:
                    # a spec-active slot first settles: its pending token's
                    # KV is written and next-token logits materialize, so
                    # the payload is the standard logits-form wire format
                    # any replica (spec or not) can import; the importer
                    # re-primes its own draft cache rather than shipping
                    # draft KV
                    if self.spec is not None and self._spec_form[slot]:
                        self._spec_settle_slot(slot)
                    payload = self._export_slot(slot, req, cmd.get("wire"))
            except Exception as e:  # noqa: BLE001 — skip the slot, keep the rest
                skipped.append({"slot": slot, "reason": str(e)})
                self._count_mig("export", "error")
                continue
            sessions.append(payload)
            if st is not None:
                self._count_mig("export", "ok_prefill")
                self._event("export_prefill", slot, req=req, mark="export",
                            slot=slot, prefill=True, done=st["done"])
            else:
                self._count_mig("export", "ok")
                self._event("export", slot, req=req, slot=slot,
                            cursor=payload["cursor"])
            self._vacate_slot(slot)
            self._complete(req, error=f"{MIGRATED_SESSION}: "
                           + ("prefill " if st is not None else "")
                           + "slot exported")
        if want is None and self._preempted:
            # preemption-parked sessions are in flight too — a drain that
            # missed them would strand their clients. Their payloads
            # already exist (raw numpy bodies): re-encode for the wire,
            # terminate with the migrated marker so the gateway splices.
            # entries leased to the spill coordinator stay parked: the
            # coordinator (or lease expiry) is their single owner — a
            # drain exporting them too would fork the session onto two
            # replicas at once
            now = time.monotonic()
            parked = [e for e in self._preempted
                      if e.get("hold_until", 0.0) <= now]
            self._preempted = [e for e in self._preempted
                               if e.get("hold_until", 0.0) > now]
            for entry in parked:
                req = entry["req"]
                sessions.append(encode_payload(entry["payload"]))
                self._count_mig("export", "ok")
                self._event("export_parked", req.seq, req=req,
                            mark="export", parked=True)
                self._complete(
                    req, error=f"{MIGRATED_SESSION}: parked session exported")
        return {"sessions": sessions, "skipped": skipped}

    @staticmethod
    def _request_doc(req: Request) -> dict:
        return {"trace_id": req.trace_id,
                "adapter": req.adapter_name,
                "prompt_ids": list(req.prompt_ids),
                "tokens": list(req.tokens),
                "max_new_tokens": req.max_new_tokens,
                "temperature": req.temperature, "top_p": req.top_p,
                "seed": req.seed, "stop_ids": list(req.stop_ids)}

    def _export_slot(self, slot: int, req: Request,
                     wire: Optional[str], b64: bool = True) -> dict:
        from datatunerx_tpu.serving import migration as mig

        # the migration path's designed sync point: the slot's scalar
        # decode state crosses to host once per exported session
        cursor, pos, remaining, rng, logits = jax.device_get(  # dtxlint: disable=DTX001
            (self._cache["len"][slot], self._pos[slot],
             self._remaining[slot], self._rng[slot], self._logits[slot]))
        if self.paged:
            # gather only the live prefix's blocks (bucket-rounded so the
            # static-width program count stays bounded) — the wire pays
            # cursor columns, not a max_seq_len row
            w = min(-(-max(1, int(cursor)) // DECODE_BUCKET) * DECODE_BUCKET,  # dtxlint: disable=DTX001 — cursor is host (device_get above)
                    self.max_seq_len)
            row = self._extract(self._cache, jnp.asarray(slot, jnp.int32),
                                jnp.asarray(cursor, jnp.int32), width=w)
        else:
            row = {"pos": self._cache["pos"][slot:slot + 1],
                   "len": jnp.asarray(cursor, jnp.int32)}
            for key in kv_leaf_keys(self._cache):
                row[key] = self._cache[key][:, slot:slot + 1]
        payload = mig.build_payload(
            self.cfg, self.kv_quant,
            request=self._request_doc(req),
            row=row, cursor=cursor, pos=pos, remaining=remaining,
            rng=rng, logits=logits, wire=wire, b64=b64)
        # learned spec-controller state rides the payload as plain JSON
        # (encode/normalize pass unknown keys through untouched): the
        # destination's re-prime rebuilds the draft KV, but without this
        # the controller restarts cold — acceptance EMAs and learned tree
        # widths would relearn from scratch after every migration
        if self.spec is not None:
            payload["spec"] = self.spec_ctrl.export_slot_state(slot)
        return payload

    def _export_prefill_slot(self, slot: int, st: dict,
                             wire: Optional[str]) -> dict:
        """Serialize a mid-chunked-prefill slot: the KV written so far
        (``base + done`` lanes) plus a ``pending`` document carrying the
        un-prefilled prompt tail. No decode state exists yet — rng/logits
        are placeholders; the importer's ``_finish_prefill`` arms the slot
        from ``req.seed`` exactly as an undisturbed in-place prefill
        would, so the handoff is token-exact by construction."""
        from datatunerx_tpu.serving import migration as mig

        req = st["req"]
        cursor = int(st.get("base", 0)) + int(st["done"])  # dtxlint: disable=DTX001 — pending-doc fields are host ints
        w = min(-(-max(1, cursor) // DECODE_BUCKET) * DECODE_BUCKET,
                self.max_seq_len)
        row = self._extract(self._cache, jnp.asarray(slot, jnp.int32),
                            jnp.asarray(cursor, jnp.int32), width=w)
        payload = mig.build_payload(
            self.cfg, self.kv_quant,
            request=self._request_doc(req),
            row=row, cursor=cursor, pos=st["n_prompt"],
            remaining=st["max_new"], rng=np.zeros(2, np.uint32),
            logits=np.zeros((self.cfg.vocab_size,), np.float32),
            wire=wire, b64=True)
        done = int(st["done"])  # dtxlint: disable=DTX001 — pending-doc fields are host ints
        payload["pending"] = {
            "ids": [int(t) for t in st["ids"][done:]],  # dtxlint: disable=DTX001 — pending-doc fields are host ints
            "mask": [int(m) for m in st["mask"][done:]],  # dtxlint: disable=DTX001 — pending-doc fields are host ints
            "positions": [int(p) for p in st["positions"][done:]],  # dtxlint: disable=DTX001 — pending-doc fields are host ints
            "n_prompt": int(st["n_prompt"]),  # dtxlint: disable=DTX001 — pending-doc fields are host ints
            "max_new": int(st["max_new"]),  # dtxlint: disable=DTX001 — pending-doc fields are host ints
            "base": int(st.get("base", 0)),  # dtxlint: disable=DTX001 — pending-doc fields are host ints
            "done": done,
        }
        return payload

    def _do_import(self, cmd: dict) -> dict:
        from datatunerx_tpu.serving import migration as mig

        payload = mig.normalize_payload(cmd["payload"], self.cfg)
        cursor = payload["cursor"]
        pos_val = payload["pos"]
        W = self.max_seq_len
        if cursor >= W:
            raise ValueError(
                f"session depth {cursor} exceeds this replica's context {W}")
        remaining = max(1, min(payload["remaining"], W - cursor))
        slot = next((i for i in range(self.slots)
                     if self._slot_req[i] is None), None)
        if slot is None:
            raise _RetryLater(
                f"no free cache slot to import into ({self.slots} busy)")
        name = payload["adapter"]
        idx = 0
        pinned = False
        if name:
            if self.adapter_registry is not None:
                # hit/miss stats latch across retry ticks, like a
                # readmission retry at _admit
                first_lookup = not cmd.get("_adapter_seen", False)
                cmd["_adapter_seen"] = True
                try:
                    acquired = self.adapter_registry.acquire(
                        name, count_hit=first_lookup)
                except KeyError:
                    raise ValueError(
                        f"unknown adapter {name!r} on this replica")
                if acquired is None:
                    # mid-load (or pool pinned): retry next tick until the
                    # command's deadline — the import itself kicked the
                    # load-on-miss, same as admission would
                    loading = self.adapter_registry.describe(
                        name).get("loading", False)
                    raise _RetryLater(
                        f"adapter {name!r} "
                        + ("still loading" if loading
                           else "pool exhausted (all slots pinned)"))
                idx, pinned = acquired, True
            elif name in self._static_adapter_ids:
                idx = self._static_adapter_ids[name]
            else:
                raise ValueError(f"unknown adapter {name!r} on this replica")
        pending = payload.get("pending")
        try:
            if pending is not None:
                return self._import_prefill_tail(payload, pending, slot,
                                                 name, idx, pinned, cursor)
            occupy = contextlib.nullcontext()
            if self.paged:
                # overcommit engines import lazily too: the grower extends
                # the table as the resumed decode advances
                blocks = self._pool.reserve(cursor, remaining)
                if blocks is None:
                    depth = self._pool.reserve_depth(cursor, remaining)
                    raise _RetryLater(
                        "kv blocks exhausted "
                        f"(need {-(-depth // self.block_size)}"
                        f", free {self._pool.free})")
                occupy = self._pool.occupy(slot, blocks, cursor + remaining)
            with occupy as table:
                row = mig.unpack_kv_row(payload["kv"], full_width=W,
                                        quantize=self.kv_quant)
                row_logits = mig.unpack_logits(payload, self.cfg.vocab_size)
                req = self._imported_request(payload, slot, idx, name)
                self._insert_row(slot, table, row, row_logits, cursor,
                                 self._arm_args(req, pos_val, remaining),
                                 rng=payload["rng"])
        except Exception:
            if pinned:
                self.adapter_registry.release(name)
            raise
        if pinned:
            self._slot_adapter[slot] = name
        self._seat(slot, req)
        self._count_mig("import", "ok")
        self._event("import", slot, cursor, req=req, slot=slot,
                    cursor=cursor, adapter=name, tail_tokens=req.resume_base)
        text = (self.tokenizer.decode(req.tokens, skip_special_tokens=True)
                if req.tokens else "")
        return {"session": req.trace_id, "slot": slot,
                "tokens": req.resume_base, "cursor": cursor,
                "remaining": remaining, "adapter": name,
                "text_so_far": text, "_request": req}

    def _imported_request(self, payload: dict, slot: int, idx: int,
                          name: str) -> Request:
        """The request a migrated session goes on as in ``slot``."""
        req = Request(
            payload["prompt_ids"], payload["max_new_tokens"],
            payload["temperature"], payload["top_p"],
            payload["seed"], payload["stop_ids"],
            idx, adapter_name=name,
            trace_id=payload["trace_id"] or f"dtx-{uuid.uuid4().hex[:16]}")
        req.tokens = payload["tokens"]
        req.resume_base = len(req.tokens)
        if self.spec is not None:
            # re-prime contract: the wire carries no draft-cache state; the
            # slot joins speculative decoding after its draft row is
            # re-prefilled from the payload's prompt + tail (the scheduler
            # does this before the slot's first spec step — priming affects
            # acceptance only, never output exactness)
            p_ids, _, _, p_plen, p_n, _, _ = prepare_prompt(
                payload["prompt_ids"], self.tokenizer.eos_token_id,
                self.max_seq_len, payload["max_new_tokens"])
            req.spec_prime_ids = p_ids[p_plen - p_n:]
            # warm the controller from the source's learned state
            # (acceptance EMAs, learned per-depth widths): re-prime rebuilds
            # the draft KV but must not reset what the source already
            # learned about this session's acceptance
            self.spec_ctrl.import_slot_state(slot, payload.get("spec"))
        return req

    def _import_prefill_tail(self, payload: dict, pending: dict, slot: int,
                             name: str, idx: int, pinned: bool,
                             cursor: int) -> dict:
        """Admit a mid-chunked-prefill export: scatter the KV written so
        far into fresh blocks, then register the remaining prompt tail as
        a normal ``_pending`` chunked prefill (``key`` None — an imported
        tail is not a cold prefill and never publishes a prefix entry).
        ``_finish_prefill`` then arms decode from ``req.seed`` exactly as
        the source replica would have, so the handoff is token-exact."""
        from datatunerx_tpu.serving import migration as mig

        if not self.paged:
            raise ValueError("mid-prefill import requires a paged engine")
        ids = [int(t) for t in pending["ids"]]  # dtxlint: disable=DTX001 — wire payloads carry host scalars
        mask = [int(m) for m in pending["mask"]]  # dtxlint: disable=DTX001 — wire payloads carry host scalars
        positions = [int(p) for p in pending["positions"]]  # dtxlint: disable=DTX001 — wire payloads carry host scalars
        final = cursor + len(ids)
        W = self.max_seq_len
        if final >= W:
            raise ValueError(
                f"prefill depth {final} exceeds this replica's context {W}")
        max_new = max(1, int(pending["max_new"]))  # dtxlint: disable=DTX001 — wire payloads carry host scalars
        blocks = self._pool.reserve(final, max_new)
        if blocks is None:
            raise _RetryLater(
                "kv blocks exhausted for mid-prefill import "
                f"(free {self._pool.free})")
        with self._pool.occupy(slot, blocks, final + max_new) as table:
            row = mig.unpack_kv_row(payload["kv"], full_width=W,
                                    quantize=self.kv_quant)
            req = self._imported_request(payload, slot, idx, name)
            # the row's unwritten tail is sentinel-padded to full
            # width, so the scatter doubles as the recycled-block scrub
            self._cache = paged_insert_row(self._cache, slot, table, row)
            self._cache["len"] = self._cache["len"].at[slot].set(cursor)
        if pinned:
            self._slot_adapter[slot] = name
        self._seat(slot, req, {
            "ids": ids, "mask": mask, "positions": positions,
            "plen": len(ids), "n_prompt": int(pending["n_prompt"]),  # dtxlint: disable=DTX001 — wire payloads carry host scalars
            "max_new": max_new, "base": cursor, "key": None})
        self._count_mig("import", "ok_prefill")
        self._event("import_prefill", slot, cursor, req=req, mark="import",
                    slot=slot, cursor=cursor, adapter=name, prefill=True,
                    tail=len(ids))
        text = (self.tokenizer.decode(req.tokens, skip_special_tokens=True)
                if req.tokens else "")
        return {"session": req.trace_id, "slot": slot,
                "tokens": req.resume_base, "cursor": cursor,
                "remaining": max_new, "adapter": name,
                "text_so_far": text, "_request": req, "prefill": True}

    # ------------------------------------------------- fleet spill (parked)
    def _do_hold_parked(self, cmd: dict) -> dict:
        from datatunerx_tpu.serving.migration import encode_payload

        now = time.monotonic()
        hold_until = now + float(cmd.get("hold_s", 10.0))  # dtxlint: disable=DTX001 — mig-command args are host scalars
        limit = int(cmd.get("max_sessions", 4))  # dtxlint: disable=DTX001 — mig-command args are host scalars
        out = []
        for entry in self._preempted:
            if len(out) >= limit:
                break
            if entry.get("hold_until", 0.0) > now:
                continue  # already leased
            entry["hold_until"] = hold_until
            payload = entry["payload"]
            out.append({"trace_id": entry["req"].trace_id,
                        "seq": entry["req"].seq,
                        "cursor": int(payload["cursor"]),  # dtxlint: disable=DTX001 — parked payloads carry host scalars
                        "remaining": int(payload["remaining"]),  # dtxlint: disable=DTX001 — parked payloads carry host scalars
                        "payload": encode_payload(payload)})
        return {"sessions": out, "parked": len(self._preempted)}

    def _do_drop_parked(self, cmd: dict) -> dict:
        from datatunerx_tpu.serving.migration import MIGRATED_SESSION

        want = set(cmd.get("trace_ids") or [])
        keep, dropped = [], 0
        for entry in self._preempted:
            req = entry["req"]
            if req.trace_id in want:
                dropped += 1
                self._count_preempt("spilled")
                self._event("spill", req.seq, req=req)
                self._complete(
                    req, error=f"{MIGRATED_SESSION}: parked session spilled")
            else:
                keep.append(entry)
        self._preempted = keep
        return {"dropped": dropped}

    def _do_release_parked(self, cmd: dict) -> dict:
        want = set(cmd.get("trace_ids") or [])
        released = 0
        for entry in self._preempted:
            if entry["req"].trace_id in want and entry.pop(
                    "hold_until", None) is not None:
                released += 1
        return {"released": released}

    # ------------------------------------------------- fleet prefix tier
    def _adapter_akey_name(self, akey) -> Optional[str]:
        """Cache-key adapter identity → fleet-wide NAME (dynamic pools key
        by name already; static stacks key by index). None = unmappable."""
        if isinstance(akey, str):
            return akey
        if akey == 0:
            return ""
        for n, idx in self._static_adapter_ids.items():
            if idx == akey:
                return n
        return None

    def _mount_entry_row(self, ent: dict, cursor: int):
        """Gather a COW block entry into a dense row by temporarily
        installing its blocks on a FREE slot's table (nothing reads an
        unoccupied slot's table, and it is restored before returning)."""
        slot = next((i for i in range(self.slots)
                     if self._slot_req[i] is None), None)
        if slot is None:
            raise _RetryLater("no free slot to stage a prefix export")
        w = min(-(-max(1, cursor) // DECODE_BUCKET) * DECODE_BUCKET,
                self.max_seq_len)
        with self._pool.mounted(slot, ent["blocks"]) as table:
            self._pool.set_row(slot, table)
            return self._extract(self._cache, jnp.asarray(slot, jnp.int32),
                                 jnp.asarray(cursor, jnp.int32), width=w)

    def _do_export_prefix(self, cmd: dict) -> dict:
        from datatunerx_tpu.serving import migration as mig

        if self._prefix is None:
            return {"entries": []}
        exclude = cmd.get("exclude") or set()
        limit = int(cmd.get("max_entries", 4))  # dtxlint: disable=DTX001 — mig-command args are host scalars
        wire = cmd.get("wire")
        entries: List[dict] = []
        for key, ent in self._prefix.snapshot_entries():
            if len(entries) >= limit:
                break
            ptoks, akey = key
            name = self._adapter_akey_name(akey)
            if name is None:
                continue
            fp = mig.prefix_fingerprint(name, ptoks)
            if fp in exclude:
                continue
            cursor = int(ent["cursor"])  # dtxlint: disable=DTX001 — prefix entries store host cursors
            try:
                if ent.get("blocks") is not None:
                    row = self._mount_entry_row(ent, cursor)
                else:
                    row = ent["cache"]
                entries.append({
                    "kind": mig.PREFIX_KIND,
                    "version": mig.PAYLOAD_VERSION,
                    "fingerprint": fp,
                    "adapter": name,
                    "prompt_ids": [int(t) for t in ptoks],  # dtxlint: disable=DTX001 — prefix entries store host cursors
                    "cursor": cursor,
                    "no_reuse": bool(ent.get("no_reuse", False)),
                    "logits": (None if ent.get("logits") is None
                               else mig.pack_logits(ent["logits"])),
                    "kv": mig.pack_kv_row(
                        row, cursor, wire, kv_heads=self.cfg.num_kv_heads),
                    "model_sig": mig.model_signature(self.cfg,
                                                     self.kv_quant),
                })
                self._count_mig("export_prefix", "ok")
            except Exception:  # noqa: BLE001 — publish is best-effort
                self._count_mig("export_prefix", "error")
                continue
        return {"entries": entries}

    def _do_import_prefix(self, cmd: dict) -> dict:
        from datatunerx_tpu.serving import migration as mig

        if self._prefix is None:
            raise ValueError("prefix cache disabled on this replica")
        payload = cmd["payload"]
        mig.check_prefix_signature(payload, self.cfg)
        name = payload.get("adapter") or ""
        if not name:
            akey = "" if self.adapter_registry is not None else 0
        elif self.adapter_registry is not None:
            if name not in self.adapter_registry.names():
                raise ValueError(f"unknown adapter {name!r} on this replica")
            akey = name
        elif name in self._static_adapter_ids:
            akey = self._static_adapter_ids[name]
        else:
            raise ValueError(f"unknown adapter {name!r} on this replica")
        ptoks = tuple(int(t) for t in payload["prompt_ids"])  # dtxlint: disable=DTX001 — wire payloads carry host scalars
        key = (ptoks, akey)
        if self._prefix.get(key) is not None:
            return {"imported": False, "reason": "present"}
        cursor = int(payload["cursor"])  # dtxlint: disable=DTX001 — wire payloads carry host scalars
        if not 0 < cursor < self.max_seq_len:
            raise ValueError(
                f"prefix depth {cursor} unusable in context "
                f"{self.max_seq_len}")
        row = mig.unpack_kv_row(payload["kv"], full_width=self.max_seq_len,
                                quantize=self.kv_quant)
        logits = (None if payload.get("logits") is None
                  else mig.unpack_logits(payload, self.cfg.vocab_size))
        no_reuse = bool(payload.get("no_reuse")) or logits is None
        if self.cow:
            full, rem = divmod(cursor, self.block_size)
            n_blocks = full + (1 if rem else 0)
            blocks = self._pool.take(n_blocks)
            if blocks is None:
                raise _RetryLater(
                    f"kv blocks exhausted for prefix import "
                    f"(need {n_blocks}, free {self._pool.free})")
            slot = next((i for i in range(self.slots)
                         if self._slot_req[i] is None), None)
            if slot is None:
                self._pool.free_entry(blocks)
                raise _RetryLater("no free slot to stage a prefix import")
            try:
                # the scatter installs the table on the free slot, restored
                # right after — the ENTRY owns these blocks, not a slot
                with self._pool.mounted(slot, blocks) as table:
                    self._cache = paged_insert_row(self._cache, slot, table,
                                                   row)
            except Exception:
                self._pool.free_entry(blocks)
                raise
            ent = {"blocks": blocks, "full": full, "rem": rem,
                   "cursor": cursor, "logits": logits}
        else:
            w = min(-(-max(1, cursor) // DECODE_BUCKET) * DECODE_BUCKET,
                    self.max_seq_len)
            ent = {"cache": row_trim(row, w), "logits": logits,
                   "cursor": cursor}
        if no_reuse:
            ent["no_reuse"] = True
        self._prefix.put(key, ent)
        self._count_mig("import_prefix", "ok")
        self._event("import_prefix", cursor)
        return {"imported": True, "cursor": cursor,
                "fingerprint": payload.get("fingerprint")}

    def _release_slots(self, slots: Sequence[int], note_session: bool = True):
        """Give up ``slots``, all that one pass of the scheduler ends (one,
        from every caller but emission): each slot's host state, then the
        pool's ONE program that clears their table rows, all under one
        ``dtx_engine_release`` span."""
        paged = self._pool is not None
        with self._phase("dtx_engine_release", slots=len(slots),
                         blocks=sum(len(self._pool.held(slot))
                                    for slot in slots) if paged else 0):
            for slot in slots:
                self._slot_req[slot] = None
                self._pending.pop(slot, None)
                self._decode_ready[slot] = False
                if self.spec is not None:
                    self._spec_form[slot] = False
                    self._spec_primed[slot] = False
                    self.spec_ctrl.reset_slot(slot)
                    # prune-on-release, like the slot acceptance EMAs:
                    # per-slot tree-path series never outlive the tenant
                    # that produced them
                    self._spec_tree_slot_path.pop(slot, None)
                name, self._slot_adapter[slot] = self._slot_adapter[slot], None
                if name is not None and self.adapter_registry is not None:
                    self.adapter_registry.release(name)
            if paged:
                self._pool.release(slots, note_session)

    def _vacate_slot(self, slot: int, note_session: bool = True):
        """Release a slot that is still ACTIVE on device (an export, a
        preemption: every other release follows the decode program's own
        deactivation) and clear its mask and token budget NOW: an interleaved
        decode chunk would otherwise keep sampling it and write a stale token
        through the NEXT tenant's table while that tenant still prefills."""
        self._release_slots([slot], note_session)
        self._active = self._active.at[slot].set(False)
        self._remaining = self._remaining.at[slot].set(0)

    # --------------------------------------------- overcommit: grow/preempt
    def _grow_tick(self):
        """On-demand block growth, run between prefill and decode: keep
        every decode-ready slot's table covering the lanes the next tick
        can write (cursor + one chunk/verify advance + the spec write
        overshoot). A slot the pool cannot serve — even after reclaiming
        prefix-cache entries and preempting younger sessions — parks
        ITSELF host-side, unless it is the oldest live session: the oldest
        is never preempted and always claims what reclamation frees, which
        is the forward-progress guarantee."""
        if not self.overcommit:
            return
        ready = [s for s in range(self.slots)
                 if self._decode_ready[s] and self._slot_req[s] is not None]
        if not ready:
            return
        # tiny [S]-int32 reads at the tick's designed sync point
        lens = np.asarray(self._cache["len"])  # dtxlint: disable=DTX001
        rem = np.asarray(self._remaining)  # dtxlint: disable=DTX001
        ready.sort(key=lambda s: self._slot_req[s].seq)
        for slot in ready:
            req = self._slot_req[slot]
            if req is None:
                continue  # preempted by an older slot's reclaim this pass
            advance = min(self._tick_advance, max(1, int(rem[slot])))  # dtxlint: disable=DTX001 — host numpy from this tick's sync point
            depth = int(lens[slot]) + advance  # dtxlint: disable=DTX001 — host numpy from this tick's sync point
            # None: the pool cannot cover the blocks the slot lacks
            while (grown := self._pool.grow(slot, depth)) is None:
                if self._reclaim_for(req):
                    continue
                if not self._is_oldest_live(req):
                    self._preempt_slot(slot)
                break
            if grown:
                self._event("grow", slot, grown)

    def _is_oldest_live(self, req: Request) -> bool:
        seqs = [r.seq for r in self._slot_req if r is not None]
        return bool(seqs) and req.seq == min(seqs)

    def _reclaim_for(self, req: Request) -> bool:
        """Free blocks for ``req``'s growth, cheapest casualty first:
        (1) drop an LRU prefix-cache block entry (a performance tier, not
        a session), (2) preempt the youngest strictly-younger decode
        session (it parks host-side and resumes token-exactly), (3)
        un-admit the youngest strictly-younger chunk-prefilling request
        (incomplete KV cannot export — it re-queues cold). False = nothing
        strictly younger left to give."""
        if self._prefix is not None:
            ent = self._prefix.pop_lru_block_entry()
            if ent is not None:
                self._pool.free_entry(ent["blocks"])
                return True
        victims = [s for s in range(self.slots)
                   if self._decode_ready[s]
                   and self._slot_req[s] is not None
                   and self._slot_req[s].seq > req.seq]
        victims = self._tenant_filter_victims(req, victims, self._slot_req)
        if victims:
            self._preempt_slot(
                self._pick_victim(victims, self._slot_req))
            return True
        pend = [s for s in list(self._pending)
                if self._pending[s]["req"].seq > req.seq]
        pend = self._tenant_filter_victims(
            req, pend, {s: self._pending[s]["req"] for s in pend})
        if pend:
            self._unadmit_pending(
                self._pick_victim(
                    pend, {s: self._pending[s]["req"] for s in pend}))
            return True
        return False

    def _tenant_filter_victims(self, req: Request, slots, req_of):
        """Tenancy guard on the victim pool: a BULK-tier requester may
        never preempt a pinned-tier tenant's session — pinned tenants
        paid for isolation from throughput traffic. No directory (or a
        non-bulk requester) passes the pool through untouched, keeping
        the tenancy-off preemption order byte-identical."""
        if self.tenants is None:
            return slots
        if getattr(req, "tenant_tier", "standard") != "bulk":
            return slots
        return [s for s in slots
                if getattr(req_of[s], "tenant_tier", "standard") != "pinned"]

    def _pick_victim(self, slots, req_of):
        """Which victim pays: tenancy off → youngest (the pre-tenancy
        order, exactly). Tenancy on → lowest tier first (bulk gives way
        before standard before pinned), youngest within the tier."""
        if self.tenants is None:
            return max(slots, key=lambda s: req_of[s].seq)
        from datatunerx_tpu.tenancy.directory import TIER_RANK

        return min(slots, key=lambda s: (
            TIER_RANK.get(getattr(req_of[s], "tenant_tier", "standard"), 1),
            -req_of[s].seq))

    def _preempt_slot(self, slot: int):
        """Park a decode session host-side: settle (spec), export its
        dtx-kv-session payload (raw numpy bodies — no base64 for
        in-process parking), deactivate the slot ON DEVICE, and release
        everything it held. The Request object stays live (same stream
        queue, same done event): resume re-installs the KV into a fresh
        slot and keeps pushing tokens to the same consumer, so the client
        never observes the preemption — zero re-prefill, zero drop."""
        req = self._slot_req[slot]
        if self.spec is not None and self._spec_form[slot]:
            self._spec_settle_slot(slot)
        payload = self._export_slot(slot, req, None, b64=False)
        self._vacate_slot(slot, note_session=False)
        self._preempted.append({"payload": payload, "req": req})
        self._preempted.sort(key=lambda e: e["req"].seq)
        self._count_preempt("exported")
        self._event("preempt", slot, req.seq, req=req, slot=slot)

    def _unadmit_pending(self, slot: int):
        """Roll a chunk-prefilling admission back to the cold queue: its
        KV is incomplete so it cannot export; blocks and adapter pin are
        released and the request re-queues at its FIFO position (seq
        order). It re-prefills on readmission — the only preemption
        outcome that repays work, reachable only when nothing younger is
        decoding."""
        req = self._pending[slot]["req"]
        self._release_slots([slot], note_session=False)
        self._waiting_front = collections.deque(
            sorted([*self._waiting_front, req], key=lambda r: r.seq))
        self._count_preempt("requeued_prefill")
        self._event("preempt_prefill", slot, req.seq, req=req,
                    mark="preempt", slot=slot, kind="prefill")

    def _resume_preempted_tick(self):
        """Re-admit preemption-parked sessions, oldest first, ahead of the
        cold queue (the admission gate keeps anything younger waiting, so
        strict FIFO fairness is preserved across the park). A head that
        cannot resume yet (no free slot / blocks / adapter mid-load) parks
        everything behind it until the next tick."""
        while self._preempted:
            entry = self._preempted[0]
            if entry.get("hold_until", 0.0) > time.monotonic():
                # leased to the fleet spill coordinator: hold local
                # resumption (and, via the admission gate, younger cold
                # admissions) until the spill lands or the lease expires
                return
            try:
                ok = self._resume_one(entry)
            except Exception as e:  # noqa: BLE001 — fail the session, not the loop
                self._preempted.pop(0)
                self._count_preempt("error")
                self._complete(entry["req"], error=str(e))
                continue
            if not ok:
                return
            self._preempted.pop(0)

    def _resume_one(self, entry: dict) -> bool:
        from datatunerx_tpu.serving import migration as mig

        req = entry["req"]
        payload = entry["payload"]
        slot = next((i for i in range(self.slots)
                     if self._slot_req[i] is None), None)
        if slot is None:
            return False
        name = req.adapter_name
        idx, pinned = 0, False
        if name:
            if self.adapter_registry is not None:
                acquired = self.adapter_registry.acquire(name,
                                                         count_hit=False)
                if acquired is None:
                    return False  # mid-load / pool pinned: retry next tick
                idx, pinned = acquired, True
            else:
                idx = self._static_adapter_ids.get(name, req.adapter)
        cursor = int(payload["cursor"])  # dtxlint: disable=DTX001 — parked payloads carry host scalars
        remaining = int(payload["remaining"])  # dtxlint: disable=DTX001 — parked payloads carry host scalars
        try:
            blocks = self._pool.reserve(cursor, remaining)
            if blocks is None and self._prefix is not None:
                # prefix-cache entries are the cheapest reclaim here too
                ent = self._prefix.pop_lru_block_entry()
                if ent is not None:
                    self._pool.free_entry(ent["blocks"])
                    blocks = self._pool.reserve(cursor, remaining)
            if blocks is None:
                if pinned:
                    self.adapter_registry.release(name)
                return False
            with self._pool.occupy(slot, blocks, cursor + remaining) as table:
                row = mig.unpack_kv_row(payload["kv"],
                                        full_width=self.max_seq_len,
                                        quantize=self.kv_quant)
                row_logits = mig.unpack_logits(payload, self.cfg.vocab_size)
                self._insert_row(
                    slot, table, row, row_logits, cursor,
                    self._arm_args(req, int(payload["pos"]), remaining),  # dtxlint: disable=DTX001 — parked payloads carry host scalars
                    rng=payload["rng"])
        except Exception:
            if pinned:
                self.adapter_registry.release(name)
            raise
        req.adapter = idx
        if pinned:
            self._slot_adapter[slot] = name
        self._seat(slot, req)
        self._count_preempt("resumed")
        self._event("resume", slot, cursor, req=req, slot=slot,
                    cursor=cursor)
        return True

    # ------------------------------------------------ speculative decoding
    def _spec_prime_slot(self, slot: int):
        """Prefill the slot's context (kept prompt + settled emitted tokens)
        through the DRAFT model into its per-slot draft cache row. Priming
        affects only acceptance rate — verification guarantees output
        exactness regardless — so an import re-primed from the payload's
        prompt is correct by construction."""
        req = self._slot_req[slot]
        ids = list(getattr(req, "spec_prime_ids", None) or [])
        if not ids:
            ids = list(req.prompt_ids)[-self.max_seq_len:] or \
                [self.tokenizer.eos_token_id or 0]
        toks = ids + list(req.tokens)
        n, W = len(toks), self.max_seq_len
        if n > W:
            # context can't be represented in the draft row: this slot
            # rides the plain path for its lifetime (no re-prime loop)
            self.spec_ctrl.force_off_slot(slot)
            self._spec_primed[slot] = True
            return
        padded = min(-(-n // DECODE_BUCKET) * DECODE_BUCKET, W)
        pad = padded - n
        eos = self.tokenizer.eos_token_id or 0
        sp = self.spec
        sp["dcache"] = sp["programs"].prime(
            sp["dparams"], sp["dcache"], jnp.asarray(slot, jnp.int32),
            jnp.asarray([[eos] * pad + toks], jnp.int32),
            jnp.asarray([[0] * pad + [1] * n], jnp.int32),
            jnp.asarray([[0] * pad + list(range(n))], jnp.int32),
            jnp.asarray(padded, jnp.int32))
        self._spec_primed[slot] = True
        self._event("spec_prime", slot, n)

    def _spec_settle_slot(self, slot: int):
        """Write the slot's pending token through the target (one masked
        single-token forward) so the slot returns to the standard
        logits-form state — the KV-migration wire format's contract. Every
        other row's cursor is restored inside the program."""
        if self.spec is None or not self._spec_form[slot]:
            return
        onehot = np.zeros((self.slots,), bool)
        onehot[slot] = True
        sp = self.spec
        row_logits, self._cache, self._pos = sp["programs"].settle(
            self.params, self._lora_arg(), self._cache, self._spec_pending,
            self._pos, self._adapter_idx, jnp.asarray(onehot))
        self._logits = jnp.where(jnp.asarray(onehot)[:, None], row_logits,
                                 self._logits)
        self._spec_form[slot] = False
        self._event("spec_settle", slot)

    def _batch_sample_mode(self) -> str:
        """Static per-batch sampling mode (bounded compiled variants):
        all-greedy batches verify/sample by argmax alone — no
        distributions, no full-vocab sort; top_p-free sampled batches use
        plain softmax; only genuinely filtering batches pay the exact
        sorted top-p path. Derived from host-side request params — no
        device sync."""
        live = [r for r in self._slot_req if r is not None]
        if all(r.temperature <= 0.0 for r in live):
            return "greedy"
        if any(r.top_p < 1.0 and r.temperature > 0.0 for r in live):
            return "topp"
        return "simple"

    def _epilogue_mode(self) -> str:
        """Sampling mode the fused epilogue runs this tick, or the "off"
        sentinel — the SINGLE compiled variant running the legacy argsort
        sampler, so --sampling_epilogue off traces byte-identical
        programs to a pre-epilogue build."""
        return ("off" if self.sampling_epilogue != "on"
                else self._batch_sample_mode())

    def _spec_decode_tick(self):
        """One speculative scheduler tick, replacing the plain decode chunk:
        (1) freshly-ready slots get their draft row primed and transition to
        pending form (their first token sampled exactly as the plain step
        would); (2) if the adaptive controller approves, ONE draft-propose /
        verify-k program emits up to k+1 tokens per drafting row with ragged
        per-row advance, otherwise the pending-form plain chunk program runs
        at identical per-token cost to the non-spec path. Returns
        ``(emitted [n, S] np, active [S] np)`` for the shared push/finish
        loop."""
        sp = self.spec
        progs = sp["programs"]
        out_rows = []

        fresh = [s for s in range(self.slots)
                 if self._decode_ready[s] and self._slot_req[s] is not None
                 and not self._spec_form[s]]
        if fresh:
            for slot in fresh:
                if not self._spec_primed[slot]:
                    self._spec_prime_slot(slot)
            fresh_mask = np.zeros((self.slots,), bool)
            fresh_mask[fresh] = True
            (enter_emitted, self._spec_pending, self._remaining,
             self._active, self._rng) = progs.enter(
                self._logits, self._spec_pending, self._remaining,
                self._active, self._rng, self._temps, self._top_ps,
                self._stops, jnp.asarray(fresh_mask),
                mode=self._epilogue_mode())
            for slot in fresh:
                self._spec_form[slot] = True
            # first-token emissions stream ahead of this tick's chunk
            out_rows.append(np.asarray(enter_emitted)[None, :])  # dtxlint: disable=DTX001

        # tiny [S] scalars at the tick's designed sync point: which rows are
        # worth drafting for (active, ≥2 budget left, acceptance healthy)
        active_prev = np.asarray(self._active)  # dtxlint: disable=DTX001
        rem_np = np.asarray(self._remaining)  # dtxlint: disable=DTX001
        spec_rows = np.zeros((self.slots,), bool)
        for s in range(self.slots):
            spec_rows[s] = bool(
                self._spec_form[s] and self._spec_primed[s]
                and active_prev[s] and rem_np[s] >= 2
                and self.spec_ctrl.slot_enabled(s))

        if spec_rows.any() and self.spec_ctrl.use_spec():
            plan = self.spec_ctrl.current_plan()
            # the verify math itself needs the true batch mode even when
            # the fused epilogue is off (acceptance is mode-dependent);
            # only the DRAW inside the program routes through the
            # epilogue, gated by SpecPrograms.epilogue
            mode = self._batch_sample_mode()
            margin = None
            if plan[0] == "tree":
                widths = plan[1]  # learned (or rectangular) per-depth widths
                k = len(widths)  # accepted path depth plays the chain k role
                with self._phase("dtx_engine_spec_tree"):
                    (emitted, acc, self._cache, sp["dcache"],
                     self._spec_pending, self._pos, self._remaining,
                     self._active, self._rng, margin) = progs.tree_step(
                        self.params, sp["dparams"], self._lora_arg(),
                        self._cache, sp["dcache"], self._spec_pending,
                        self._pos, self._remaining, self._active,
                        self._rng, self._temps, self._top_ps, self._stops,
                        self._adapter_idx, jnp.asarray(spec_rows),
                        widths=widths, mode=mode)
                self.spec_stats["tree_steps"] += 1
            else:
                k = plan[1]
                with self._phase("dtx_engine_spec_step"):
                    (emitted, acc, self._cache, sp["dcache"],
                     self._spec_pending, self._pos, self._remaining,
                     self._active, self._rng) = progs.step(
                        self.params, sp["dparams"], self._lora_arg(),
                        self._cache, sp["dcache"], self._spec_pending,
                        self._pos, self._remaining, self._active,
                        self._rng, self._temps, self._top_ps, self._stops,
                        self._adapter_idx, jnp.asarray(spec_rows), k=k,
                        mode=mode)
            out_rows.append(np.asarray(emitted).T)  # [k+1, S]  # dtxlint: disable=DTX001
            acc_np = np.asarray(acc)  # dtxlint: disable=DTX001
            # acc_np is host numpy already — no device sync here
            obs = [(s, int(acc_np[s]), k) for s in range(self.slots)  # dtxlint: disable=DTX001
                   if spec_rows[s] and active_prev[s]]
            self.spec_ctrl.observe(obs)
            self.spec_stats["spec_steps"] += 1
            self.spec_stats["row_steps"] += len(obs)
            for s, a, kk in obs:
                self.spec_stats["proposed"] += kk
                self.spec_stats["accepted"] += a
                if self._h_accept_len is not None:
                    self._h_accept_len.observe(a)
                if plan[0] == "tree":
                    ema_t = self._spec_tree_slot_path.get(s)
                    self._spec_tree_slot_path[s] = (
                        a * 1.0 if ema_t is None
                        else ema_t + self.spec_ctrl.alpha * (a - ema_t))
                req = self._slot_req[s]
                name = req.adapter_name if req is not None else ""
                ema = self._spec_adapter_ema.get(name)
                rate = a / kk
                # same smoothing as the controller's EMAs, so the adapter
                # gauge and the global/slot gauges agree on shared traffic
                alpha = self.spec_ctrl.alpha
                self._spec_adapter_ema[name] = (
                    rate if ema is None else ema + alpha * (rate - ema))
            if plan[0] == "tree" and obs:
                # learned-shape inputs, from data already on host: the
                # fraction of drafting rows whose accepted path reached
                # depth ≥ j+1, and the fraction whose root top-2 logit
                # margin was decisive (draft-side early-exit signal)
                widths = plan[1]
                depth_fracs = [
                    sum(1 for _, a, _ in obs if a >= j + 1) / len(obs)
                    for j in range(len(widths))]
                margin_np = np.asarray(margin)  # dtxlint: disable=DTX001 — designed sync point: the tick already host-read obs at this boundary
                dm = [float(margin_np[s]) for s, _, _ in obs]  # dtxlint: disable=DTX001 — margin_np is host (np.asarray above)
                decisive_frac = sum(
                    1 for m in dm
                    if m >= self.spec_ctrl.DECISIVE_MARGIN) / len(dm)
                self.spec_ctrl.observe_tree(depth_fracs, decisive_frac)
            if self.sampling_epilogue == "on":
                self.sampling_stats["fused_steps"] += 1
            else:
                self.sampling_stats["legacy_steps"] += 1
            self._event("spec", k, len(obs))
        else:
            emode = self._epilogue_mode()
            with self._phase("dtx_engine_decode",
                             live=sum(self._decode_ready)):
                (emitted, self._cache, self._spec_pending, self._pos,
                 self._remaining, self._active, self._rng) = progs.decode(
                    self.params, self._lora_arg(), self._cache,
                    self._spec_pending, self._pos, self._remaining,
                    self._active, self._rng, self._temps, self._top_ps,
                    self._stops, self._adapter_idx, K=self.chunk,
                    mode=emode)
            with self._phase("dtx_engine_decode_sync"):
                out_rows.append(np.asarray(emitted))  # [K, S]  # dtxlint: disable=DTX001
            self.spec_stats["plain_steps"] += 1
            self.sampling_stats["fused_steps" if emode != "off"
                                else "legacy_steps"] += 1
            self.spec_ctrl.note_plain_step()
            self._event("decode", self.chunk)

        with self._phase("dtx_engine_decode_sync"):
            active_np = np.asarray(self._active)  # dtxlint: disable=DTX001
        return np.concatenate(out_rows, axis=0), active_np

    def spec_info(self) -> Optional[dict]:
        """Speculative-decode observability document for stats()//metrics;
        None when no draft is configured."""
        if self.spec is None:
            return None
        snap = self.spec_ctrl.snapshot()
        info = {
            "enabled": True,
            "mode": self.spec_mode,
            "draft": self.spec["draft"],
            "k_max": self.spec_k,
            "k": snap["k"],
            "accept_rate": (round(snap["global_ema"], 4)
                            if snap["global_ema"] is not None else None),
            "adapter_accept_rate": {n: round(v, 4) for n, v in
                                    dict(self._spec_adapter_ema).items()},
            "slot_accept_rate": snap["slots"],
            "slots_off": snap["slots_off"],
            "active": self.spec_ctrl.use_spec(),
            "disabled_events": snap["disabled_events"],
        }
        if self.spec_tree is not None:
            plan = snap.get("plan") or []
            widths = (list(plan[1]) if len(plan) == 2 and plan[0] == "tree"
                      else [self.spec_tree.width] * self.spec_tree.depth)
            info["tree"] = {
                "spec": str(self.spec_tree),
                "width": self.spec_tree.width,
                "depth": self.spec_tree.depth,
                # per-depth plan widths (dtx_serving_spec_tree_width{depth})
                "widths": widths,
                "plan_width": widths[0] if widths else self.spec_tree.width,
                "slot_path_len": {s: round(v, 4) for s, v in
                                  dict(self._spec_tree_slot_path).items()},
            }
            for key in ("depth_ema", "decisive_ema"):
                if key in snap:
                    info["tree"][key] = snap[key]
        info["sampling_epilogue"] = self.sampling_epilogue
        info["epilogue_impl"] = self._epilogue_impl
        info.update(self.sampling_stats)
        info.update(self.spec_stats)
        return info

    def _scheduler(self):
        # every pass and every phase in it is a host span in the profiler's
        # own trace (one clock with the device ops) and a row of
        # sched_stats; the pass carries its number and the host clock at its
        # start, which ties Request.timeline's stamps to the trace's clock
        while not self._shutdown.is_set():
            self._tick_no += 1
            with self._phase("dtx_engine_tick", tick=self._tick_no,
                             t_perf=time.perf_counter()):
                try:
                    self._tick()
                except Exception as e:  # noqa: BLE001 — judged just below
                    if not self._cache_consumed():
                        raise
                    self._stop_serving(e)

    def _cache_consumed(self) -> bool:
        """True after a program that was given the cache to consume (every
        program that returns it: ``_Programs``) failed WHILE IT RAN: its
        donated leaves are deleted and nothing was returned in their place.
        An error raised while a program is traced or compiled comes before
        the call takes the buffers and leaves the cache intact."""
        return any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(self._cache))

    def _stop_serving(self, error: Exception):
        """The pool went with a failed program: every live session's KV is
        lost and the slot handlers themselves (``_release_slots`` clears
        rows of the block table) raise on the deleted leaves. Fail what is in
        flight or waiting and stop the scheduler: ``submit`` refuses from
        here on, as it does after ``close``."""
        msg = f"engine stopped, KV cache lost to a failed program: {error}"
        self._shutdown.set()
        live = [r for r in self._slot_req if r is not None]
        self._slot_req = [None] * self.slots
        while (req := self._take_waiting()) is not None:
            live.append(req)
        for req in live:
            self._complete(req, error=msg)

    def _tick(self):
        """One pass of the scheduler: admissions, at most a budget of
        prefill, then one decode chunk and the delivery of its tokens."""
        span = self._phase
        # migrations first: an imported session is already mid-decode
        # (its prefill budget was spent on the source replica), so it
        # outranks cold admissions for free slots
        with span("dtx_engine_migrate"):
            self._service_migrations()
        with span("dtx_engine_resume"):
            self._resume_preempted_tick()
        with span("dtx_engine_admit"):
            self._admit_waiting()
        self._prefill_tick()
        with span("dtx_engine_grow"):
            self._grow_tick()

        if not any(self._decode_ready):
            if self._pending:
                return  # keep prefilling; nothing to decode yet
            with span("dtx_engine_wait"):
                # starved (nothing has been asked of the engine) or held up
                # (something has, and cannot run): the two read alike from
                # outside, an idle chip
                cause = self._wait_cause()
                with (span("dtx_engine_wait_blocked", reason=cause) if cause
                      else span("dtx_engine_wait_empty")):
                    self._wake.wait(timeout=0.1)
                    self._wake.clear()
            return

        try:
            if self.spec is not None:
                emitted_np, active_np = self._spec_decode_tick()
            else:
                emode = self._epilogue_mode()
                with span("dtx_engine_decode",
                          live=sum(self._decode_ready), **self._dsa_marks()):
                    (emitted, self._logits, self._cache, self._pos,
                     self._remaining, self._active, self._rng) = \
                        self._decode(
                            self.params, self._lora_arg(), self._cache,
                            self._logits, self._pos,
                            self._remaining, self._active, self._rng,
                            self._temps, self._top_ps, self._stops,
                            self._adapter_idx, K=self.chunk,
                            mode=emode,
                        )
                self.sampling_stats["fused_steps" if emode != "off"
                                    else "legacy_steps"] += 1
                self._event("decode", self.chunk)
                # the decode loop's ONE designed sync point: K tokens per
                # chunk cross to host here so req.push can stream them
                with span("dtx_engine_decode_sync"):
                    emitted_np = np.asarray(emitted)  # [K, S]  # dtxlint: disable=DTX001
                    active_np = np.asarray(self._active)  # [S]  # dtxlint: disable=DTX001
                    if "moe_stats" in self._cache or "dsa_stats" in self._cache:
                        self._note_counters()
        except Exception as e:  # noqa: BLE001 — device fault: fail all in-flight
            if self._cache_consumed():
                raise  # the pool went with the program: _stop_serving
            for slot, req in enumerate(self._slot_req):
                if req is not None:
                    self._release_slots([slot])
                    self._complete(req, error=str(e))
            return

        with span("dtx_engine_emit"):
            # emission's two costs apart: the push loop goes with the tokens
            # of the chunk, release and complete with the requests that end
            pushed = int(np.count_nonzero(emitted_np >= 0))  # dtxlint: disable=DTX001 — host numpy since the sync above
            with span("dtx_engine_emit_push", tokens=pushed):
                for k in range(emitted_np.shape[0]):
                    for slot in range(self.slots):
                        # emitted_np is host-side numpy already — no device
                        # sync
                        t = int(emitted_np[k, slot])  # dtxlint: disable=DTX001
                        req = self._slot_req[slot]
                        if t >= 0 and req is not None:
                            req.push(t)
            # pending-prefill slots are inactive by design — only slots
            # that entered this decode chunk can finish here
            ended = [(slot, req) for slot, req in enumerate(self._slot_req)
                     if req is not None and self._decode_ready[slot]
                     and not bool(active_np[slot])]
            if ended:
                # the slots of the chunk are given up together: one program
                self._release_slots([slot for slot, _ in ended])
                for slot, req in ended:
                    self._event("finish", slot, req=req, slot=slot)
                    self._complete(req)

    # ---------------------------------------------------------------- API
    def submit(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 128,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        stop_ids: Optional[set] = None,
        adapter: str = "",
        trace_id: str = "",
        tenant: str = "",
    ) -> Request:
        known = self.adapter_ids
        if adapter not in known:
            raise KeyError(
                f"unknown adapter {adapter!r}; loaded: "
                f"{sorted(n for n in known if n)}"
            )
        # device index: fixed at submit for the static stack; dynamic-mode
        # names resolve (and pin) at ADMISSION — a resident slot seen here
        # could be evicted before the request reaches a cache slot
        idx = known[adapter] if self.adapter_registry is None else 0
        with self._adapter_req_lock:
            if (adapter in self.adapter_requests
                    or len(self.adapter_requests)
                    < self._adapter_requests_cap):
                self.adapter_requests[adapter] = \
                    self.adapter_requests.get(adapter, 0) + 1
        # tenancy: resolve the request's tenant (explicit name wins, else
        # the adapter maps through the directory); an unknown/absent tenant
        # stays anonymous and schedules exactly like a pre-tenancy request
        tenant_name, tier = "", "standard"
        if self.tenants is not None:
            spec = self.tenants.resolve(tenant=tenant, adapter=adapter)
            if spec is not None:
                tenant_name, tier = spec.name, spec.tier
            self._tenant_count(tenant_name or (tenant or ""),
                               "requests", 1)
            self._tenant_count(tenant_name or (tenant or ""),
                               "tokens_in", len(prompt_ids))
        stops = {int(s) for s in (stop_ids or set())}
        stops.add(int(self.tokenizer.eos_token_id))
        # every request gets a trace id (callers without one — bench, bare
        # generate() — still get a /debug/trace timeline); the gateway's
        # X-DTX-Trace-Id arrives here via serving/server.py or
        # InProcessReplica so one id follows the request end to end
        req = Request(prompt_ids, max_new_tokens, temperature, top_p, seed,
                      sorted(stops), idx, adapter_name=adapter,
                      trace_id=trace_id or f"dtx-{uuid.uuid4().hex[:16]}",
                      tenant=tenant_name or (tenant or ""),
                      tenant_tier=tier)
        if self._shutdown.is_set():
            raise RuntimeError("engine is shut down")
        req.tick_submit = self._tick_no
        self._waiting.put(req)
        self._wake.set()
        return req

    def generate(self, prompt_ids, timeout: float = 300.0, **kw) -> List[int]:
        req = self.submit(prompt_ids, **kw)
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if req.error:
            raise RuntimeError(req.error)
        return req.tokens

    def _encode_chat(self, messages: List[dict]):
        import json

        from datatunerx_tpu.serving.engine import encode_chat_messages

        # tiny LRU keyed by the serialized messages: usage reporting (the
        # serving response's prompt_tokens) and the in-process replica's
        # calibration feedback re-encode the prompt a request already
        # encoded — memoizing makes the count a dict hit instead of a
        # second O(prompt) tokenizer pass on the serving hot path
        try:
            key = json.dumps(messages, sort_keys=True)
        except (TypeError, ValueError):
            return encode_chat_messages(self.template, self.tokenizer,
                                        messages)
        with self._encode_memo_lock:
            hit = self._encode_memo.get(key)
            if hit is not None:
                self._encode_memo.move_to_end(key)
                return hit
        out = encode_chat_messages(self.template, self.tokenizer, messages)
        with self._encode_memo_lock:
            self._encode_memo[key] = out
            self._encode_memo.move_to_end(key)
            while len(self._encode_memo) > 32:
                self._encode_memo.popitem(last=False)
        return out

    def perplexity(self, prompt_ids: Sequence[int],
                   completion_ids: Sequence[int], adapter: str = "") -> dict:
        """Mean completion NLL under the (optionally adapter-indexed) model —
        the unmerged stack scores through the same lora_idx path decode uses."""
        from datatunerx_tpu.serving.engine import (
            nll_impl,
            nll_result,
            prepare_nll_inputs,
        )

        if adapter not in self.adapter_ids:
            raise KeyError(f"unknown adapter {adapter!r}")
        if not hasattr(self, "_nll"):
            def impl(params, lora, tokens, mask, aidx):
                return nll_impl(
                    params, self.cfg, tokens, mask, lora=lora,
                    lora_adapter_idx=(aidx[None] if lora is not None
                                      else None),
                )

            self._nll = jax.jit(impl)
        tokens, mask, _ = prepare_nll_inputs(
            list(prompt_ids), list(completion_ids),
            self.tokenizer.eos_token_id, self.max_seq_len,
        )
        # dynamic mode: pin the adapter across the scoring forward so LRU
        # eviction can't swap its weights out mid-read (load-on-miss runs
        # here too — scoring a cold adapter warms it for serving)
        pinned = False
        if self.adapter_registry is not None and adapter:
            # blocking acquire: scoring runs on a caller thread, so it can
            # afford to wait out a load-on-miss (which also warms the
            # adapter for serving)
            idx = self.adapter_registry.acquire(adapter, wait=True)
            if idx is None:
                raise RuntimeError(
                    "adapter pool exhausted (all slots pinned); retry")
            pinned = True
        else:
            idx = self.adapter_ids[adapter]
        try:
            nll_sum, n_tok = self._nll(
                self.params, self._lora_arg(), tokens, mask,
                jnp.asarray(idx, jnp.int32),
            )
            return nll_result(float(nll_sum), int(n_tok))
        finally:
            if pinned:
                self.adapter_registry.release(adapter)

    def chat(self, messages: List[dict], max_new_tokens: int = 128,
             temperature: float = 0.0, top_p: float = 1.0, seed: int = 0,
             adapter: str = "", trace_id: str = "",
             tenant: str = "") -> str:
        prompt_ids, stop_ids = self._encode_chat(messages)
        out = self.generate(prompt_ids, max_new_tokens=max_new_tokens,
                            temperature=temperature, top_p=top_p, seed=seed,
                            stop_ids=stop_ids, adapter=adapter,
                            trace_id=trace_id, tenant=tenant)
        return self.tokenizer.decode(out, skip_special_tokens=True)

    def chat_stream(self, messages: List[dict], max_new_tokens: int = 128,
                    temperature: float = 0.0, top_p: float = 1.0,
                    seed: int = 0, adapter: str = "", trace_id: str = "",
                    tenant: str = ""):
        """Yields text deltas as tokens stream off the decode chunks."""
        prompt_ids, stop_ids = self._encode_chat(messages)
        req = self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                          temperature=temperature, top_p=top_p, seed=seed,
                          stop_ids=stop_ids, adapter=adapter,
                          trace_id=trace_id, tenant=tenant)
        yield from self._stream_text(req, [])

    def close(self):
        self._shutdown.set()
        self._wake.set()
        self._thread.join(timeout=10)
        if self.adapter_registry is not None:
            # scheduler is down; reap any in-flight async loader threads
            self.adapter_registry.close()
        # fail any migration commands the scheduler will never service so
        # their callers don't sit out the full wait timeout (the scheduler
        # thread is joined above — nothing else touches the retry list now)
        pending = list(self._mig_retry)
        self._mig_retry = []  # dtxlint: disable=DTX006 — owner thread already joined
        while True:
            try:
                pending.append(self._mig_q.get_nowait())
            except queue.Empty:
                break
        for cmd in pending:
            cmd["_error"] = "engine shut down"
            cmd["_refused"] = False
            cmd["_done"].set()
        # preemption-parked sessions can never resume now — fail their
        # requests so consumers don't sit out their full wait timeout
        parked = list(self._preempted)
        self._preempted = []  # dtxlint: disable=DTX006 — owner thread already joined
        for entry in parked:
            entry["req"].finish(error="engine shut down")
