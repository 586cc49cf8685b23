"""Serving HTTP server: OpenAI-ish ``/chat/completions`` + health gating.

Endpoint contract matches what the reference pipeline consumes
(reference finetunejob_controller.go:433 builds
``http://<svc>:8000/chat/completions``; the Scoring operator POSTs there).
Health semantics replace KubeRay's application-level HEALTHY gate
(finetunejob_controller.go:423-424): ``/healthz`` returns 503 until the model
is fully loaded, then 200 — so a k8s readinessProbe gives the same
"model actually loaded" guarantee.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from datatunerx_tpu.obs.metrics import (
    Registry,
    adapter_load_histogram,
    exemplars_requested,
    export_moe_stats,
    export_sched_stats,
    serving_latency_histograms,
    spec_accept_len_histogram,
    set_build_info,
    set_uptime,
)
from datatunerx_tpu.obs.slo import SLOEvaluator, default_slos
from datatunerx_tpu.serving import options


class ServingState:
    def __init__(self):
        self.engine = None
        self.error: Optional[str] = None
        self.model_path = ""
        # platform / device_kind / count the process runs on (set by main()
        # from utils.runtime.require_backend) — carried in the /healthz body
        # so a caller outside can tell a chip replica from a CPU one
        self.device: dict = {}
        # () -> {"requests", "hits"} of the persistent compile cache; set by
        # main() (the module itself stays importable without jax)
        self.compile_cache_stats = None
        # disaggregation role this replica declares to the fleet:
        # "prefill" (long-prompt specialist), "decode", or "mixed" (the
        # default — role-less routing, byte-identical to older fleets).
        # Surfaced as dtx_serving_role{role=...}; the gateway's
        # HTTPReplica scrape keeps its routing view in sync.
        self.role = "mixed"
        # the server's ONE registry: engine latency histograms record into
        # it (load_engine_async passes it down) and every scrape-time gauge
        # is re-stated into it, so /metrics is a single exposition
        self.registry = Registry()
        self.started_at = time.monotonic()
        # serializes scrape-time gauge restating (concurrent scrapes would
        # race clear/set on the labeled counters)
        self.scrape_lock = threading.Lock()
        # SLO evaluator over this registry (obs/slo.py) — built lazily so
        # tests driving the Handler directly get a working /debug/slo, and
        # main() can install a --slo_config set before the first request
        self.slo: Optional[SLOEvaluator] = None
        self.slo_lock = threading.Lock()


STATE = ServingState()

# ceiling on slot-labeled series per family in the exposition: slots are a
# small fixed pool, so this never binds on a healthy engine — it is a guard
# against unbounded label cardinality if a spec_info document goes wrong
_SLOT_SERIES_CAP = 1024


def slo_evaluator() -> SLOEvaluator:
    """The server's evaluator, created on first use with the default
    serving objectives unless main() already installed a configured one."""
    with STATE.slo_lock:
        if STATE.slo is None:
            STATE.slo = SLOEvaluator(STATE.registry, default_slos("serving"))
        return STATE.slo


def metrics_text(with_exemplars: bool = True) -> str:
    """The /metrics body: scrape-time gauges re-stated into the shared
    registry next to the engine's live histograms. Factored off the HTTP
    handler so scripts/metrics_lint.py validates the same bytes a scraper
    sees. The HTTP wire defaults to with_exemplars=False (classic-parser
    safety); ``/metrics?exemplars=1`` opts in."""
    with STATE.scrape_lock:
        return _metrics_text_locked(with_exemplars)


def _metrics_text_locked(with_exemplars: bool = True) -> str:
    reg = STATE.registry
    eng = STATE.engine
    set_build_info(reg, "serving")
    set_uptime(reg, "serving", STATE.started_at)
    # dtx_slo_* verdict gauges: sample FIRST so window baselines advance
    # under scrape-only deployments (no /debug/slo poller, no sampler)
    ev = slo_evaluator()
    ev.sample()
    ev.restate_gauges(ev.evaluate())
    # declare the serving latency histograms even before the engine loads:
    # a scraper sees stable series from the first scrape (zero counts), and
    # an engine sharing this registry observes into these same objects
    # (one declaration site in obs.metrics — help text cannot diverge)
    serving_latency_histograms(reg)
    reg.gauge("dtx_serving_up", "1 once the model is fully loaded.").set(
        1 if eng is not None else 0)
    stats = getattr(eng, "prefill_stats", None)
    pf = reg.counter("dtx_serving_prefill_total",
                     "Admissions by prefill kind (full/reuse/extend).")
    # engine-derived series are re-stated per scrape — cleared first so a
    # swapped/reloaded engine can't leave stale samples behind
    hits = reg.counter("dtx_serving_prefix_cache_hits_total",
                       "Exact prefix-cache hits (prefill skipped).")
    partial = reg.counter("dtx_serving_prefix_cache_partial_hits_total",
                          "Strict-prefix hits (suffix-only prefill).")
    misses = reg.counter("dtx_serving_prefix_cache_misses_total",
                         "Full prefills.")
    evictions = reg.counter("dtx_serving_prefix_cache_evictions_total",
                            "Prefix-cache LRU evictions.")
    for c in (pf, hits, partial, misses, evictions):
        c.clear()
    if stats is not None:
        for kind, n in sorted(stats.items()):
            pf.set(n, {"kind": kind})
        # hit = exact reuse, partial = suffix extension, miss = full;
        # .get so a partially-populated stats dict (engine mid-init or a
        # duck-typed test engine) can't 500 the scrape
        hits.set(stats.get("reuse", 0))
        partial.set(stats.get("extend", 0))
        misses.set(stats.get("full", 0))
    # what the cache gave admissions, in prompt tokens (engine.prefix_stats)
    given = {key: reg.counter(f"dtx_serving_prefix_{name}_total", text)
             for key, name, text in (
        ("shared_tokens", "shared_tokens",
         "Prompt tokens admissions took from the prefix cache (shared "
         "blocks mapped, a cached row inserted) instead of prefilling."),
        ("prefilled_tokens", "prefilled_tokens",
         "Prompt tokens admissions prefilled (whole cold prompts and the "
         "suffixes of strict-prefix hits)."),
        ("blocks_reclaimed_at_admission", "blocks_reclaimed",
         "KV blocks freed from idle prefix-cache entries for a waiting "
         "request the pool could not otherwise admit."))}
    prefix_stats = getattr(eng, "prefix_stats", None)
    for key, c in given.items():
        c.clear()
        if prefix_stats is not None:
            c.set(prefix_stats.get(key, 0))
    prefix = getattr(eng, "_prefix", None)
    entries = reg.gauge("dtx_serving_prefix_cache_entries",
                        "Live prefix-cache entries.")
    entries.clear()
    if prefix is not None:
        entries.set(len(prefix))
        evictions.set(prefix.evictions)
    slots_busy = reg.gauge("dtx_serving_slots_busy",
                           "Cache slots holding an in-flight request.")
    # _capacity, not _total: the Prometheus _total suffix is reserved for
    # counters, and these are gauges (PR 7 naming unification — the old
    # dtx_serving_{slots,kv_blocks}_total names are gone; the gateway's
    # scrape parser accepts both during a rolling upgrade)
    slots_total = reg.gauge("dtx_serving_slots_capacity",
                            "Configured cache slots.")
    slots_busy.clear()
    slots_total.clear()
    if eng is not None and hasattr(eng, "_slot_req"):
        slots_busy.set(sum(1 for r in eng._slot_req if r is not None))
        slots_total.set(eng.slots)
    # paged KV cache: FREE BLOCKS are the real admission headroom (the
    # gateway prefers this gauge over free slots — a slot is cheap, the
    # blocks behind it are not)
    blocks_free = reg.gauge("dtx_serving_kv_blocks_free",
                            "Free paged KV-cache blocks.")
    blocks_total = reg.gauge("dtx_serving_kv_blocks_capacity",
                             "Total paged KV-cache blocks.")
    blocks_reserved = reg.gauge("dtx_serving_kv_blocks_reserved",
                                "Allocated paged KV-cache blocks (slots' "
                                "tables + COW prefix-cache entries).")
    block_size_g = reg.gauge("dtx_serving_kv_block_size",
                             "Tokens per paged KV block — the unit the "
                             "gateway's fleet-true admission prices "
                             "admits in.")
    over_ratio = reg.gauge("dtx_serving_kv_overcommit_ratio",
                           "Live sessions' eager-equivalent block demand "
                           "over the physical pool (> 1 = overcommitted; "
                           "only meaningful with --kv_overcommit on).")
    preempt = reg.counter("dtx_serving_preemptions_total",
                          "KV-overcommit preemptions by outcome (exported "
                          "= session parked host-side, resumed = parked "
                          "session re-admitted token-exactly, "
                          "requeued_prefill = mid-prefill admission "
                          "rolled back to the cold queue).")
    blocks_free.clear()
    blocks_total.clear()
    blocks_reserved.clear()
    block_size_g.clear()
    over_ratio.clear()
    preempt.clear()
    if getattr(eng, "total_kv_blocks", None):
        blocks_free.set(eng.free_kv_blocks)
        blocks_total.set(eng.total_kv_blocks)
        reserved = getattr(eng, "kv_blocks_reserved", None)
        if reserved is None:
            reserved = eng.total_kv_blocks - eng.free_kv_blocks
        blocks_reserved.set(reserved)
        block_size_g.set(getattr(eng, "block_size", 0) or 0)
        ratio = getattr(eng, "kv_overcommit_ratio", None)
        if ratio is not None:
            over_ratio.set(ratio)
    pstats = getattr(eng, "preempt_stats", None)
    if isinstance(pstats, dict):
        for outcome, np_ in sorted(pstats.items()):
            preempt.set(np_, {"outcome": outcome})
    # disaggregated fleet plane: the role this replica declares (one-hot
    # label the gateway's role-aware routing scrapes) and the parked-
    # session backlog the fleet spill coordinator treats as work
    role_g = reg.gauge("dtx_serving_role",
                       "Replica disaggregation role, one-hot by label "
                       "(prefill / decode / mixed).")
    parked_g = reg.gauge("dtx_serving_sessions_parked",
                         "Preemption-parked sessions awaiting resume — "
                         "the fleet spill coordinator's work signal.")
    role_g.clear()
    role_g.set(1, {"role": STATE.role})
    parked_g.set(int(getattr(eng, "parked_sessions", 0) or 0))
    # dynamic adapter pool (datatunerx_tpu/adapters/): occupancy, the
    # residency set the gateway's cache-locality routing scrapes, and
    # per-adapter traffic. Declared/cleared on every scrape so a swapped
    # engine or an unloaded adapter can't leave stale series behind.
    adapter_load_histogram(reg)  # stable series even pre-engine-load
    pool_cap = reg.gauge("dtx_serving_adapter_pool_slots_capacity",
                         "Adapter pool slots (loadable adapters resident "
                         "at once; the base model is not a slot).")
    pool_free = reg.gauge("dtx_serving_adapter_pool_slots_free",
                          "Adapter pool slots holding no adapter.")
    resident_g = reg.gauge("dtx_serving_adapter_resident",
                           "1 per adapter resident in the pool "
                           "(load-on-miss already paid).")
    registered_g = reg.gauge("dtx_serving_adapter_registered",
                             "1 per adapter this replica can serve "
                             "(resident or loadable on miss).")
    a_loads = reg.counter("dtx_serving_adapter_loads_total",
                          "Adapters materialised into pool slots "
                          "(checkpoint load + device insert).")
    a_evict = reg.counter("dtx_serving_adapter_evictions_total",
                          "Unpinned residents LRU-evicted to make room.")
    a_hits = reg.counter("dtx_serving_adapter_hits_total",
                         "Admissions whose adapter was already resident.")
    a_miss = reg.counter("dtx_serving_adapter_misses_total",
                         "Admissions that had to load their adapter.")
    a_reqs = reg.counter("dtx_serving_adapter_requests_total",
                         "Requests per adapter name ('' = base model).")
    for m in (pool_cap, pool_free, resident_g, registered_g, a_loads,
              a_evict, a_hits, a_miss, a_reqs):
        m.clear()
    occ_fn = getattr(eng, "adapter_occupancy", None)
    occ = occ_fn() if callable(occ_fn) else None
    if occ:
        pool_cap.set(occ.get("slots", 0))
        pool_free.set(occ.get("free", 0))
        for name in occ.get("resident_adapters") or []:
            resident_g.set(1, {"adapter": name})
        for name in occ.get("registered_adapters") or []:
            registered_g.set(1, {"adapter": name})
        a_loads.set(occ.get("loads", 0))
        a_evict.set(occ.get("evictions", 0))
        a_hits.set(occ.get("hits", 0))
        a_miss.set(occ.get("misses", 0))
    # speculative decoding: proposal/acceptance counters + the acceptance-
    # rate EMAs (global, per adapter, per slot) the gateway's spec-friendly
    # routing reads. Declared every scrape (stable zero series on non-spec
    # engines), restated from the engine's spec_info document.
    spec_accept_len_histogram(reg)  # engine observes into this same object
    sp_enabled = reg.gauge("dtx_serving_spec_enabled",
                           "1 when speculative decoding is configured "
                           "(a draft model is loaded).")
    sp_active = reg.gauge("dtx_serving_spec_active",
                          "1 while the adaptive controller is actually "
                          "drafting (0 = fallen back to plain decode).")
    sp_k = reg.gauge("dtx_serving_spec_k",
                     "Current proposal depth k (adaptive, <= --spec_k).")
    sp_rate = reg.gauge("dtx_serving_spec_accept_rate",
                        "Global acceptance-rate EMA (accepted/proposed "
                        "per verify step).")
    sp_rate_adapter = reg.gauge("dtx_serving_spec_adapter_accept_rate",
                                "Acceptance-rate EMA per adapter name "
                                "('' = base model).")
    sp_rate_slot = reg.gauge("dtx_serving_spec_slot_accept_rate",
                             "Acceptance-rate EMA per live cache slot.")
    sp_prop = reg.counter("dtx_serving_spec_proposed_total",
                          "Draft tokens proposed to the verifier.")
    sp_acc = reg.counter("dtx_serving_spec_accepted_total",
                         "Proposed tokens the target accepted.")
    sp_steps = reg.counter("dtx_serving_spec_steps_total",
                           "Decode programs run by path (spec = draft/"
                           "verify, plain = pending-form fallback).")
    # tree-draft families: declared every scrape like the rest (stable
    # zeros on chain-only engines), restated from spec_info()["tree"]
    sp_tree_steps = reg.counter("dtx_serving_spec_tree_steps_total",
                                "Verify steps that ran the tree-draft "
                                "program (vs chain draft/verify).")
    sp_tree_width = reg.gauge("dtx_serving_spec_tree_width",
                              "Current tree branch width per draft depth "
                              "(learned/adaptive, <= the --spec_tree W; "
                              "label depth is 1-based).")
    sp_tree_depth = reg.gauge("dtx_serving_spec_tree_depth",
                              "Configured tree draft depth D (0 = chain "
                              "drafts).")
    sp_tree_path = reg.gauge("dtx_serving_spec_tree_slot_path_len",
                             "Accepted root-to-leaf path length EMA per "
                             "live cache slot.")
    # fused sampling epilogue (ops/pallas_sampling.py): resolved mode +
    # decode ticks by sampler path — the epilogue-on/off bench twin reads
    # these to prove which path actually ran
    sp_epilogue = reg.gauge("dtx_serving_sampling_epilogue",
                            "Fused sampling epilogue state: 0 = off "
                            "(legacy host sampler), 1 = on via the XLA "
                            "oracle, 2 = on via the Pallas kernel.")
    sp_fused = reg.counter("dtx_serving_sampling_fused_steps_total",
                           "Decode/spec ticks by sampler path (fused = "
                           "on-chip epilogue, legacy = host argsort).")
    for m in (sp_enabled, sp_active, sp_k, sp_rate, sp_rate_adapter,
              sp_rate_slot, sp_prop, sp_acc, sp_steps, sp_tree_steps,
              sp_tree_width, sp_tree_depth, sp_tree_path, sp_epilogue,
              sp_fused):
        m.clear()
    spec_fn = getattr(eng, "spec_info", None)
    spec_doc = spec_fn() if callable(spec_fn) else None
    sp_enabled.set(1 if spec_doc else 0)
    if spec_doc:
        sp_active.set(1 if spec_doc.get("active") else 0)
        sp_k.set(spec_doc.get("k", 0))
        if spec_doc.get("accept_rate") is not None:
            sp_rate.set(spec_doc["accept_rate"])
        for name, v in sorted(
                (spec_doc.get("adapter_accept_rate") or {}).items()):
            sp_rate_adapter.set(v, {"adapter": name})
        # per-slot series are pruned on slot release engine-side; the cap
        # here bounds exposition cardinality even if an engine misbehaves
        for slot, v in sorted(
                (spec_doc.get("slot_accept_rate") or {}).items()
                )[:_SLOT_SERIES_CAP]:
            sp_rate_slot.set(v, {"slot": str(slot)})
        sp_prop.set(spec_doc.get("proposed", 0))
        sp_acc.set(spec_doc.get("accepted", 0))
        sp_steps.set(spec_doc.get("spec_steps", 0), {"path": "spec"})
        sp_steps.set(spec_doc.get("plain_steps", 0), {"path": "plain"})
        sp_tree_steps.set(spec_doc.get("tree_steps", 0))
        tree_doc = spec_doc.get("tree")
        if tree_doc:
            widths = (tree_doc.get("widths") or
                      [tree_doc.get("plan_width", 0)])
            for j, w in enumerate(widths):
                sp_tree_width.set(w, {"depth": str(j + 1)})
            sp_tree_depth.set(tree_doc.get("depth", 0))
            for slot, v in sorted(
                    (tree_doc.get("slot_path_len") or {}).items()
                    )[:_SLOT_SERIES_CAP]:
                sp_tree_path.set(v, {"slot": str(slot)})
    # the fused epilogue runs in plain decode too, spec or not — restate
    # from the engine, not the spec document
    impl = getattr(eng, "_epilogue_impl", "off")
    sp_epilogue.set({"off": 0, "xla": 1, "kernel": 2}.get(impl, 0))
    samp_stats = getattr(eng, "sampling_stats", None)
    if isinstance(samp_stats, dict):
        sp_fused.set(samp_stats.get("fused_steps", 0), {"path": "fused"})
        sp_fused.set(samp_stats.get("legacy_steps", 0), {"path": "legacy"})
    export_moe_stats(reg, eng)
    export_sched_stats(reg, eng)
    gen_toks = reg.counter("dtx_serving_generated_tokens_total",
                           "Tokens emitted to finished requests.")
    path_g = reg.gauge("dtx_serving_decode_path",
                       "How each attending layer kind's token step reads "
                       "the KV cache, one-hot by label (pallas = in-place "
                       "block-table kernel, gather = paged XLA oracle, "
                       "dense).")
    window_g = reg.gauge("dtx_serving_decode_window",
                         "Lanes of sliding window the paged decode kernel "
                         "was built with; absent where it has none (no "
                         "kernel, no window, or one the cache cannot "
                         "exceed, which is dropped).")
    gen_toks.clear()
    path_g.clear()
    window_g.clear()
    if getattr(eng, "generated_tokens", None) is not None:
        gen_toks.set(eng.generated_tokens)
    for kind, path in (getattr(eng, "decode_paths", None) or {}).items():
        path_g.set(1, {"kind": kind, "path": path})
    if getattr(eng, "decode_window", None):
        window_g.set(eng.decode_window)
    # persistent compile cache (utils/runtime.py): a replica that started
    # against a warm cache shows hits == requests
    cc_req = reg.counter("dtx_serving_compile_cache_requests_total",
                         "Compiles that consulted the persistent cache.")
    cc_hit = reg.counter("dtx_serving_compile_cache_hits_total",
                         "Compiles served from the persistent cache.")
    cc = STATE.compile_cache_stats() if STATE.compile_cache_stats else {}
    cc_req.set(cc.get("requests", 0))
    cc_hit.set(cc.get("hits", 0))
    # KV migration fabric: session export/import outcomes (restated from
    # the engine's scheduler-thread counters, cleared first like the rest)
    s_exp = reg.counter("dtx_serving_session_export_total",
                        "Live decode sessions exported for replica-to-"
                        "replica handoff, by outcome.")
    s_imp = reg.counter("dtx_serving_session_import_total",
                        "Exported sessions imported (re-prefill-free "
                        "resume), by outcome.")
    s_exp.clear()
    s_imp.clear()
    sess_stats = getattr(eng, "session_stats", None)
    if isinstance(sess_stats, dict):
        for outcome, n in sorted((sess_stats.get("export") or {}).items()):
            s_exp.set(n, {"outcome": outcome})
        for outcome, n in sorted((sess_stats.get("import") or {}).items()):
            s_imp.set(n, {"outcome": outcome})
    # multi-tenant QoS plane: per-tenant usage + the host-RAM adapter
    # tier's load split. BOTH families are created only when their plane
    # is configured — a tenancy-less engine's scrape must stay
    # byte-identical (the PR 15/16 gating contract).
    usage_fn = getattr(eng, "tenant_usage", None)
    usage = usage_fn() if callable(usage_fn) else None
    if usage is not None:
        t_reqs = reg.counter("dtx_serving_tenant_requests_total",
                             "Requests per tenant ('' = anonymous).")
        t_toks = reg.counter("dtx_serving_tenant_tokens_total",
                             "Tokens per tenant by direction (in = "
                             "prompt, out = generated).")
        t_blocks = reg.gauge("dtx_serving_tenant_kv_blocks",
                             "Live paged KV blocks held by the tenant's "
                             "in-flight sessions.")
        t_res = reg.gauge("dtx_serving_tenant_adapters_resident",
                          "The tenant's adapters currently resident in "
                          "the pool.")
        t_tier = reg.gauge("dtx_serving_tenant_tier",
                           "Tenant tier, one-hot by label "
                           "(pinned / standard / bulk).")
        for m in (t_reqs, t_toks, t_blocks, t_res, t_tier):
            m.clear()
        for tname, row in sorted(usage.items()):
            lbl = {"tenant": tname}
            t_reqs.set(row.get("requests", 0), lbl)
            t_toks.set(row.get("tokens_in", 0),
                       {"tenant": tname, "direction": "in"})
            t_toks.set(row.get("tokens_out", 0),
                       {"tenant": tname, "direction": "out"})
            if "kv_blocks" in row:
                t_blocks.set(row["kv_blocks"], lbl)
            if "adapters_resident" in row:
                t_res.set(row["adapters_resident"], lbl)
            if row.get("tier"):
                t_tier.set(1, {"tenant": tname, "tier": row["tier"]})
    host_fn = getattr(getattr(eng, "adapter_registry", None),
                      "host_tier_stats", None)
    host = host_fn() if callable(host_fn) else None
    if host is not None:
        h_hits = reg.counter("dtx_serving_adapter_host_hits_total",
                             "Adapter loads served from the host-RAM "
                             "tier (no orbax read).")
        h_orbax = reg.counter("dtx_serving_adapter_orbax_loads_total",
                              "Adapter loads that paid the orbax "
                              "checkpoint read.")
        h_evict = reg.counter("dtx_serving_adapter_host_evictions_total",
                              "Host-tier entries evicted to fit newer "
                              "weights under the byte budget.")
        h_bytes = reg.gauge("dtx_serving_adapter_host_bytes",
                            "Bytes of adapter weights cached in the "
                            "host-RAM tier.")
        h_entries = reg.gauge("dtx_serving_adapter_host_entries",
                              "Adapters cached in the host-RAM tier.")
        for m in (h_hits, h_orbax, h_evict, h_bytes, h_entries):
            m.clear()
        h_hits.set(host.get("host_hits", 0))
        h_orbax.set(host.get("orbax_loads", 0))
        h_evict.set(host.get("evictions", 0))
        h_bytes.set(host.get("bytes", 0))
        h_entries.set(host.get("entries", 0))
    # per-adapter demand: prefer the occupancy doc's LOCK-GUARDED copy
    # (dynamic engines); static engines snapshot under the engine's own
    # lock — copying the live dict bare would race a concurrent submit
    reqs = (occ or {}).get("requests")
    if reqs is None:
        raw = getattr(eng, "adapter_requests", None)
        if raw:
            lock = getattr(eng, "_adapter_req_lock", None)
            if lock is not None:
                with lock:
                    reqs = dict(raw)
            else:
                reqs = dict(raw)
    for name, n in sorted((reqs or {}).items()):
        a_reqs.set(n, {"adapter": name})
    return reg.expose(with_exemplars=with_exemplars)


class Handler(BaseHTTPRequestHandler):
    def _json(self, code: int, payload: dict):
        # count BEFORE the body goes out so a scrape racing the response
        # can't miss its own request (gateway/server.py does the same)
        self._record(code)
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        # echo the gateway's trace id so one id follows a request
        # operator → gateway → replica (gateway/server.py generates it)
        trace = self.headers.get("X-DTX-Trace-Id")
        if trace:
            self.send_header("X-DTX-Trace-Id", trace)
        self.end_headers()
        self.wfile.write(body)

    def _record(self, code: int):
        STATE.registry.counter(
            "dtx_serving_requests_total",
            "Requests by terminal HTTP code (gateway-parity naming).").inc(
            {"code": str(code)})

    def do_GET(self):
        if self.path == "/healthz":
            if STATE.engine is not None:
                self._json(200, {"status": "HEALTHY",
                                 "model": STATE.model_path, **STATE.device})
            elif STATE.error:
                self._json(500, {"status": "FAILED", "error": STATE.error,
                                 **STATE.device})
            else:
                self._json(503, {"status": "LOADING", **STATE.device})
        elif self.path == "/v1/models":
            self._json(200, {"object": "list", "data": [
                {"id": STATE.model_path, "object": "model"}]})
        elif self.path.split("?")[0] == "/metrics":
            self._metrics()
        elif self.path == "/admin/adapters":
            self._adapters_get()
        elif self.path == "/debug/slo":
            # same evaluator/report shape as the gateway's /debug/slo —
            # obs/slo.py is the single verdict implementation
            self._json(200, slo_evaluator().report(plane="serving"))
        elif self.path.startswith("/debug/trace/"):
            self._debug_trace(self.path[len("/debug/trace/"):])
        else:
            self._json(404, {"error": "not found"})

    # ------------------------------------------------- dynamic adapter plane
    def _adapters_get(self):
        """The replica's adapter inventory: registered names, resident set,
        pool occupancy + load/evict/hit/miss stats. 501 on engines without
        a dynamic pool (static --adapters stacks still report their fixed
        names)."""
        eng = STATE.engine
        if eng is None:
            self._json(503, {"error": "model not loaded"})
            return
        occ_fn = getattr(eng, "adapter_occupancy", None)
        occ = occ_fn() if callable(occ_fn) else None
        if occ is None:
            ids = getattr(eng, "adapter_ids", None)
            self._json(200, {
                "dynamic": False,
                "registered": sorted(n for n in (ids or {}) if n),
                "resident": sorted(n for n in (ids or {}) if n),
            })
            return
        catalog_fn = getattr(eng, "adapter_catalog", None)
        self._json(200, {
            "dynamic": True,
            "registered": occ.pop("registered_adapters", []),
            "resident": occ.pop("resident_adapters", []),
            # name → checkpoint: what a replacement replica needs to
            # rebuild this warm set (ManagedReplicaSet drain inheritance)
            "checkpoints": (catalog_fn() if callable(catalog_fn) else {}),
            "pool": occ,
        })

    def _adapters_post(self, req: dict):
        """POST /admin/adapters {"name": n, "checkpoint": path[, "load":
        bool]} — register a tenant adapter at runtime; by default the
        weights are warmed into a pool slot immediately so the first
        request is a residency hit. 400 on geometry violations (rank >
        rank_max, foreign targets), 409 on a live-name conflict, 501 on
        static-stack engines."""
        eng = STATE.engine
        if eng is None:
            self._json(503, {"error": "model not loaded"})
            return
        name = str(req.get("name") or "")
        ckpt = str(req.get("checkpoint") or "")
        if not name or not ckpt:
            self._json(400, {"error": "name and checkpoint are required"})
            return
        load = req.get("load", True)
        loader = getattr(eng, "load_adapter", None)
        if not callable(loader):
            self._json(501, {"error": "engine has no dynamic adapter pool"})
            return
        from datatunerx_tpu.adapters import AdapterPinnedError

        try:
            self._json(200, loader(name, ckpt, preload=bool(load)))
        except NotImplementedError as e:  # static stack: can never succeed
            self._json(501, {"error": str(e)})
        except AdapterPinnedError as e:
            self._json(409, {"error": str(e)})
        except RuntimeError as e:  # pool exhausted: retryable
            self._json(409, {"error": str(e)})
        except (ValueError, FileNotFoundError) as e:
            self._json(400, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 — serving must answer
            self._json(500, {"error": str(e)})

    def _adapters_delete(self, name: str):
        """DELETE /admin/adapters/<name> — evict + unregister. 409 while
        in-flight requests pin the adapter; retry after they drain."""
        eng = STATE.engine
        if eng is None:
            self._json(503, {"error": "model not loaded"})
            return
        unloader = getattr(eng, "unload_adapter", None)
        if not callable(unloader):
            self._json(501, {"error": "engine has no dynamic adapter pool"})
            return
        from datatunerx_tpu.adapters import AdapterPinnedError

        try:
            if unloader(name):
                self._json(200, {"unloaded": name})
            else:
                self._json(404, {"error": f"no adapter {name!r}"})
        except NotImplementedError as e:
            self._json(501, {"error": str(e)})
        except AdapterPinnedError as e:
            self._json(409, {"error": str(e)})
        except Exception as e:  # noqa: BLE001
            self._json(500, {"error": str(e)})

    def do_DELETE(self):
        if self.path.startswith("/admin/adapters/"):
            self._adapters_delete(self.path[len("/admin/adapters/"):])
        else:
            self._json(404, {"error": "not found"})

    def _metrics(self):
        """Prometheus text exposition from the shared registry (obs.metrics):
        engine latency histograms + scrape-time gauges, one encoder.
        Exemplar annotations only on the ?exemplars=1 debug view (classic
        parsers reject the tail)."""
        # getattr: tests drive a bare Handler (no request line, no path)
        body = metrics_text(
            with_exemplars=exemplars_requested(
                getattr(self, "path", ""))).encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/plain; version=0.0.4")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _debug_trace(self, trace_id: str):
        """Per-request span timeline from the engine's trace ring — the
        replica half of the gateway's GET /debug/trace/<id> merge."""
        store = getattr(STATE.engine, "trace_store", None)
        doc = store.get(trace_id) if store is not None and trace_id else None
        if doc is None:
            self._json(404, {"error": f"no trace {trace_id!r}"})
        else:
            self._json(200, doc)

    def _debug_profile(self, req: dict):
        """Arm an N-second jax.profiler window (one at a time per process).
        Engine decode/prefill ticks are TraceAnnotation-labeled, so the
        capture reads like the scheduler's own timeline in XProf."""
        from datatunerx_tpu.obs.profiling import (
            process_profiler,
            resolve_profile_dir,
        )

        try:
            seconds = float(req.get("seconds", 2.0))
        except (TypeError, ValueError):
            self._json(400, {"error": "seconds must be a number"})
            return
        try:
            log_dir = resolve_profile_dir(str(req.get("dir") or ""))
        except ValueError as e:  # dir escapes the allowed root
            self._json(400, {"error": str(e)})
            return
        try:
            effective = process_profiler().start(log_dir, seconds)
        except Exception as e:  # noqa: BLE001 — profiler fault ≠ server fault
            self._json(500, {"error": f"profiler failed to start: {e}"})
            return
        if effective is None:
            self._json(409, {"error": "a profile capture is already running",
                             "active": process_profiler().status()})
            return
        # echo the CLAMPED window, not the request — what will actually run
        self._json(202, {"profiling": log_dir, "seconds": effective})

    # --------------------------------------------------- KV migration fabric
    def _sessions_export(self, req: dict):
        """POST /admin/sessions/export {"slots": [..]?, "wire":
        "bf16"|"int8"?, "prefill": bool?} — serialize (and terminate)
        in-flight decode sessions for replica-to-replica handoff;
        ``prefill`` additionally ships MID-chunked-prefill slots (blocks
        written so far + remaining prompt tail). 501 on engines without
        the migration surface."""
        eng = STATE.engine
        if eng is None:
            self._json(503, {"error": "model not loaded"})
            return
        fn = getattr(eng, "export_sessions", None)
        if not callable(fn):
            self._json(501, {"error": "engine has no session export"})
            return
        kw = {"slots": req.get("slots"),
              "wire_quant": req.get("wire") or None}
        if req.get("prefill"):
            # only when asked: older engines lack the kwarg entirely
            kw["include_prefill"] = True
        try:
            self._json(200, fn(**kw))
        except TimeoutError as e:
            self._json(503, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 — serving must answer
            self._json(500, {"error": str(e)})

    def _fleet_admin(self, attr: str, kwargs: dict):
        """Shared shell for the fleet-plane admin surfaces (spill leases
        + prefix tier). Engine refusals (ValueError/KeyError) map to 409
        — the coordinator's fall-back-or-retry signal — and a missing
        engine method to 501, which HTTPReplica reads as 'replica kind
        without the surface' (None, skipped quietly)."""
        eng = STATE.engine
        if eng is None:
            self._json(503, {"error": "model not loaded"})
            return
        fn = getattr(eng, attr, None)
        if not callable(fn):
            self._json(501, {"error": f"engine has no {attr}"})
            return
        try:
            self._json(200, fn(**kwargs))
        except (ValueError, KeyError) as e:
            self._json(409, {"error": str(e)})
        except TimeoutError as e:
            self._json(503, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 — serving must answer
            self._json(500, {"error": str(e)})

    def _sessions_hold(self, req: dict):
        """POST /admin/sessions/hold {"max_sessions": n, "hold_s": s} —
        lease preemption-parked sessions for a peer spill (phase 1)."""
        self._fleet_admin("hold_parked", {
            "max_sessions": int(req.get("max_sessions", 4)),
            "hold_s": float(req.get("hold_s", 10.0))})

    def _sessions_drop(self, req: dict):
        """POST /admin/sessions/drop {"trace_ids": [...]} — finish a
        spill: drop the re-homed sessions, terminating their source
        requests with the migrated marker."""
        self._fleet_admin("drop_parked", {
            "trace_ids": list(req.get("trace_ids") or [])})

    def _sessions_release(self, req: dict):
        """POST /admin/sessions/release {"trace_ids": [...]} — abort a
        spill: clear the leases so the sessions resume locally."""
        self._fleet_admin("release_parked", {
            "trace_ids": list(req.get("trace_ids") or [])})

    def _prefix_export(self, req: dict):
        """POST /admin/prefix/export {"max_entries": n, "exclude":
        [fp...], "wire": "bf16"|"int8"?} — publishable local prefix-cache
        entries for the fleet prefix tier."""
        self._fleet_admin("export_prefix_entries", {
            "exclude": req.get("exclude") or None,
            "max_entries": int(req.get("max_entries", 4)),
            "wire_quant": req.get("wire") or None})

    def _prefix_import(self, req: dict):
        """POST /admin/prefix/import <dtx-kv-prefix payload> — install a
        fleet-published prefix entry into the local prefix cache."""
        self._fleet_admin("import_prefix_entry", {"payload": dict(req)})

    def _sessions_import(self, req: dict):
        """POST /admin/sessions/import <payload> — admit an exported
        session and resume its decode. Default response is an SSE stream:
        first event ``{"imported": meta}``, then ``{"delta": text}``
        continuation events (text beyond the migrated tail), then
        ``[DONE]`` — one round-trip carries the receipt AND the spliced
        stream. ``"stream": false`` blocks until the session finishes and
        returns the full text (tooling/tests). 409 on a refusal the
        caller should fall back cold on (no slot, blocks exhausted,
        unknown adapter, incompatible payload)."""
        eng = STATE.engine
        if eng is None:
            self._json(503, {"error": "model not loaded"})
            return
        fn = getattr(eng, "import_session", None)
        if not callable(fn):
            self._json(501, {"error": "engine has no session import"})
            return
        stream = bool(req.pop("stream", True))
        try:
            meta = dict(fn(req))
        except (ValueError, KeyError) as e:
            self._json(409, {"error": str(e)})
            return
        except TimeoutError as e:
            self._json(503, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001
            self._json(500, {"error": str(e)})
            return
        handle = meta.pop("_request", None)
        if not stream:
            if handle is not None:
                handle.done.wait(300)
                meta["error"] = handle.error
                meta["text"] = eng.tokenizer.decode(
                    handle.tokens, skip_special_tokens=True)
            self._json(200, {"imported": meta})
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.end_headers()

        def event(payload: dict):
            self.wfile.write(b"data: " + json.dumps(payload).encode()
                             + b"\n\n")
            self.wfile.flush()

        code = 200
        try:
            event({"imported": meta})
            try:
                if handle is not None:
                    for delta in eng.resume_stream(handle):
                        event({"delta": delta})
            except Exception as e:  # noqa: BLE001 — headers already sent
                event({"error": {"message": str(e)}})
                code = 500
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            code = 499
        self._record(code)

    def do_POST(self):
        if self.path == "/perplexity":
            self._perplexity()
            return
        fleet_routes = {
            "/admin/sessions/export": self._sessions_export,
            "/admin/sessions/import": self._sessions_import,
            "/admin/sessions/hold": self._sessions_hold,
            "/admin/sessions/drop": self._sessions_drop,
            "/admin/sessions/release": self._sessions_release,
            "/admin/prefix/export": self._prefix_export,
            "/admin/prefix/import": self._prefix_import,
        }
        if self.path in fleet_routes:
            try:
                length = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                self._json(400, {"error": f"invalid JSON body: {e}"})
                return
            fleet_routes[self.path](req)
            return
        if self.path == "/admin/adapters":
            try:
                length = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                self._json(400, {"error": f"invalid JSON body: {e}"})
                return
            self._adapters_post(req)
            return
        if self.path == "/debug/profile":
            try:
                length = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                self._json(400, {"error": f"invalid JSON body: {e}"})
                return
            self._debug_profile(req)
            return
        if self.path not in ("/chat/completions", "/v1/chat/completions"):
            self._json(404, {"error": "not found"})
            return
        if STATE.engine is None:
            self._json(503, {"error": "model not loaded"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            try:
                req = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                self._json(400, {"error": f"invalid JSON body: {e}"})
                return
            messages = req.get("messages")
            if not isinstance(messages, list) or not messages:
                self._json(400, {"error": "messages must be a non-empty list"})
                return
            kwargs = dict(
                max_new_tokens=int(req.get("max_tokens", 128)),
                temperature=float(req.get("temperature", 0.0)),
                top_p=float(req.get("top_p", 1.0)),
            )
            # "model" routes to a named LoRA adapter on batched engines
            # (multi-tenant serving; unknown names 400 rather than silently
            # serving the base)
            adapter = req.get("model") or ""
            if adapter and getattr(STATE.engine, "adapter_ids", None) is not None:
                if adapter == STATE.model_path:
                    adapter = ""
                elif adapter not in STATE.engine.adapter_ids:
                    self._json(400, {"error": f"unknown model/adapter {adapter!r}"})
                    return
                kwargs["adapter"] = adapter
            # hand the gateway's trace id to engines that keep span
            # timelines (duck-typed/single-slot engines just don't get it)
            trace = self.headers.get("X-DTX-Trace-Id") or ""
            if trace and getattr(STATE.engine, "trace_store", None) is not None:
                kwargs["trace_id"] = trace
            # tenancy: hand the gateway's tenant name to engines running a
            # directory (everyone else never sees the kwarg)
            tenant = self.headers.get("X-DTX-Tenant") or ""
            if tenant and getattr(STATE.engine, "tenants", None) is not None:
                kwargs["tenant"] = tenant
            if req.get("stream"):
                self._stream_chat(messages, kwargs,
                                  usage=self._prompt_usage(messages))
                return
            usage = self._prompt_usage(messages)
            text = STATE.engine.chat(messages, **kwargs)
            body = {
                "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": STATE.model_path,
                "choices": [{
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": "stop",
                }],
            }
            if usage is not None:
                usage["completion_tokens"] = self._count_tokens(text)
                usage["total_tokens"] = (usage["prompt_tokens"]
                                         + usage["completion_tokens"])
                body["usage"] = usage
            self._json(200, body)
        except Exception as e:  # noqa: BLE001 - serving must answer, not die
            self._json(500, {"error": str(e)})

    @staticmethod
    def _prompt_usage(messages) -> Optional[dict]:
        """Replica-side tokenized prompt length — the TRUTHFUL count the
        gateway's admission calibrates with (the chars-per-token heuristic
        is a guess; this is what prefill actually pays). None on engines
        without the chat encoder (duck-typed stand-ins)."""
        enc = getattr(STATE.engine, "_encode_chat", None)
        if not callable(enc):
            return None
        try:
            return {"prompt_tokens": len(enc(messages)[0])}
        except Exception:  # noqa: BLE001 — usage is advisory
            return None

    @staticmethod
    def _count_tokens(text: str) -> int:
        tok = getattr(STATE.engine, "tokenizer", None)
        if tok is None or not text:
            return 0
        try:
            return len(tok.encode(text, add_special_tokens=False))
        except TypeError:  # tokenizers without the kwarg
            return len(tok.encode(text))
        except Exception:  # noqa: BLE001
            return 0

    def _perplexity(self):
        """POST {"prompt": str, "completion": str[, "model": adapter]} →
        completion NLL/perplexity under the served model. Backs the
        perplexity metric of dataset-driven scoring."""
        if STATE.engine is None:
            self._json(503, {"error": "model not loaded"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(length) or b"{}")
            prompt = req.get("prompt") or ""
            completion = req.get("completion") or ""
            if not completion:
                self._json(400, {"error": "completion is required"})
                return
            tok = STATE.engine.tokenizer
            p_ids = tok.encode(prompt) if prompt else []
            try:
                c_ids = tok.encode(completion, add_special_tokens=False)
            except TypeError:  # tokenizers without the kwarg
                c_ids = tok.encode(completion)
            kwargs = {}
            adapter = req.get("model") or ""
            if adapter and getattr(STATE.engine, "adapter_ids", None) is not None:
                if adapter not in STATE.engine.adapter_ids:
                    self._json(400, {"error": f"unknown model/adapter {adapter!r}"})
                    return
                kwargs["adapter"] = adapter
            self._json(200, STATE.engine.perplexity(p_ids, c_ids, **kwargs))
        except Exception as e:  # noqa: BLE001
            self._json(500, {"error": str(e)})

    def _stream_chat(self, messages, kwargs, usage=None):
        """SSE: one ``data: {chat.completion.chunk}`` event per text delta,
        then ``data: [DONE]`` (OpenAI stream shape). The terminal chunk
        carries ``usage`` (replica-side tokenized prompt length) so
        streaming clients — the gateway's HTTPReplica included — get the
        same truthful count the non-streamed response body does."""
        stream_fn = getattr(STATE.engine, "chat_stream", None)
        if stream_fn is None:  # single-slot engine: one terminal delta
            def stream_fn(msgs, **kw):
                yield STATE.engine.chat(msgs, **kw)
        rid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        trace = self.headers.get("X-DTX-Trace-Id")
        if trace:
            self.send_header("X-DTX-Trace-Id", trace)
        self.end_headers()

        def event(payload: dict):
            self.wfile.write(b"data: " + json.dumps(payload).encode() + b"\n\n")
            self.wfile.flush()

        code = 200
        try:
            try:
                for delta in stream_fn(messages, **kwargs):
                    event({
                        "id": rid, "object": "chat.completion.chunk",
                        "created": int(time.time()), "model": STATE.model_path,
                        "choices": [{"index": 0,
                                     "delta": {"content": delta},
                                     "finish_reason": None}],
                    })
                terminal = {
                    "id": rid, "object": "chat.completion.chunk",
                    "created": int(time.time()), "model": STATE.model_path,
                    "choices": [{"index": 0, "delta": {},
                                 "finish_reason": "stop"}],
                }
                if usage is not None:
                    terminal["usage"] = usage
                event(terminal)
            except Exception as e:  # noqa: BLE001 — headers already sent:
                # a second HTTP response would corrupt the stream, so errors
                # become a terminal SSE event instead
                event({"error": {"message": str(e)}})
                code = 500
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            code = 499
        self._record(code)

    def log_message(self, *a):
        pass


def load_engine_async(args, on_failure=None):
    """Build the engine ``args`` (a namespace of serving/options.py) asks for
    on a background thread. A failure is recorded in
    ``STATE.error`` (``/healthz`` → 500 FAILED), its traceback printed, and
    ``on_failure()`` called — ``main`` passes the HTTP server's shutdown so
    the process EXITS non-zero instead of answering 500 for ever: a replica
    that could not open its chip must not look like one still loading."""
    def _load():
        try:
            STATE.model_path = args.model_path
            if args.slots > 1 and not args.quantization:
                from datatunerx_tpu.serving.batched_engine import BatchedEngine

                # the server's registry: engine TTFT/TPOT/prefill-chunk
                # histograms land in the same /metrics exposition
                STATE.engine = BatchedEngine(registry=STATE.registry,
                                             **options.engine_kwargs(args))
            else:
                # refusing beats silently serving the base model under a
                # tenant's adapter name / running a full-size cache the
                # operator budgeted HBM against
                refused = options.requires_batched(args)
                if refused:
                    raise ValueError(
                        f"{refused[0]} requires the batched engine "
                        "(--slots > 1, no --quantization)"
                    )
                # single-slot path also carries serve-time quantization
                from datatunerx_tpu.serving.engine import InferenceEngine

                STATE.engine = InferenceEngine(
                    args.model_path, args.checkpoint_path or None,
                    template=args.template, max_seq_len=args.max_seq_len,
                    quantization=args.quantization or None,
                )
        except Exception as e:  # noqa: BLE001 — reported, then fatal in main
            STATE.error = str(e) or type(e).__name__
            traceback.print_exc()
            if on_failure is not None:
                on_failure()

    t = threading.Thread(target=_load, daemon=True)
    t.start()
    return t


def build_parser():
    p = argparse.ArgumentParser(prog="datatunerx-tpu-serving")
    options.add_arguments(p)
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--role", default="mixed",
                   choices=["prefill", "decode", "mixed"],
                   help="disaggregation role declared to the fleet: "
                        "prefill = long-prompt specialist (the gateway "
                        "steers prompts over its threshold here and the "
                        "handoff coordinator re-homes finished prefills "
                        "for decode), decode = token production, mixed "
                        "(default) = role-less, routing byte-identical "
                        "to older fleets")
    p.add_argument("--slo_config", default="",
                   help="JSON file of SLO specs (obs/slo.py format) judged "
                        "at GET /debug/slo; default: built-in serving "
                        "availability + TTFT objectives")
    p.add_argument("--slo_sample_s", type=float, default=15.0,
                   help="background SLO sampling interval (0 = sample only "
                        "on /debug/slo)")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)

    from datatunerx_tpu.utils import runtime

    info = runtime.startup("server")
    STATE.device = {k: info[k] for k in ("platform", "device_kind", "count")}
    STATE.compile_cache_stats = runtime.compile_cache_stats
    STATE.role = args.role
    if args.slo_config:
        from datatunerx_tpu.obs.slo import load_slos

        with STATE.slo_lock:
            STATE.slo = SLOEvaluator(STATE.registry,
                                     load_slos(args.slo_config))
    if args.slo_sample_s > 0:
        slo_evaluator().start(args.slo_sample_s)

    srv = ThreadingHTTPServer(("0.0.0.0", args.port), Handler)
    load_engine_async(args, on_failure=srv.shutdown)
    print(f"[serving] listening on :{args.port} (model loading async)", flush=True)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    if STATE.error:
        print(f"[serving] engine failed to load: {STATE.error}",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
