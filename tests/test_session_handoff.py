"""KV migration fabric: live session export/import (serving/migration.py).

The correctness bar is the ISSUE's oracle: a session exported mid-decode
and imported on another replica resumes TOKEN-EXACTLY vs an undisturbed
run — greedy AND fixed-seed sampled, bf16 AND int8 kv_quant caches, base
AND mixed-rank pooled adapters (the target resolves the adapter NAME,
load-on-miss included). On top of the engine primitive: the gateway's
drain handoff (export → import → mid-stream SSE splice with no duplicate
or missing text), the admin HTTP wire format, refusal paths, the
replacement-inheritance satellite lives in test_gateway.py, and the
burn-rate autoscale + trace-log converter satellites."""

import json
import threading
import time
from http.server import ThreadingHTTPServer

import pytest

from datatunerx_tpu.serving.batched_engine import BatchedEngine

MODEL = "preset:debug"


def _throttled(eng, delay=0.04):
    """Slow each decode chunk so a test can deterministically catch a
    request mid-decode. Returns the original to restore."""
    orig = eng._decode

    def slow(*a, **k):
        time.sleep(delay)
        return orig(*a, **k)

    eng._decode = slow
    return orig


def _export_mid_decode(src, prompt, min_tokens=3, **kw):
    """Submit on a throttled ``src``, wait until it has streamed a few
    tokens, then export. Returns the (single) payload."""
    orig = _throttled(src)
    try:
        req = src.submit(prompt, **kw)
        deadline = time.monotonic() + 30
        while len(req.tokens) < min_tokens and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(req.tokens) >= min_tokens, "decode never started"
        doc = src.export_sessions()
    finally:
        src._decode = orig
    assert len(doc["sessions"]) == 1, doc
    assert req.done.wait(10) and "session migrated" in (req.error or "")
    return doc["sessions"][0]


def _import_and_wait(dst, payload, timeout=120):
    meta = dst.import_session(json.loads(json.dumps(payload)))
    handle = meta.pop("_request")
    assert handle.done.wait(timeout), "imported session never finished"
    assert handle.error is None, handle.error
    return handle, meta


@pytest.fixture(scope="module")
def paged_pair():
    src = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16)
    dst = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16)
    yield src, dst
    src.close()
    dst.close()


# --------------------------------------------------- engine-level parity

def test_export_import_greedy_parity(paged_pair):
    src, dst = paged_pair
    prompt = src.tokenizer.encode("the quick brown fox jumps over")
    want = src.generate(prompt, max_new_tokens=24)
    payload = _export_mid_decode(src, prompt, max_new_tokens=24)
    assert payload["kv"]["wire"] == "bf16"  # lossless native encoding
    handle, meta = _import_and_wait(dst, payload)
    assert handle.tokens == want, (handle.tokens, want)
    # the migrated tail was already streamed by the source; the import
    # receipt carries it detokenized for the gateway's splice
    assert meta["tokens"] == len(payload["tokens"])
    # elastic accounting on BOTH sides: source freed at export, target
    # freed at completion
    assert src.free_kv_blocks == src.total_kv_blocks
    assert dst.free_kv_blocks == dst.total_kv_blocks
    assert src.session_stats["export"].get("ok", 0) >= 1
    assert dst.session_stats["import"].get("ok", 0) >= 1


def test_export_import_sampled_parity(paged_pair):
    """Fixed-seed sampled resume: the payload carries the slot's LIVE rng
    key (not the seed), so the continuation consumes the same stream the
    undisturbed run would."""
    src, dst = paged_pair
    prompt = src.tokenizer.encode("sampling determinism migrates too")
    for seed in (0, 11):
        want = src.generate(prompt, max_new_tokens=16, temperature=0.8,
                            top_p=0.9, seed=seed)
        payload = _export_mid_decode(src, prompt, max_new_tokens=16,
                                     temperature=0.8, top_p=0.9, seed=seed)
        handle, _ = _import_and_wait(dst, payload)
        assert handle.tokens == want, (seed, handle.tokens, want)


def test_export_import_spec_active_session(paged_pair):
    """A SPEC-ACTIVE session (pending-token form, draft cache live) exports
    cleanly: the engine settles the pending token so the payload is the
    standard logits-form wire format, the importer re-primes its own draft
    cache from the payload's prompt + tail, and the greedy continuation is
    token-exact vs an undisturbed non-spec run — both into a spec engine
    and into a plain engine (the wire carries no spec state at all)."""
    ref, plain_dst = paged_pair
    src = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        spec_draft="take:2", spec_k=3, spec_mode="on")
    dst = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        spec_draft="take:2", spec_k=3, spec_mode="on")
    try:
        prompt = src.tokenizer.encode("speculative sessions migrate too")
        want = ref.generate(prompt, max_new_tokens=24)

        def export_mid_spec(target_dst):
            # throttle the SPEC tick (the spec engine never runs _decode)
            orig = src._spec_decode_tick

            def slow(*a, **k):
                time.sleep(0.04)
                return orig(*a, **k)

            src._spec_decode_tick = slow
            try:
                req = src.submit(prompt, max_new_tokens=24)
                deadline = time.monotonic() + 30
                while len(req.tokens) < 3 and time.monotonic() < deadline:
                    time.sleep(0.01)
                assert len(req.tokens) >= 3
                doc = src.export_sessions()
            finally:
                src._spec_decode_tick = orig
            assert len(doc["sessions"]) == 1, doc
            assert req.done.wait(10)
            # the settle wrote the pending token: payload cursor covers
            # every emitted token and carries next-token logits
            payload = doc["sessions"][0]
            assert any(ev[0] == "spec_settle" for ev in src.sched_trace)
            handle, _ = _import_and_wait(target_dst, payload)
            return handle

        handle = export_mid_spec(dst)
        assert handle.tokens == want, (handle.tokens, want)
        # the spec importer RE-PRIMED its draft (re-prime contract: no
        # draft KV on the wire) and kept speculating after the import
        assert any(ev[0] == "spec_prime" for ev in dst.sched_trace)
        assert (dst.spec_info() or {}).get("proposed", 0) > 0

        handle2 = export_mid_spec(plain_dst)  # spec → non-spec replica
        assert handle2.tokens == want, (handle2.tokens, want)
    finally:
        src.close()
        dst.close()


def test_export_import_spec_tree_active_session(paged_pair):
    """A TREE-SPEC-ACTIVE session exports cleanly: the settle collapses
    the in-flight verify columns to the standard logits-form wire format
    (no tree state on the wire), and the greedy continuation is
    token-exact — into a tree replica, and into a PLAIN replica that has
    never heard of trees."""
    ref, plain_dst = paged_pair
    # same config as test_speculative's tree engine, so the tree program
    # family compiles once per suite run (weak take:1 draft — the export
    # interrupts REAL rejection/rollback traffic, not an all-accept run)
    src = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=3, decode_chunk=4, kv_block_size=16,
                        spec_draft="take:1", spec_k=3, spec_mode="on",
                        spec_tree="2x2")
    # the tree importer is the EXPORTER itself: its slot freed at export,
    # so the import lands in a fresh slot of the same tree engine
    dst = src
    try:
        prompt = src.tokenizer.encode("tree sessions migrate too")
        want = ref.generate(prompt, max_new_tokens=16)

        orig = src._spec_decode_tick

        def slow(*a, **k):
            time.sleep(0.04)
            return orig(*a, **k)

        src._spec_decode_tick = slow
        try:
            req = src.submit(prompt, max_new_tokens=16)
            deadline = time.monotonic() + 30
            while len(req.tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(req.tokens) >= 3
            doc = src.export_sessions()
        finally:
            src._spec_decode_tick = orig
        assert len(doc["sessions"]) == 1, doc
        assert req.done.wait(10)
        payload = doc["sessions"][0]
        # the exporter really was mid-TREE decode, not chain, and the
        # settle collapsed it; the wire format is tree-agnostic
        assert src.spec_info()["tree_steps"] > 0
        assert any(ev[0] == "spec_settle" for ev in src.sched_trace)
        # the KV/logits/rng wire stays tree-agnostic; the learned
        # spec-controller document rides alongside as plain JSON (slot
        # acceptance EMA + learned widths warm the importer's controller)
        wire_doc = {k: v for k, v in payload.items() if k != "spec"}
        assert "tree" not in json.dumps(wire_doc)
        assert payload["spec"]["plan"][0] == "tree"
        # the learned per-depth evidence rides along (importer controllers
        # adopt it instead of restarting the width search cold)
        assert "depth_ema" in payload["spec"]
        json.dumps(payload["spec"])  # JSON-safe end to end

        n_prime0 = sum(1 for ev in dst.sched_trace if ev[0] == "spec_prime")
        steps0 = dst.spec_info()["tree_steps"]
        handle, _ = _import_and_wait(dst, payload)
        assert handle.tokens == want, (handle.tokens, want)
        # the tree importer re-primed and kept tree-verifying after import
        assert sum(1 for ev in dst.sched_trace
                   if ev[0] == "spec_prime") > n_prime0
        assert dst.spec_info()["tree_steps"] > steps0

        # the SAME payload lands on a plain replica too: tree → plain
        handle2, _ = _import_and_wait(plain_dst, payload)
        assert handle2.tokens == want, (handle2.tokens, want)
    finally:
        src.close()


def test_export_import_int8_kv_parity():
    """int8 kv_quant engines ship their cache's own int8+scale bytes —
    the 'int8 over the wire' path is EXACT for them, greedy and sampled."""
    src = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        kv_quant="int8")
    dst = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        kv_quant="int8")
    try:
        prompt = src.tokenizer.encode("quantized cache migration probe")
        for kw in ({}, {"temperature": 0.7, "top_p": 0.9, "seed": 5}):
            want = src.generate(prompt, max_new_tokens=16, **kw)
            payload = _export_mid_decode(src, prompt, max_new_tokens=16,
                                         **kw)
            assert payload["kv"]["wire"] == "int8"
            assert "k_scale" in payload["kv"]
            handle, _ = _import_and_wait(dst, payload)
            assert handle.tokens == want, (kw, handle.tokens, want)
    finally:
        src.close()
        dst.close()


def test_export_import_mixed_rank_adapters(tmp_path):
    """Adapter sessions migrate by NAME across heterogeneous resident
    sets: the target's pool may hold the adapter in a different slot — or
    not at all, in which case the import itself pays the load-on-miss
    (parked and retried, like admission) — and still resumes
    token-exactly. Ranks 2 and 4 prove rank-padding survives the trip."""
    from datatunerx_tpu.serving.adapters import make_adapter_checkpoint

    cks = {n: make_adapter_checkpoint(str(tmp_path / n), MODEL,
                                      seed=3 + i, rank=2 * (i + 1))
           for i, n in enumerate(("a", "b"))}
    src = BatchedEngine(MODEL, adapters=cks, adapter_pool=2,
                        adapter_rank_max=8, template="vanilla",
                        max_seq_len=256, slots=2, decode_chunk=4,
                        kv_block_size=16)
    dst = BatchedEngine(MODEL, adapters=cks, adapter_pool=1,
                        adapter_rank_max=8, template="vanilla",
                        max_seq_len=256, slots=2, decode_chunk=4,
                        kv_block_size=16)
    try:
        prompt = src.tokenizer.encode("tenant session on the move")
        for adapter in ("a", "b"):
            want = src.generate(prompt, max_new_tokens=12, adapter=adapter)
            payload = _export_mid_decode(src, prompt, max_new_tokens=12,
                                         adapter=adapter)
            assert payload["adapter"] == adapter
            # dst has ONE pool slot: importing "b" after "a" forces an
            # evict + load-on-miss inside the import retry loop
            handle, meta = _import_and_wait(dst, payload)
            assert handle.tokens == want, (adapter, handle.tokens, want)
            assert meta["adapter"] == adapter
        assert dst.adapter_occupancy()["resident"] == 1
        # adapter sessions must differ from base, or parity is vacuous
        assert want != src.generate(prompt, max_new_tokens=12)
    finally:
        src.close()
        dst.close()


def test_int8_wire_from_bf16_cache_resumes(paged_pair):
    """Forcing the int8 wire encoding from a bf16 cache (bandwidth mode)
    rounds the prefix through kv_quantize — the session must still resume
    and run to completion (token-exactness is only promised for native
    encodings; this asserts the lossy path is functional, not identical)."""
    src, dst = paged_pair
    prompt = src.tokenizer.encode("compressed wire migration")
    n_new = 16
    orig = _throttled(src)
    try:
        req = src.submit(prompt, max_new_tokens=n_new)
        deadline = time.monotonic() + 30
        while len(req.tokens) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        doc = src.export_sessions(wire_quant="int8")
    finally:
        src._decode = orig
    payload = doc["sessions"][0]
    assert payload["kv"]["wire"] == "int8"
    handle, _ = _import_and_wait(dst, payload)
    assert len(handle.tokens) <= n_new
    # the migrated tail is preserved verbatim
    assert handle.tokens[:len(payload["tokens"])] == payload["tokens"]


def test_export_deactivates_slot_next_tenant_uncorrupted():
    """Regression (review find): export released the slot host-side but
    left it ACTIVE on device — an interleaved decode chunk kept sampling
    the stale slot and wrote a stale token through the NEXT tenant's
    freshly-installed block table while that tenant was still
    chunk-prefilling, corrupting its prompt KV. The exported slot must be
    deactivated at export, and a request admitted into the freed slot
    while another slot keeps decoding must produce undisturbed tokens."""
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        prefill_chunk=64, prefill_token_budget=64)
    try:
        long_prompt = eng.tokenizer.encode("chunked prefill target " * 40)
        short = eng.tokenizer.encode("short co-tenant")
        want = eng.generate(long_prompt, max_new_tokens=8)

        orig = _throttled(eng, delay=0.05)
        try:
            # A keeps decoding throughout; B is exported; C admits into
            # B's freed slot and chunk-prefills WHILE A's decode interleaves
            req_a = eng.submit(short, max_new_tokens=64, temperature=0.9,
                               seed=1)
            req_b = eng.submit(short, max_new_tokens=64, temperature=0.9,
                               seed=2)
            deadline = time.monotonic() + 30
            while (any(r is None for r in eng._slot_req)
                   or not all(eng._decode_ready)) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            slot_b = eng._slot_req.index(req_b)
            doc = eng.export_sessions(slots=[slot_b])
            assert len(doc["sessions"]) == 1
            req_c = eng.submit(long_prompt, max_new_tokens=8)
            assert req_c.done.wait(120) and req_c.error is None, req_c.error
            assert req_c.tokens == want, (req_c.tokens, want)
            assert req_a.done.wait(120) and req_a.error is None
        finally:
            eng._decode = orig
    finally:
        eng.close()


# ------------------------------------------------------------- refusals

def test_import_refusals(paged_pair):
    src, dst = paged_pair
    prompt = src.tokenizer.encode("refusal probe")
    payload = _export_mid_decode(src, prompt, max_new_tokens=12)

    # incompatible model signature → immediate refusal
    bad = json.loads(json.dumps(payload))
    bad["model_sig"]["layers"] = 999
    with pytest.raises(ValueError, match="incompatible model"):
        dst.import_session(bad)

    # unknown adapter name → immediate refusal (dst has no pool)
    bad = json.loads(json.dumps(payload))
    bad["adapter"] = "nobody-registered-this"
    with pytest.raises(ValueError, match="unknown adapter"):
        dst.import_session(bad)

    # full pool: every slot busy → parked import refused at its deadline
    orig = _throttled(dst, delay=0.05)
    try:
        occupants = [dst.submit(prompt, max_new_tokens=48)
                     for _ in range(dst.slots)]
        deadline = time.monotonic() + 30
        while (any(r is None for r in dst._slot_req)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        with pytest.raises(ValueError, match="no free cache slot"):
            dst.import_session(json.loads(json.dumps(payload)),
                               wait_s=0.3)
        assert dst.session_stats["import"].get("refused", 0) >= 1
    finally:
        dst._decode = orig
        for r in occupants:
            r.done.wait(120)


# ------------------------------------------------------ gateway e2e splice

def test_gateway_drain_splices_stream_no_dup_no_missing(paged_pair):
    """The tentpole's consumer: a mid-stream /admin/drain exports the
    session, imports it on the peer, and the client's SSE stream continues
    with NO duplicate and NO missing text — final text equals an
    undisturbed run byte-for-byte. The drained replica is empty the moment
    drain returns (free rolling restart), and the whole handoff is visible
    in the request trace and the handoff counters."""
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway

    src, dst = paged_pair
    engines = [src, dst]
    pool = ReplicaPool([InProcessReplica(f"replica-{i}", e)
                        for i, e in enumerate(engines)])
    gw = Gateway(pool, model_name=MODEL)
    req = {"messages": [{"role": "user",
                         "content": "tell me a long story about foxes"}],
           "max_tokens": 40, "temperature": 0.0}
    try:
        want = gw.chat(dict(req), trace_id="dtx-undisturbed")

        origs = [(e, _throttled(e)) for e in engines]
        collected: dict = {}

        def consume():
            collected["text"] = "".join(
                gw.chat_stream(dict(req), trace_id="dtx-handoff-e2e"))

        try:
            th = threading.Thread(target=consume)
            th.start()
            # drain the moment the request is actually DECODING (a slot
            # still mid-chunked-prefill is skipped by export, by design)
            deadline = time.monotonic() + 15
            src_i = None
            while src_i is None and time.monotonic() < deadline:
                src_i = next(
                    (i for i, e in enumerate(engines)
                     if any(r is not None and e._decode_ready[s]
                            for s, r in enumerate(e._slot_req))), None)
                time.sleep(0.002)
            assert src_i is not None, "stream never reached a decode slot"
            assert gw.drain(f"replica-{src_i}")
            assert gw.last_handoff["imported"] == 1, gw.last_handoff
            # free rolling restart: the drained replica holds NOTHING the
            # reap would wait on
            assert all(r is None for r in engines[src_i]._slot_req)
            th.join(timeout=120)
            assert not th.is_alive(), "spliced stream never finished"
        finally:
            for e, o in origs:
                e._decode = o
        assert collected["text"] == want, (collected["text"], want)

        stats = gw.handoff_stats()
        assert stats.get("imported") == 1 and stats.get("splice_ok") == 1
        assert not stats.get("cold")
        # the import landed in the TARGET's scheduler trace
        assert any(ev[0] == "import"
                   for ev in engines[1 - src_i].sched_trace)
        # handoff span events merged into the end-to-end trace
        doc = gw.trace("dtx-handoff-e2e")
        names = {ev.get("name") for sp in doc["spans"]
                 for ev in sp.get("events", [])}
        assert {"handoff_pending", "handoff_splice"} <= names, names
        assert {"export", "import"} <= names, names
    finally:
        for r in pool.replicas():
            r.undrain()
        gw.slo.stop()


# ------------------------------------------------------------ HTTP wire

def test_admin_sessions_http_roundtrip(paged_pair):
    """The serving admin surface end-to-end over real sockets: import an
    exported session via POST /admin/sessions/import (SSE receipt +
    continuation), then export a live session back out via
    POST /admin/sessions/export through HTTPReplica."""
    from datatunerx_tpu.gateway.replica_pool import HTTPReplica
    from datatunerx_tpu.serving import server as serving

    src, dst = paged_pair
    old_engine, old_model = serving.STATE.engine, serving.STATE.model_path
    serving.STATE.engine, serving.STATE.model_path = dst, MODEL
    srv = ThreadingHTTPServer(("127.0.0.1", 0), serving.Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    replica = HTTPReplica("r-http", f"http://127.0.0.1:{srv.server_port}")
    try:
        prompt = src.tokenizer.encode("over the wire we go")
        want_text = src.tokenizer.decode(
            src.generate(prompt, max_new_tokens=20),
            skip_special_tokens=True)
        payload = _export_mid_decode(src, prompt, max_new_tokens=20)

        out = replica.import_session(payload)
        assert out is not None
        meta, stream = out
        assert meta["session"] == payload["trace_id"]
        text = str(meta.get("text_so_far") or "") + "".join(stream)
        assert text == want_text, (text, want_text)

        # now export FROM the server side: a fresh live session on dst
        orig = _throttled(dst)
        try:
            req2 = dst.submit(prompt, max_new_tokens=20)
            deadline = time.monotonic() + 30
            while len(req2.tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            doc = replica.export_sessions()
        finally:
            dst._decode = orig
        assert doc is not None and len(doc["sessions"]) == 1
        handle, _ = _import_and_wait(src, doc["sessions"][0])
        assert src.tokenizer.decode(
            handle.tokens, skip_special_tokens=True) == want_text
    finally:
        srv.shutdown()
        srv.server_close()
        serving.STATE.engine, serving.STATE.model_path = (old_engine,
                                                          old_model)


def test_serving_metrics_expose_session_series(paged_pair):
    """Hands a session over itself: the counters it reads are its own,
    whatever other tests of the file ran on these engines, on whichever
    worker."""
    src, dst = paged_pair
    from datatunerx_tpu.serving import server as serving

    prompt = src.tokenizer.encode("a session of this test's own")
    _import_and_wait(dst, _export_mid_decode(src, prompt, max_new_tokens=16))
    old_engine = serving.STATE.engine
    try:
        serving.STATE.engine = src
        exported = serving.metrics_text()
        serving.STATE.engine = dst
        imported = serving.metrics_text()
    finally:
        serving.STATE.engine = old_engine
    assert 'dtx_serving_session_export_total{outcome="ok"}' in exported
    assert 'dtx_serving_session_import_total{outcome="ok"}' in imported


# ----------------------------------------- selftest fleet (no model load)

def test_selftest_fleet_drain_handoff():
    """The CI smoke path in miniature: fake engines with the migration
    surface behind a REAL gateway — a drain fired while a stream is in
    flight hands the session over, the client sees every token exactly
    once, and nothing lands on the cold path."""
    from datatunerx_tpu.loadgen.replay import (
        build_selftest_fleet,
        drain_when_busy,
    )

    gw, engines = build_selftest_fleet(adapters=[], delay_s=0.01)
    try:
        req = {"messages": [{"role": "user", "content": "hi"}],
               "max_tokens": 8}
        collected: dict = {}

        def consume():
            collected["text"] = "".join(
                gw.chat_stream(dict(req), trace_id="dtx-fake-1"))

        th = threading.Thread(target=consume)
        th.start()
        # wait until some replica actually streams, then drain it
        deadline = time.monotonic() + 5
        busy = None
        while busy is None and time.monotonic() < deadline:
            busy = next((r for r in gw.pool.replicas() if r.inflight), None)
            time.sleep(0.002)
        assert busy is not None
        out = drain_when_busy(gw, busy.name)
        assert out["drained"]
        th.join(timeout=10)
        assert collected["text"] == "tok " * 8, collected
        stats = gw.handoff_stats()
        assert stats.get("imported") == 1 and not stats.get("cold"), stats
    finally:
        gw.slo.stop()


def test_selftest_fleet_handoff_off_is_cold():
    """With session_handoff off the same drain kills nothing (sessions
    complete in place) — and an export-kill falls back to the legacy
    re-emit path, still serving the client."""
    from datatunerx_tpu.loadgen.replay import build_selftest_fleet

    gw, engines = build_selftest_fleet(adapters=[], delay_s=0.01,
                                       session_handoff=False)
    try:
        req = {"messages": [{"role": "user", "content": "hi"}],
               "max_tokens": 8}
        collected: dict = {}

        def consume():
            collected["text"] = "".join(
                gw.chat_stream(dict(req), trace_id="dtx-fake-2"))

        th = threading.Thread(target=consume)
        th.start()
        deadline = time.monotonic() + 5
        busy = None
        while busy is None and time.monotonic() < deadline:
            busy = next((e for e in engines if e._live), None)
            time.sleep(0.002)
        assert busy is not None
        busy.export_sessions()  # reap-deadline kill: payload discarded
        th.join(timeout=10)
        # legacy failover re-emits with the prefix skipped: complete text
        assert collected["text"] == "tok " * 8, collected
        assert not gw.handoff_stats().get("imported")
    finally:
        gw.slo.stop()


# --------------------------------------------------- satellite: autoscale

def test_autoscale_hint_consumes_slo_burn():
    from datatunerx_tpu.gateway.autoscale import autoscale_hint

    base = dict(replicas=2, available_replicas=2, queue_depth=0,
                queued_tokens=0, shed_count=0, p95_latency_s=0.0)
    # burning faster than budget → scale up, objective NAMED
    hint = autoscale_hint(**base, slo_burn={"name": "gw-avail",
                                            "burn_rate": 2.5})
    assert hint["desiredReplicas"] == 3
    assert "gw-avail" in hint["reason"] and "2.50" in hint["reason"]
    assert hint["sloBurnRate"] == 2.5
    # comfortable burn + idle queue → scale down
    hint = autoscale_hint(**base, slo_burn={"name": "gw-avail",
                                            "burn_rate": 0.1})
    assert hint["desiredReplicas"] == 1 and hint["reason"] == "idle"
    # burn replaces the raw-p95 trigger entirely when present
    hint = autoscale_hint(**{**base, "p95_latency_s": 999.0},
                          slo_burn={"name": "gw-avail", "burn_rate": 0.5})
    assert hint["desiredReplicas"] == 2
    # without slo_burn the p95 branch is byte-identical to before
    hint = autoscale_hint(**{**base, "p95_latency_s": 999.0})
    assert hint["desiredReplicas"] == 3 and "p95" in hint["reason"]
    assert "sloBurnRate" not in hint


def test_gateway_autoscale_burn_rate_wiring():
    """A CONFIGURED gateway (slos passed = --slo_config) scales on burn
    rate; serving 5xx burns the availability budget and the hint names
    the objective."""
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway
    from datatunerx_tpu.obs.slo import SLO
    from tests.test_gateway import FakeEngine

    slos = [SLO.from_dict({
        "name": "gw-avail", "objective": 0.9, "windows_s": [60],
        "sli": {"kind": "error_ratio",
                "metric": "dtx_gateway_requests_total",
                "bad": {"code": "^5"}}})]
    pool = ReplicaPool([InProcessReplica("r0", FakeEngine("r0"))])
    gw = Gateway(pool, slos=slos)
    try:
        assert gw.slo_configured
        for _ in range(5):
            gw.record_request(500)
        hint = gw.autoscale()
        assert hint["desiredReplicas"] == 2, hint
        assert "gw-avail" in hint["reason"]
        # unconfigured gateway: no SLO keys in the hint at all
        gw2 = Gateway(ReplicaPool([InProcessReplica(
            "r0", FakeEngine("r0"))]))
        try:
            assert not gw2.slo_configured
            assert "sloBurnRate" not in gw2.autoscale()
        finally:
            gw2.slo.stop()
    finally:
        gw.slo.stop()


# ------------------------------------------- satellite: trace-log convert

def test_from_trace_log_converter(tmp_path):
    from datatunerx_tpu.loadgen.workload import (
        from_trace_log,
        read_trace,
        write_trace,
    )

    log = tmp_path / "gw_spans.jsonl"
    spans = [
        {"name": "gateway.stream", "trace_id": "dtx-1",
         "start_ms": 1000.0, "attrs": {"chars": 40, "adapter": "t-a"}},
        {"name": "engine.request", "trace_id": "dtx-1",
         "start_ms": 1001.0, "attrs": {}},  # replica half: skipped
        {"name": "gateway.request", "trace_id": "dtx-2",
         "start_ms": 1500.0, "attrs": {}},
        {"name": "gateway.stream", "trace_id": "dtx-3",
         "start_ms": 1250.0, "attrs": {"chars": 8}},
    ]
    with open(log, "w", encoding="utf-8") as f:
        for sp in spans:
            f.write(json.dumps(sp) + "\n")

    meta, events = from_trace_log(str(log))
    assert meta["source"] == "trace_log" and meta["requests"] == 3
    # sorted by start, offsets relative to the first span
    assert [e["t"] for e in events] == [0.0, 0.25, 0.5]
    assert events[0]["model"] == "t-a"
    assert events[0]["max_tokens"] == 10  # 40 chars / 4 chars-per-token
    assert events[1]["max_tokens"] == 2
    assert events[2]["max_tokens"] == 16  # non-streamed: default
    assert all(e["messages"][0]["content"] for e in events)
    # converted events survive the dtx-load-trace roundtrip
    out = tmp_path / "converted.jsonl"
    write_trace(str(out), events, meta)
    meta2, events2 = read_trace(str(out))
    assert events2 == events and meta2 == meta

    with pytest.raises(ValueError, match="no gateway request spans"):
        empty = tmp_path / "empty.jsonl"
        empty.write_text(json.dumps({"name": "other"}) + "\n")
        from_trace_log(str(empty))
