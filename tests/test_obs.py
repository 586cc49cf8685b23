"""Unified observability plane (datatunerx_tpu/obs, PR 7).

Three contracts under test:

  spans    — lifecycle (open→close, nesting, orphan reap), the bounded
             trace ring (MRU eviction, per-trace span cap, JSONL log),
             and the engine bridge that folds scheduler timelines into
             per-request spans with true TTFT/TPOT.
  metrics  — MS_BUCKETS histogram bucket math and exposition round-trip
             through the PR 2 parser; the serving/gateway /metrics now
             built from ONE registry (build info, uptime, latency
             histograms all in a single valid exposition).
  end2end  — GET /debug/trace/<id> on the gateway returns the merged
             gateway→replica→engine timeline for both in-process and
             HTTP replicas, and tracing is decode-invisible: enabled vs
             disabled engines emit token-exact outputs.
"""

import json
import threading
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

from datatunerx_tpu.obs.metrics import (
    MS_BUCKETS,
    Histogram,
    Registry,
    set_build_info,
    set_uptime,
)
from datatunerx_tpu.obs.trace import (
    Span,
    Tracer,
    TraceStore,
    build_request_span,
)
from tests.test_prometheus_exposition import parse_exposition


# ------------------------------------------------------------- histograms

def test_ms_buckets_histogram_bucket_math():
    h = Histogram("t_ms", buckets=MS_BUCKETS)
    for v in (0.4, 2.0, 9.9, 10.0, 600.0, 50_000.0):
        h.observe(v)
    samples, types = parse_exposition(
        "\n".join(h.expose()) + "\n")
    assert types["t_ms"] == "histogram"
    # cumulative counts at the edges the observes straddle
    assert samples[("t_ms_bucket", (("le", "1.0"),))] == 1
    assert samples[("t_ms_bucket", (("le", "2.5"),))] == 2
    # 10.0 lands IN the le=10 bucket (le is inclusive)
    assert samples[("t_ms_bucket", (("le", "10.0"),))] == 4
    assert samples[("t_ms_bucket", (("le", "1000.0"),))] == 5
    assert samples[("t_ms_bucket", (("le", "+Inf"),))] == 6
    assert samples[("t_ms_count", ())] == 6
    assert samples[("t_ms_sum", ())] == pytest.approx(50622.3)


def test_registry_shared_across_planes_single_exposition():
    reg = Registry()
    set_build_info(reg, "serving")
    set_uptime(reg, "serving")
    reg.histogram("dtx_serving_ttft_ms", buckets=MS_BUCKETS).observe(12.0)
    samples, types = parse_exposition(reg.expose())
    assert types["dtx_build_info"] == "gauge"
    assert types["dtx_serving_uptime_seconds"] == "gauge"
    assert types["dtx_serving_ttft_ms"] == "histogram"
    key = next(k for k in samples if k[0] == "dtx_build_info")
    assert ("plane", "serving") in key[1]


def test_registry_returns_same_metric_object():
    reg = Registry()
    assert reg.counter("a_total") is reg.counter("a_total")
    assert reg.histogram("b_ms") is reg.histogram("b_ms")


# ------------------------------------------------------------------ spans

def test_span_lifecycle_nesting_and_store():
    store = TraceStore()
    tracer = Tracer(store=store)
    with tracer.span("outer", trace_id="t1") as outer:
        outer.event("hello", k=1)
        with tracer.span("inner") as inner:  # inherits t1 via contextvar
            assert inner.trace_id == "t1"
            assert inner.parent == "outer"
    doc = store.get("t1")
    names = {s["name"]: s for s in doc["spans"]}
    assert set(names) == {"outer", "inner"}
    assert names["outer"]["status"] == "ok"
    assert names["outer"]["duration_ms"] >= 0
    assert names["outer"]["events"][0]["name"] == "hello"
    assert tracer.open_count() == 0


def test_span_error_status_on_exception():
    tracer = Tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("boom", trace_id="t2"):
            raise RuntimeError("kaput")
    doc = tracer.store.get("t2")
    assert doc["spans"][0]["status"] == "error"
    assert "kaput" in doc["spans"][0]["attrs"]["error"]


def test_explicit_start_finish_no_contextvar_leak():
    tracer = Tracer()
    sp = tracer.start("gen", trace_id="t3", parent=None)
    # explicit spans never install themselves as the ambient parent
    with tracer.span("other", trace_id="t4") as other:
        assert other.parent is None
    tracer.finish(sp)
    assert tracer.store.get("t3") is not None


def test_orphan_reap():
    tracer = Tracer(orphan_age_s=0.0)
    sp = tracer.start("leaked", trace_id="t5")
    assert tracer.open_count() == 1
    assert tracer.reap_orphans(max_age_s=0.0) == 1
    assert tracer.open_count() == 0
    doc = tracer.store.get("t5")
    assert doc["spans"][0]["status"] == "orphaned"
    # a request that outlived the reaper and then completed must not land
    # in the trace a second time
    tracer.finish(sp)
    assert len(tracer.store.get("t5")["spans"]) == 1


def test_trace_ring_eviction_and_span_cap():
    store = TraceStore(capacity=2, max_spans_per_trace=3)
    for tid in ("a", "b", "c"):
        store.add(Span("s", trace_id=tid).to_dict())
    # capacity 2: oldest trace evicted whole
    assert store.get("a") is None
    assert store.get("b") is not None and store.get("c") is not None
    assert store.evictions == 1
    # adding to an existing trace bumps it to MRU: "b" survives the next add
    store.add(Span("s2", trace_id="b").to_dict())
    store.add(Span("s", trace_id="d").to_dict())
    assert store.get("b") is not None
    assert store.get("c") is None
    # span cap: extra spans dropped, trace retained
    for i in range(5):
        store.add(Span(f"s{i}", trace_id="d").to_dict())
    assert len(store.get("d")["spans"]) == 3


def test_trace_store_jsonl_log(tmp_path):
    path = tmp_path / "spans.jsonl"
    store = TraceStore(jsonl_path=str(path))
    store.add(Span("one", trace_id="x").to_dict())
    store.add(Span("two", trace_id="y").to_dict())
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [ln["name"] for ln in lines] == ["one", "two"]


# ---------------------------------------------------------- engine bridge

def test_build_request_span_ttft_tpot_math():
    """Scripted admit/prefill/activate/decode sequence: the derived
    TTFT/TPOT must be the wall deltas of the scripted stamps."""
    t0 = 100.0
    timeline = [(t0 + 0.001, "admit", {"slot": 0, "mode": "chunked"}),
                (t0 + 0.010, "prefill", {"tokens": 64}),
                (t0 + 0.020, "prefill", {"tokens": 64}),
                (t0 + 0.025, "activate", {"slot": 0}),
                (t0 + 0.200, "finish", {"slot": 0})]
    first, last, n = t0 + 0.050, t0 + 0.170, 7
    span = build_request_span("tid", t0, timeline, first, last, n,
                              wall_submit_ms=1.7e12)
    assert span["trace_id"] == "tid"
    assert span["attrs"]["ttft_ms"] == pytest.approx(50.0)
    assert span["attrs"]["tpot_ms"] == pytest.approx(20.0)  # 120ms / 6
    assert span["attrs"]["n_tokens"] == 7
    # events sorted by offset; duration covers through the last stamp
    names = [e["name"] for e in span["events"]]
    assert names == ["admit", "prefill", "prefill", "activate",
                     "first_token", "finish"]
    assert span["duration_ms"] == pytest.approx(200.0)
    assert span["status"] == "ok"


def test_build_request_span_error_and_no_tokens():
    span = build_request_span("tid", 10.0, [(10.001, "admit", {})],
                              None, None, 0, wall_submit_ms=0.0,
                              error="device fault")
    assert span["status"] == "error"
    assert span["attrs"]["error"] == "device fault"
    assert "ttft_ms" not in span["attrs"]


# --------------------------------------------------------- engine tracing

MODEL = "preset:debug"


@pytest.fixture(scope="module")
def traced_engine():
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4)
    yield eng
    eng.close()


def test_engine_request_span_timeline(traced_engine):
    eng = traced_engine
    ids = eng.tokenizer.encode("observability plane test prompt")
    req = eng.submit(ids, max_new_tokens=6, trace_id="trace-eng-1")
    assert req.done.wait(timeout=120)
    doc = eng.trace_store.get("trace-eng-1")
    assert doc is not None
    span = doc["spans"][0]
    assert span["name"] == "engine.request"
    names = [e["name"] for e in span["events"]]
    assert names[0] == "admit"
    assert "first_token" in names and "finish" in names
    assert span["attrs"]["n_tokens"] == len(req.tokens)
    assert span["attrs"]["ttft_ms"] > 0
    assert span["attrs"]["tpot_ms"] > 0
    # the shared-registry histograms saw the same request
    assert eng.registry.histogram("dtx_serving_ttft_ms").count >= 1
    assert eng.registry.histogram("dtx_serving_tpot_ms").count >= 1


def test_engine_mints_trace_id_when_absent(traced_engine):
    eng = traced_engine
    ids = eng.tokenizer.encode("no id supplied")
    req = eng.submit(ids, max_new_tokens=3)
    assert req.done.wait(timeout=120)
    assert req.trace_id.startswith("dtx-")
    assert eng.trace_store.get(req.trace_id) is not None


def test_tracing_disabled_is_token_exact(traced_engine):
    """Side-by-side: a tracing-disabled engine must decode the exact same
    tokens (greedy) — instrumentation cannot perturb the model."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng_off = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                            slots=2, decode_chunk=4, tracing=False)
    try:
        ids = traced_engine.tokenizer.encode(
            "the quick brown fox inspects the telemetry")
        out_on = traced_engine.generate(list(ids), max_new_tokens=12)
        out_off = eng_off.generate(list(ids), max_new_tokens=12)
        assert out_on == out_off
        assert len(eng_off.trace_store) == 0  # nothing recorded when off
    finally:
        eng_off.close()


def test_engine_chunked_prefill_span_events():
    """A chunked admission's span carries the prefill chunk events the PR 5
    sched_trace only kept in a test deque."""
    from datatunerx_tpu.serving.batched_engine import BatchedEngine

    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        prefill_chunk=64, prefill_token_budget=64)
    try:
        ids = (eng.tokenizer.encode("long context ") * 40)[:150]
        req = eng.submit(ids, max_new_tokens=4, trace_id="trace-chunked")
        assert req.done.wait(timeout=120)
        span = eng.trace_store.get("trace-chunked")["spans"][0]
        names = [e["name"] for e in span["events"]]
        assert names[0] == "admit"
        assert names.count("prefill") >= 2  # 150 tokens / 64-chunk
        assert "activate" in names
    finally:
        eng.close()


# ------------------------------------------------- gateway /debug endpoints

class _TracedFakeEngine:
    """Duck-typed engine with the real trace plumbing: records an
    engine-side span per chat under the caller's trace id."""

    def __init__(self):
        self.trace_store = TraceStore()
        self.slots = 2
        self._slot_req = [None, None]
        self.prefill_stats = {"full": 0, "reuse": 0, "extend": 0}

    def chat(self, messages, trace_id="", **kw):
        self.trace_store.add(
            build_request_span(trace_id, 1.0,
                               [(1.001, "admit", {"slot": 0})],
                               1.05, 1.17, 7, wall_submit_ms=0.0))
        return "fake reply"


def _gateway(replicas):
    from datatunerx_tpu.gateway.replica_pool import ReplicaPool
    from datatunerx_tpu.gateway.server import Gateway

    return Gateway(ReplicaPool(replicas), model_name="preset:test")


def test_gateway_debug_trace_inprocess_merge():
    from datatunerx_tpu.gateway.replica_pool import InProcessReplica

    gw = _gateway([InProcessReplica("r0", _TracedFakeEngine())])
    try:
        out = gw.chat({"messages": [{"role": "user", "content": "hi"}]},
                      trace_id="t-merge")
        assert out == "fake reply"
        doc = gw.trace("t-merge")
        names = [s["name"] for s in doc["spans"]]
        assert "gateway.request" in names and "engine.request" in names
        engine_span = next(s for s in doc["spans"]
                           if s["name"] == "engine.request")
        assert engine_span["replica"] == "r0"
        assert engine_span["attrs"]["ttft_ms"] == pytest.approx(50.0)
        assert engine_span["attrs"]["tpot_ms"] == pytest.approx(20.0)
        gw_span = next(s for s in doc["spans"]
                       if s["name"] == "gateway.request")
        assert [e["name"] for e in gw_span["events"]][:2] == [
            "admitted", "route"]
        # queue-wait histogram observed exactly one admission
        assert gw.registry.histogram("dtx_gateway_queue_wait_ms").count == 1
    finally:
        gw.close()


@pytest.fixture()
def serving_http_url():
    """A real serving HTTP server (ThreadingHTTPServer + the serving
    Handler) fronting the traced fake engine — the HTTP-replica half."""
    from datatunerx_tpu.serving import server as serving

    old_engine, old_model = serving.STATE.engine, serving.STATE.model_path
    serving.STATE.engine = _TracedFakeEngine()
    serving.STATE.model_path = "preset:test"
    srv = ThreadingHTTPServer(("127.0.0.1", 0), serving.Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()
    serving.STATE.engine = old_engine
    serving.STATE.model_path = old_model


def test_gateway_debug_trace_http_replica_merge(serving_http_url):
    """End-to-end over HTTP: gateway → X-DTX-Trace-Id header → serving
    handler → engine trace ring → GET /debug/trace merge at the gateway."""
    from datatunerx_tpu.gateway.replica_pool import HTTPReplica
    from datatunerx_tpu.gateway.server import serve

    gw = _gateway([HTTPReplica("r0", serving_http_url)])
    srv = serve(gw, port=0, host="127.0.0.1")
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_port}"
    try:
        body = json.dumps(
            {"messages": [{"role": "user", "content": "hi"}]}).encode()
        req = urllib.request.Request(
            url + "/chat/completions", data=body,
            headers={"Content-Type": "application/json",
                     "X-DTX-Trace-Id": "t-http"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers["X-DTX-Trace-Id"] == "t-http"
        # replica half served by serving's own /debug/trace endpoint
        with urllib.request.urlopen(
                serving_http_url + "/debug/trace/t-http", timeout=10) as r:
            rdoc = json.load(r)
        assert rdoc["spans"][0]["name"] == "engine.request"
        # merged view at the gateway
        with urllib.request.urlopen(
                url + "/debug/trace/t-http", timeout=10) as r:
            doc = json.load(r)
        names = [s["name"] for s in doc["spans"]]
        assert "gateway.request" in names and "engine.request" in names
        # unknown id → 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/debug/trace/nope", timeout=10)
        assert e.value.code == 404
    finally:
        srv.shutdown()
        gw.close()


def test_gateway_stream_failover_trace():
    """A mid-stream replica death shows up in the trace as a retry event
    with the resumption offset."""
    from datatunerx_tpu.gateway.replica_pool import InProcessReplica

    class DyingEngine:
        def chat_stream(self, messages, **kw):
            yield "hel"
            raise RuntimeError("replica died mid-stream")

    class HealthyEngine:
        def chat_stream(self, messages, **kw):
            yield "hello"
            yield " world"

    gw = _gateway([InProcessReplica("dying", DyingEngine()),
                   InProcessReplica("ok", HealthyEngine())])
    # force deterministic routing order: dying first
    gw.router.policy = "round_robin"
    try:
        text = "".join(gw.chat_stream(
            {"messages": [{"role": "user", "content": "hi"}]},
            trace_id="t-failover"))
        assert text == "hello world"
        span = next(s for s in gw.trace("t-failover")["spans"]
                    if s["name"] == "gateway.stream")
        events = [e["name"] for e in span["events"]]
        assert "retry" in events
        retry = next(e for e in span["events"] if e["name"] == "retry")
        assert retry["resumed_at_char"] == 3
        assert span["attrs"]["attempts"] == 2
    finally:
        gw.close()


def test_gateway_metrics_has_build_info_uptime_and_queue_wait():
    from datatunerx_tpu.gateway.replica_pool import InProcessReplica

    gw = _gateway([InProcessReplica("r0", _TracedFakeEngine())])
    try:
        gw.chat({"messages": [{"role": "user", "content": "hi"}]},
                trace_id="t-m")
        samples, types = parse_exposition(gw.metrics_text())
        assert types["dtx_build_info"] == "gauge"
        assert types["dtx_gateway_uptime_seconds"] == "gauge"
        assert types["dtx_gateway_queue_wait_ms"] == "histogram"
        assert samples[("dtx_gateway_queue_wait_ms_count", ())] == 1
        assert samples[("dtx_gateway_trace_open_spans", ())] == 0
    finally:
        gw.close()


def test_serving_metrics_histograms_from_shared_registry(serving_http_url):
    # the requests counter exists from the first request the process's registry
    # records: this test may be the first on its worker to send one
    with urllib.request.urlopen(serving_http_url + "/healthz", timeout=10) as r:
        assert r.status == 200
    with urllib.request.urlopen(serving_http_url + "/metrics",
                                timeout=10) as r:
        samples, types = parse_exposition(r.read().decode())
    assert types["dtx_serving_ttft_ms"] == "histogram"
    assert types["dtx_serving_tpot_ms"] == "histogram"
    assert types["dtx_build_info"] == "gauge"
    assert types["dtx_serving_uptime_seconds"] == "gauge"
    assert types["dtx_serving_requests_total"] == "counter"
    assert samples[("dtx_serving_slots_capacity", ())] == 2


# ------------------------------------------------------------- profiling

def test_profiler_single_flight(tmp_path, monkeypatch):
    """One capture at a time per process; stubbed jax.profiler so the test
    exercises the gating, not XLA."""
    import jax

    from datatunerx_tpu.obs.profiling import Profiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    p = Profiler()
    assert p.start(str(tmp_path / "t1"), seconds=30) == 30.0
    assert p.status()["dir"].endswith("t1")
    assert p.start(str(tmp_path / "t2"), seconds=30) is None  # refused
    p.close()  # cancels the window, joins the worker
    assert p.status() is None
    assert [c[0] for c in calls] == ["start", "stop"]
    # the returned window is the CLAMPED one the worker will actually run
    assert p.start(str(tmp_path / "t3"), seconds=600) == 120.0
    p.close()


def test_resolve_profile_dir_confinement(tmp_path, monkeypatch):
    from datatunerx_tpu.obs.profiling import resolve_profile_dir

    monkeypatch.setenv("DTX_PROFILE_DIR", str(tmp_path))
    assert resolve_profile_dir("run1") == str(tmp_path / "run1")
    assert resolve_profile_dir(str(tmp_path / "abs")) == str(
        tmp_path / "abs")
    auto = resolve_profile_dir(None)
    assert auto.startswith(str(tmp_path))
    with pytest.raises(ValueError):
        resolve_profile_dir("../outside")
    with pytest.raises(ValueError):
        resolve_profile_dir("/etc/cron.d")


def test_serving_debug_profile_endpoint(serving_http_url, tmp_path,
                                        monkeypatch):
    import jax

    from datatunerx_tpu.obs import profiling

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(profiling, "_PROFILER", profiling.Profiler())
    monkeypatch.setenv("DTX_PROFILE_DIR", str(tmp_path))

    def post(payload):
        body = json.dumps(payload).encode()
        return urllib.request.urlopen(urllib.request.Request(
            serving_http_url + "/debug/profile", data=body,
            headers={"Content-Type": "application/json"}, method="POST"),
            timeout=10)

    try:
        # a dir escaping the allowed root is refused before any state change
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"seconds": 1, "dir": "../escape"})
        assert e.value.code == 400
        with post({"seconds": 600, "dir": str(tmp_path / "p")}) as r:
            assert r.status == 202
            out = json.load(r)
            assert out["profiling"].endswith("p")
            assert out["seconds"] == 120.0  # echoed CLAMPED, not requested
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"seconds": 30, "dir": str(tmp_path / "q")})
        assert e.value.code == 409  # second capture refused, not corrupted
    finally:
        profiling.process_profiler().close()


# --------------------------------------------------------- training logger

def test_metrics_logger_prom_exposition(tmp_path):
    from datatunerx_tpu.training.metrics_log import MetricsLogger

    lg = MetricsLogger(str(tmp_path), total_steps=100, uid="u1")
    lg.log_train(10, {"loss": 1.25, "lr": 1e-4,
                      "pipe_step_wait_ms": 0.7, "pipe_queue_depth": 1.5})
    lg.log_eval(10, {"eval_loss": 2.5, "rouge-1": 0.5})
    prom = (tmp_path / "watch" / "metrics.prom").read_text()
    samples, types = parse_exposition(prom)
    assert samples[("dtx_train_loss", (("uid", "u1"),))] == 1.25
    # the pipeline-health signals ROADMAP wants for prefetch autotuning
    assert samples[("dtx_train_pipe_step_wait_ms", (("uid", "u1"),))] == 0.7
    assert samples[("dtx_train_pipe_queue_depth", (("uid", "u1"),))] == 1.5
    assert samples[("dtx_eval_eval_loss", (("uid", "u1"),))] == 2.5
    # jsonl key "rouge-1" sanitized into a valid metric name
    assert ("dtx_eval_rouge_1", (("uid", "u1"),)) in samples
    assert types["dtx_build_info"] == "gauge"


def test_metrics_logger_jsonl_behavior_unchanged(tmp_path):
    """The registry mirror is additive: the jsonl record a `dtx train` user
    watches is byte-for-byte what the pre-PR logger wrote (loss parity)."""
    from datatunerx_tpu.training.metrics_log import MetricsLogger

    lg = MetricsLogger(str(tmp_path), total_steps=10)
    lg.log_train(1, {"loss": 0.5, "lr": 3e-4})
    rec = json.loads(
        (tmp_path / "watch" / "trainer_log.jsonl").read_text())
    assert rec["loss"] == 0.5
    assert rec["lr"] == 3e-4
    assert rec["current_steps"] == 1
    assert rec["total_steps"] == 10
    assert set(rec) == {"current_steps", "total_steps", "percentage",
                        "elapsed_time", "eta", "loss", "lr"}
