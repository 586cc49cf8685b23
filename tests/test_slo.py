"""SLO plane (obs/slo.py) + histogram exemplars: spec validation, windowed
burn-rate math, /debug/slo on both servers, the promotion guard's SLO mode,
exemplar exposition end-to-end (p99 bucket → trace id → /debug/trace), and
the mixed-version scrape-parser tolerance."""

import json
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from datatunerx_tpu.obs.metrics import MS_BUCKETS, Registry
from datatunerx_tpu.obs.slo import (
    SLO,
    SLOEvaluator,
    default_slos,
    parse_slos,
    violations,
)
from tests.test_prometheus_exposition import parse_exposition


def _latency_slo(name="ttft", objective=0.9, threshold=250.0,
                 windows=(60.0, 600.0), metric="dtx_serving_ttft_ms"):
    return SLO.from_dict({
        "name": name, "objective": objective, "windows_s": list(windows),
        "sli": {"kind": "latency", "metric": metric,
                "threshold_ms": threshold}})


def _error_slo(name="avail", objective=0.9,
               metric="dtx_serving_requests_total"):
    return SLO.from_dict({
        "name": name, "objective": objective,
        "sli": {"kind": "error_ratio", "metric": metric,
                "bad": {"code": "^5"}}})


# ----------------------------------------------------------------- specs

def test_spec_validation_rejects_bad_configs():
    with pytest.raises(ValueError, match="objective"):
        SLO.from_dict({"name": "x", "objective": 1.0,
                       "sli": {"kind": "latency", "metric": "m",
                               "threshold": 1}})
    with pytest.raises(ValueError, match="kind"):
        SLO.from_dict({"name": "x", "objective": 0.9,
                       "sli": {"kind": "nope", "metric": "m"}})
    with pytest.raises(ValueError, match="threshold"):
        SLO.from_dict({"name": "x", "objective": 0.9,
                       "sli": {"kind": "latency", "metric": "m"}})
    with pytest.raises(ValueError, match="bad"):
        SLO.from_dict({"name": "x", "objective": 0.9,
                       "sli": {"kind": "error_ratio", "metric": "m"}})
    with pytest.raises(ValueError, match="duplicate"):
        parse_slos([{"name": "a", "objective": 0.9,
                     "sli": {"kind": "latency", "metric": "m",
                             "threshold": 1}}] * 2)
    # every plane's defaults must validate
    for plane in ("gateway", "serving", "loadgen"):
        assert default_slos(plane)


# ------------------------------------------------------------- evaluation

def test_latency_windowed_compliance_and_burn_rate():
    reg = Registry()
    h = reg.histogram("dtx_serving_ttft_ms", buckets=MS_BUCKETS)
    import time

    slo = _latency_slo(objective=0.9, threshold=250.0)
    ev = SLOEvaluator(reg, [slo])
    t0 = time.monotonic()  # fake stamps anchored AFTER the ctor baseline
    ev.sample(now=t0)
    # 8 fast + 2 slow = 80% compliance against a 90% objective
    for _ in range(8):
        h.observe(10.0)
    for _ in range(2):
        h.observe(5000.0)
    out = ev.evaluate(now=t0 + 30.0)
    assert len(out) == 1
    w = out[0]["windows"][0]
    assert (w["good"], w["total"]) == (8, 10)
    assert w["compliance"] == pytest.approx(0.8)
    assert w["burn_rate"] == pytest.approx(2.0)  # 20% bad / 10% budget
    assert out[0]["compliant"] is False  # both windows burning > 1.0
    assert out[0]["budget_remaining"] == 0.0
    assert out[0]["threshold_effective"] == 250.0


def test_multi_window_rule_needs_every_window_burning():
    reg = Registry()
    h = reg.histogram("dtx_serving_ttft_ms", buckets=MS_BUCKETS)
    import time

    slo = _latency_slo(objective=0.9, windows=(60.0, 600.0))
    ev = SLOEvaluator(reg, [slo])
    t0 = time.monotonic()
    ev.sample(now=t0)
    for _ in range(98):
        h.observe(10.0)
    ev.sample(now=t0 + 560.0)  # long-window baseline: 98 good, 0 bad
    for _ in range(2):
        h.observe(9000.0)  # a fast-window spike
    out = ev.evaluate(now=t0 + 600.0)[0]
    fast, slow = out["windows"]
    assert fast["burn_rate"] > 1.0          # fast window on fire
    assert slow["burn_rate"] <= 1.0         # 2% bad over the long window
    assert out["compliant"] is True         # not material yet — no page


def test_error_ratio_label_matching():
    reg = Registry()
    c = reg.counter("dtx_serving_requests_total")
    ev = SLOEvaluator(reg, [_error_slo(objective=0.9)])
    ev.sample()
    for code, n in (("200", 7), ("429", 1), ("500", 1), ("503", 1)):
        for _ in range(n):
            c.inc({"code": code})
    v = ev.verdicts()[0]
    # 429 counts as served (good); 5xx are the bad events
    assert (v["good"], v["total"]) == (8, 10)
    assert v["compliant"] is False
    assert "avail" in violations([v])[0]
    assert "0.9" in violations([v])[0]  # the objective is NAMED


def test_counter_reset_clamps_to_zero_delta():
    reg = Registry()
    c = reg.counter("dtx_serving_requests_total")
    for _ in range(5):
        c.inc({"code": "500"})
    ev = SLOEvaluator(reg, [_error_slo()])
    ev.sample()
    c.clear()  # a swapped engine restarting its counters
    v = ev.verdicts()[0]
    assert v["no_data"] is True and v["compliant"] is True


def test_restated_gauges_expose_cleanly():
    reg = Registry()
    h = reg.histogram("dtx_serving_ttft_ms", buckets=MS_BUCKETS)
    ev = SLOEvaluator(reg, default_slos("serving"))
    h.observe(10.0)
    ev.restate_gauges(ev.evaluate())
    samples, types = parse_exposition(reg.expose())
    assert types["dtx_slo_objective"] == "gauge"
    key = ("dtx_slo_compliant", (("slo", "serving-ttft-p95"),))
    assert samples[key] == 1
    assert ("dtx_slo_burn_rate",
            (("slo", "serving-ttft-p95"), ("window", "300s"))) in samples


# -------------------------------------------------------------- exemplars

def test_exemplar_kept_per_bucket_and_exposed():
    reg = Registry()
    h = reg.histogram("dtx_serving_ttft_ms", buckets=MS_BUCKETS)
    h.observe(3.0)                      # no trace id → no exemplar
    assert h.exemplars() == {}
    h.observe(3.0, trace_id="dtx-aa")
    h.observe(4.0, trace_id="dtx-bb")   # same bucket: LAST exemplar wins
    h.observe(9000.0, trace_id="dtx-slow")
    ex = h.exemplars()
    assert ex[5.0][0] == "dtx-bb"
    assert ex[10000.0][0] == "dtx-slow"
    text = reg.expose()
    assert '# {trace_id="dtx-bb"} 4.0' in text
    parse_exposition(text)  # valid format, bucket lines only


def test_exemplar_end_to_end_gateway(tmp_path):
    """Acceptance: a latency bucket's exemplar names a trace id that
    GET /debug/trace/<id> resolves."""
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway, serve

    class _Eng:
        def chat(self, messages, **kw):
            return "ok"

    gw = Gateway(ReplicaPool([InProcessReplica("r0", _Eng())]),
                 model_name="m")
    srv = serve(gw, port=0, host="127.0.0.1")
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_port}"
    try:
        req = urllib.request.Request(
            url + "/chat/completions",
            data=json.dumps({"messages": [
                {"role": "user", "content": "hi"}]}).encode(),
            headers={"Content-Type": "application/json",
                     "X-DTX-Trace-Id": "dtx-exemplar-e2e"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
        # the default wire is classic-parser safe: NO exemplar tails
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            plain = r.read().decode()
        assert " # {" not in plain
        parse_exposition(plain)
        # the explicit debug view carries them
        with urllib.request.urlopen(url + "/metrics?exemplars=1",
                                    timeout=10) as r:
            text = r.read().decode()
        parse_exposition(text)
        # find the exemplar on the gateway latency histogram and follow it
        tid = None
        for line in text.splitlines():
            if (line.startswith("dtx_gateway_request_latency_seconds_bucket")
                    and "# {trace_id=" in line):
                tid = line.split('trace_id="')[1].split('"')[0]
                break
        assert tid == "dtx-exemplar-e2e"
        with urllib.request.urlopen(
                url + "/debug/trace/" + tid, timeout=10) as r:
            doc = json.load(r)
        assert doc["trace_id"] == tid and doc["spans"]
    finally:
        srv.shutdown()
        gw.close()


def test_engine_tracing_off_observes_no_exemplars():
    """The tracing-off observe path must not attach exemplars (the
    zero-cost contract the token-parity test rides on)."""
    from datatunerx_tpu.obs.metrics import serving_latency_histograms

    reg = Registry()
    ttft, tpot = serving_latency_histograms(reg)
    ttft.observe(5.0)   # what _complete does with tracing=False
    tpot.observe(1.0)
    assert ttft.exemplars() == {} and tpot.exemplars() == {}


# ------------------------------------------------------------- /debug/slo

def test_gateway_debug_slo_http():
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway, serve

    class _Eng:
        def chat(self, messages, **kw):
            return "ok"

    gw = Gateway(ReplicaPool([InProcessReplica("r0", _Eng())]),
                 model_name="m")
    srv = serve(gw, port=0, host="127.0.0.1")
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_port}"
    try:
        gw.chat({"messages": [{"role": "user", "content": "hi"}]},
                trace_id="t1")
        gw.record_request(200)
        with urllib.request.urlopen(url + "/debug/slo", timeout=10) as r:
            doc = json.load(r)
        assert doc["plane"] == "gateway"
        names = {s["name"] for s in doc["slos"]}
        assert {"gateway-availability", "gateway-fast-requests"} <= names
        assert doc["compliant"] is True
    finally:
        srv.shutdown()
        gw.close()


def test_serving_debug_slo_http():
    from datatunerx_tpu.serving import server as serving

    old_engine, old_slo = serving.STATE.engine, serving.STATE.slo
    serving.STATE.engine = None
    serving.STATE.slo = None
    srv = ThreadingHTTPServer(("127.0.0.1", 0), serving.Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.server_port}/debug/slo",
                timeout=10) as r:
            doc = json.load(r)
        assert doc["plane"] == "serving"
        assert {s["name"] for s in doc["slos"]} == {
            "serving-availability", "serving-ttft-p95"}
    finally:
        srv.shutdown()
        serving.STATE.engine = old_engine
        serving.STATE.slo = old_slo


# ------------------------------------------------- scrape-parser tolerance

def test_http_replica_scrape_tolerates_exemplars():
    """Mixed-version fleet regression: a replica whose /metrics carries
    exemplar annotations (and unknown trailing annotations) must still
    scrape-parse into stats."""
    from datatunerx_tpu.gateway.replica_pool import HTTPReplica

    exposition = "\n".join([
        "# TYPE dtx_serving_slots_busy gauge",
        "dtx_serving_slots_busy 2",
        "# TYPE dtx_serving_slots_capacity gauge",
        "dtx_serving_slots_capacity 4 # future-annotation",
        "# TYPE dtx_serving_kv_blocks_free gauge",
        "dtx_serving_kv_blocks_free 77",
        "# TYPE dtx_serving_kv_blocks_capacity gauge",
        "dtx_serving_kv_blocks_capacity 128",
        "# TYPE dtx_serving_adapter_resident gauge",
        'dtx_serving_adapter_resident{adapter="t-a"} 1',
        '# TYPE dtx_serving_adapter_registered gauge',
        'dtx_serving_adapter_registered{adapter="t-a"} 1',
        'dtx_serving_adapter_registered{adapter="t # b"} 1',
        "# TYPE dtx_serving_ttft_ms histogram",
        'dtx_serving_ttft_ms_bucket{le="5.0"} 3 '
        '# {trace_id="dtx-abc"} 4.2 1700000000.1',
        'dtx_serving_ttft_ms_bucket{le="+Inf"} 3',
        "dtx_serving_ttft_ms_sum 12.0",
        "dtx_serving_ttft_ms_count 3",
    ]) + "\n"

    class _H(BaseHTTPRequestHandler):
        def do_GET(self):
            body = exposition.encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), _H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        rep = HTTPReplica("r0", f"http://127.0.0.1:{srv.server_port}")
        st = rep.stats()
        assert st["slots_busy"] == 2 and st["slots_total"] == 4
        assert st["kv_blocks_free"] == 77 and st["kv_blocks_total"] == 128
        assert st["resident_adapters"] == {"t-a"}
        # a label VALUE containing " # " is data, not an annotation
        assert st["adapters"] == {"t-a", "t # b"}
    finally:
        srv.shutdown()


# ------------------------------------------------------ promotion SLO mode

def test_promotion_slo_verdict_mode_rolls_back_naming_objective():
    from datatunerx_tpu.experiment.promotion import (
        PromotionConfig,
        PromotionController,
        ROLLED_BACK,
        SHIFTING,
    )
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway

    class _Eng:
        def chat(self, messages, **kw):
            return "ok"

    pool = ReplicaPool([InProcessReplica("fleet-0", _Eng()),
                        InProcessReplica("canary", _Eng())])
    gw = Gateway(pool, model_name="m")
    try:
        cfg = PromotionConfig.from_dict({
            "schedule": [0.5, 1.0], "step_s": 0.0, "min_requests": 1,
            "slo_min_events": 2,
            "slos": [{
                "name": "promo-availability", "objective": 0.99,
                "sli": {"kind": "error_ratio",
                        "metric": "dtx_gateway_requests_total",
                        "bad": {"code": "^5"}}}],
        })
        promo = PromotionController(gw, "canary", config=cfg)
        assert promo.tick() == SHIFTING  # stage 0 begins, SLO sampled
        # stage traffic: mostly healthy, but 5xx blows the 99% objective
        canary = pool.get("canary")
        for _ in range(3):
            canary.record_outcome(True, 1.0)
        for code in (200, 200, 500):
            gw.record_request(code)
        assert promo.tick() == ROLLED_BACK
        assert "promo-availability" in promo.reason
        assert "0.99" in promo.reason
        assert promo.status()["slos"][0]["compliant"] is False
    finally:
        gw.close()


def test_promotion_slo_mode_clean_run_completes():
    from datatunerx_tpu.experiment.promotion import (
        COMPLETED,
        PromotionConfig,
        PromotionController,
        TERMINAL,
    )
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway

    class _Eng:
        def chat(self, messages, **kw):
            return "ok"

    pool = ReplicaPool([InProcessReplica("fleet-0", _Eng()),
                        InProcessReplica("canary", _Eng())])
    gw = Gateway(pool, model_name="m")
    try:
        cfg = PromotionConfig.from_dict({
            "schedule": [0.5, 1.0], "step_s": 0.0, "min_requests": 1,
            "slo_min_events": 2,
            "slos": [{
                "name": "promo-availability", "objective": 0.99,
                "sli": {"kind": "error_ratio",
                        "metric": "dtx_gateway_requests_total",
                        "bad": {"code": "^5"}}}],
        })
        promo = PromotionController(gw, "canary", config=cfg)
        canary = pool.get("canary")
        for _ in range(24):
            if promo.state in TERMINAL:
                break
            canary.record_outcome(True, 1.0)
            gw.record_request(200)
            promo.tick()
        assert promo.state == COMPLETED
    finally:
        gw.close()


def test_promotion_slo_guard_runs_with_zero_canary_traffic():
    """A fleet-wide SLO breach rolls the promotion back even when the
    stage routed no requests to the canary (the SLO judges the gateway's
    registry, not the canary's outcome window)."""
    from datatunerx_tpu.experiment.promotion import (
        PromotionConfig,
        PromotionController,
        ROLLED_BACK,
        SHIFTING,
    )
    from datatunerx_tpu.gateway.replica_pool import (
        InProcessReplica,
        ReplicaPool,
    )
    from datatunerx_tpu.gateway.server import Gateway

    class _Eng:
        def chat(self, messages, **kw):
            return "ok"

    pool = ReplicaPool([InProcessReplica("fleet-0", _Eng()),
                        InProcessReplica("canary", _Eng())])
    gw = Gateway(pool, model_name="m")
    try:
        cfg = PromotionConfig.from_dict({
            "schedule": [0.5, 1.0], "step_s": 0.0, "min_requests": 1,
            "slo_min_events": 2,
            "slos": [{
                "name": "fleet-availability", "objective": 0.99,
                "sli": {"kind": "error_ratio",
                        "metric": "dtx_gateway_requests_total",
                        "bad": {"code": "^5"}}}],
        })
        promo = PromotionController(gw, "canary", config=cfg)
        assert promo.tick() == SHIFTING
        # fleet-wide 5xx during the stage; the canary served NOTHING
        for code in (200, 500, 500):
            gw.record_request(code)
        assert promo.tick() == ROLLED_BACK
        assert "fleet-availability" in promo.reason
    finally:
        gw.close()
