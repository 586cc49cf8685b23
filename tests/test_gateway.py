"""Inference gateway (gateway/): routing, admission control, circuit
breakers, mid-stream failover, autoscale hints, and operator wiring.

CPU-only and model-free: replicas wrap duck-typed fake engines (the
InProcessReplica contract), so every scenario — including killing a replica
mid-stream — runs in milliseconds. The HTTP surface is exercised through a
real ThreadingHTTPServer on a loopback port.
"""

import json
import subprocess
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from datatunerx_tpu.gateway.admission import (
    AdmissionController,
    Overloaded,
    estimate_prompt_tokens,
)
from datatunerx_tpu.gateway.autoscale import autoscale_hint, parse_hint
from datatunerx_tpu.gateway.replica_pool import (
    CircuitBreaker,
    HTTPReplica,
    InProcessReplica,
    NoReplicaAvailable,
    ReplicaError,
    ReplicaPool,
)
from datatunerx_tpu.gateway.router import session_key
from datatunerx_tpu.gateway.server import Gateway, ManagedReplicaSet, serve


class FakeEngine:
    """Duck-typed engine: chat/chat_stream/slots/_slot_req/adapter_ids."""

    def __init__(self, name, reply="hello world", slots=4, adapters=(),
                 delay=0.0, die_after_deltas=None):
        self.name = name
        self.reply = reply
        self.slots = slots
        self._slot_req = [None] * slots
        self.adapter_ids = {"": 0, **{a: i + 1 for i, a in enumerate(adapters)}}
        self.delay = delay
        self.die_after_deltas = die_after_deltas
        self.dead = False
        self.calls = 0

    def chat(self, messages, **kw):
        self.calls += 1
        if self.dead:
            raise RuntimeError(f"{self.name} is dead")
        if self.delay:
            time.sleep(self.delay)
        return self.reply

    def chat_stream(self, messages, **kw):
        self.calls += 1
        # two-char deltas, dying after die_after_deltas when configured
        for i in range(0, len(self.reply), 2):
            if self.dead:
                raise RuntimeError(f"{self.name} died mid-stream")
            if (self.die_after_deltas is not None
                    and i // 2 >= self.die_after_deltas):
                self.dead = True
                raise RuntimeError(f"{self.name} died mid-stream")
            if self.delay:
                time.sleep(self.delay)
            yield self.reply[i:i + 2]


def make_gateway(engines, policy="least_busy", admission=None, **gw_kw):
    pool = ReplicaPool([InProcessReplica(e.name, e) for e in engines])
    return Gateway(pool, policy=policy, admission=admission, **gw_kw)


MSGS = [{"role": "user", "content": "hi there"}]


# ---------------------------------------------------------------- breakers
def test_circuit_breaker_lifecycle():
    b = CircuitBreaker(failure_threshold=2, cooldown_s=0.05)
    assert b.state == "closed" and b.allow()
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()
    assert b.state == "open" and not b.allow()
    time.sleep(0.06)
    assert b.state == "half_open" and b.allow()  # one probe allowed
    b.record_failure()  # probe failed → re-open
    assert b.state == "open"
    time.sleep(0.06)
    b.record_success()
    assert b.state == "closed"


# ----------------------------------------------------------------- routing
def test_least_busy_routing_prefers_idle_replica():
    busy, idle = FakeEngine("r0"), FakeEngine("r1")
    busy._slot_req[0] = busy._slot_req[1] = object()  # 2/4 slots busy
    gw = make_gateway([busy, idle])
    # distinct conversations so session affinity doesn't pin
    for i in range(4):
        gw.chat({"messages": [{"role": "user", "content": f"q{i}"}]})
    assert idle.calls == 4 and busy.calls == 0


def test_round_robin_rotates_over_replicas():
    engines = [FakeEngine(f"r{i}") for i in range(3)]
    gw = make_gateway(engines, policy="round_robin")
    for i in range(6):
        gw.chat({"messages": [{"role": "user", "content": f"q{i}"}]})
    assert [e.calls for e in engines] == [2, 2, 2]


def test_session_affinity_pins_conversation():
    engines = [FakeEngine("r0"), FakeEngine("r1")]
    gw = make_gateway(engines, policy="round_robin")
    convo = [{"role": "system", "content": "you are helpful"},
             {"role": "user", "content": "turn 1"}]
    gw.chat({"messages": convo})
    first = [e.calls for e in engines].index(1)
    # later turns share messages[0] → same replica despite round-robin
    for turn in range(2, 6):
        gw.chat({"messages": convo + [
            {"role": "user", "content": f"turn {turn}"}]})
    assert engines[first].calls == 5
    assert engines[1 - first].calls == 0
    assert session_key(convo) == session_key(
        convo + [{"role": "user", "content": "later"}])


def test_adapter_awareness_routes_to_loaded_replica():
    plain = FakeEngine("r0")
    tuned = FakeEngine("r1", adapters=("billing-bot",))
    gw = make_gateway([plain, tuned])
    for i in range(3):
        gw.chat({"messages": [{"role": "user", "content": f"q{i}"}],
                 "model": "billing-bot"})
    assert tuned.calls == 3 and plain.calls == 0


def test_draining_replica_gets_no_new_requests():
    engines = [FakeEngine("r0"), FakeEngine("r1")]
    gw = make_gateway(engines, policy="round_robin")
    assert gw.pool.drain("r0")
    for i in range(4):
        gw.chat({"messages": [{"role": "user", "content": f"q{i}"}]})
    assert engines[0].calls == 0 and engines[1].calls == 4


# ---------------------------------------------------------------- failover
def test_nonstream_failover_on_dead_replica():
    dead, alive = FakeEngine("r0"), FakeEngine("r1", reply="from r1")
    dead.dead = True
    dead._slot_req = [None] * 4  # looks idle → least-busy picks it first
    alive._slot_req[0] = object()
    gw = make_gateway([dead, alive])
    assert gw.chat({"messages": MSGS}) == "from r1"
    assert gw.pool.get("r0").breaker._failures >= 1


def test_midstream_failover_resumes_without_duplicating_prefix():
    dying = FakeEngine("r0", reply="hello world", die_after_deltas=2)
    backup = FakeEngine("r1", reply="hello world")
    backup._slot_req[0] = object()  # bias first pick to r0
    gw = make_gateway([dying, backup])
    deltas = list(gw.chat_stream({"messages": MSGS}))
    # r0 emitted "he","ll" then died; r1 re-served and the gateway skipped
    # the 4 already-emitted chars — the client sees the text exactly once
    assert "".join(deltas) == "hello world"
    assert dying.calls == 1 and backup.calls == 1
    assert gw.registry.counter("dtx_gateway_failovers_total").get() == 1


def test_all_replicas_dead_raises():
    e0, e1 = FakeEngine("r0"), FakeEngine("r1")
    e0.dead = e1.dead = True
    gw = make_gateway([e0, e1])
    with pytest.raises(NoReplicaAvailable):
        gw.chat({"messages": MSGS})


def test_breaker_opens_after_repeated_failures_and_recovers():
    flaky, steady = FakeEngine("r0"), FakeEngine("r1")
    flaky.dead = True
    gw = make_gateway([flaky, steady])
    gw.pool.get("r0").breaker.cooldown_s = 60  # no half-open during test
    for i in range(5):
        gw.chat({"messages": [{"role": "user", "content": f"q{i}"}]})
    assert gw.pool.get("r0").breaker.state == "open"
    # circuit open → r0 is no longer even attempted
    flaky.calls = 0
    gw.chat({"messages": [{"role": "user", "content": "after open"}]})
    assert flaky.calls == 0


# --------------------------------------------------------------- admission
def test_admission_sheds_past_token_budget():
    adm = AdmissionController(max_queue=100, token_budget=40)
    msgs = [{"role": "user", "content": "x" * 60}]  # ~19 tokens
    t1 = adm.try_admit(msgs)
    t2 = adm.try_admit(msgs)
    with pytest.raises(Overloaded) as ei:
        adm.try_admit(msgs)
    assert ei.value.retry_after_s >= 1
    assert adm.shed_count == 1
    t1.release()
    t2.release()
    adm.try_admit(msgs).release()  # budget freed → admits again


def test_admission_bounds_queue_depth():
    adm = AdmissionController(max_queue=2, token_budget=10_000)
    tickets = [adm.try_admit(MSGS) for _ in range(2)]
    with pytest.raises(Overloaded):
        adm.try_admit(MSGS)
    for t in tickets:
        t.release()


def test_estimate_tokens_scales_with_content():
    small = estimate_prompt_tokens([{"role": "user", "content": "hi"}])
    big = estimate_prompt_tokens([{"role": "user", "content": "x" * 4000}])
    assert big > small * 10


def test_estimate_tokens_chars_per_token_configurable():
    msgs = [{"role": "user", "content": "x" * 400}]
    default = estimate_prompt_tokens(msgs)  # 400/4 + 4
    dense = estimate_prompt_tokens(msgs, chars_per_token=2.0)  # 400/2 + 4
    assert default == 104
    assert dense == 204


def test_estimate_tokens_prefers_real_tokenizer():
    msgs = [{"role": "user", "content": "hello world"}]
    exact = estimate_prompt_tokens(msgs, count_tokens=lambda t: 7)
    assert exact == 7 + 4
    # a tokenizer that blows up must not shed the request: heuristic fallback
    def broken(text):
        raise RuntimeError("tokenizer died")

    fallback = estimate_prompt_tokens(msgs, count_tokens=broken)
    assert fallback == estimate_prompt_tokens(msgs)


def test_admission_controller_uses_configured_estimator():
    counted = []

    def count(text):
        counted.append(text)
        return 30

    adm = AdmissionController(max_queue=10, token_budget=40,
                              count_tokens=count)
    t1 = adm.try_admit([{"role": "user", "content": "abc"}])
    assert t1.tokens == 34  # 30 counted + template overhead
    with pytest.raises(Overloaded):  # 34 + 34 > 40
        adm.try_admit([{"role": "user", "content": "def"}])
    assert counted == ["abc", "def"]
    t1.release()


# --------------------------------------------------------------- autoscale
def test_autoscale_hint_scales_up_on_backlog_and_down_when_idle():
    up = autoscale_hint(replicas=2, available_replicas=2, queue_depth=20,
                        queued_tokens=5000, shed_count=0, p95_latency_s=1.0)
    assert up["desiredReplicas"] == 3 and "queue depth" in up["reason"]
    shed = autoscale_hint(replicas=1, available_replicas=1, queue_depth=3,
                          queued_tokens=900, shed_count=7, p95_latency_s=0.5)
    assert shed["desiredReplicas"] == 2
    down = autoscale_hint(replicas=3, available_replicas=3, queue_depth=0,
                          queued_tokens=0, shed_count=0, p95_latency_s=0.1)
    assert down["desiredReplicas"] == 2 and down["reason"] == "idle"
    assert parse_hint(down) == down | {"reason": "idle"}
    assert parse_hint({"replicas": "x"}) is None
    # a long-past overload blip (cumulative sheds, none recent) must NOT
    # ratchet the fleet up forever
    stale = autoscale_hint(replicas=2, available_replicas=2, queue_depth=2,
                           queued_tokens=100, shed_count=50, shed_recent=0,
                           p95_latency_s=0.5)
    assert stale["desiredReplicas"] == 2


def test_gateway_autoscale_uses_shed_delta_not_lifetime_total():
    slow = FakeEngine("r0", delay=0.2)
    gw = make_gateway(
        [slow], admission=AdmissionController(max_queue=1, token_budget=10**6))
    t = threading.Thread(
        target=lambda: gw.chat({"messages": MSGS}))
    t.start()
    while gw.admission.depth == 0:
        time.sleep(0.005)
    with pytest.raises(Overloaded):
        gw.admission.try_admit(MSGS)
    hint1 = gw.autoscale()  # shed happened since last poll → scale up
    assert hint1["shedCount"] == 1 and hint1["desiredReplicas"] == 2
    t.join()
    # no new sheds since hint1: the lifetime total alone must not demand more
    t2 = threading.Thread(target=lambda: gw.chat({"messages": MSGS}))
    t2.start()
    while gw.admission.depth == 0:
        time.sleep(0.005)
    hint2 = gw.autoscale()
    t2.join()
    assert hint2["shedCount"] == 1  # cumulative still reported
    assert "shedding" not in hint2["reason"]


def test_capacity_clamps_hint_to_bounds_and_free_slices():
    from datatunerx_tpu.operator.capacity import serving_replicas_for

    hint = {"replicas": 2, "desiredReplicas": 3}
    assert serving_replicas_for(hint, max_replicas=8) == 3
    assert serving_replicas_for(hint, max_replicas=2) == 2
    assert serving_replicas_for(hint, max_replicas=8, free_slices=0) == 2
    assert serving_replicas_for({"replicas": 4, "desiredReplicas": 3},
                                min_replicas=4) == 4


# ---------------------------------------------------------- operator wiring
def test_serving_spec_carries_gateway_fields():
    from datatunerx_tpu.operator.api import FinetuneJob, ObjectMeta
    from datatunerx_tpu.operator.generate import generate_serving_spec
    from datatunerx_tpu.operator.webhooks import admit

    job = FinetuneJob(
        metadata=ObjectMeta(name="j1", namespace="default"),
        spec={"finetune": {"finetuneSpec": {
            "llm": "m", "dataset": "d",
            "hyperparameter": {"hyperparameterRef": "h"}}},
            "serveConfig": {"replicas": 3}},
    )
    admit(job)  # defaulting: replicas>1 implies gateway + policy + bounds
    cfg = job.spec["serveConfig"]
    assert cfg["gateway"] is True and cfg["maxReplicas"] == 3
    spec = generate_serving_spec(job, {})
    assert spec["replicas"] == 3 and spec["gateway"] is True
    assert spec["policy"] == "least_busy" and spec["max_replicas"] == 3


def test_webhook_rejects_bad_serve_config():
    from datatunerx_tpu.operator.api import FinetuneJob, ObjectMeta
    from datatunerx_tpu.operator.webhooks import AdmissionError, admit

    def job(serve):
        return FinetuneJob(
            metadata=ObjectMeta(name="j", namespace="default"),
            spec={"finetune": {"finetuneSpec": {
                "llm": "m", "dataset": "d",
                "hyperparameter": {"hyperparameterRef": "h"}}},
                "serveConfig": serve},
        )

    with pytest.raises(AdmissionError):
        admit(job({"replicas": 0}))
    with pytest.raises(AdmissionError):
        admit(job({"minReplicas": 3, "maxReplicas": 1}))
    with pytest.raises(AdmissionError):
        admit(job({"policy": "fastest"}))


def test_crd_schema_includes_gateway_fields():
    from datatunerx_tpu.operator.api import FinetuneJob
    from datatunerx_tpu.operator.crdgen import crd_for

    crd = crd_for(FinetuneJob)
    serve = (crd["spec"]["versions"][0]["schema"]["openAPIV3Schema"]
             ["properties"]["spec"]["properties"]["serveConfig"]["properties"])
    for field in ("replicas", "gateway", "policy", "minReplicas",
                  "maxReplicas"):
        assert field in serve, field


def test_controller_applies_clamped_scale():
    from datatunerx_tpu.operator.api import FinetuneJob, ObjectMeta
    from datatunerx_tpu.operator.finetunejob_controller import (
        FinetuneJobController,
    )

    class FakeBackend:
        def __init__(self):
            self.scaled = []
            self.hint = autoscale_hint(
                replicas=2, available_replicas=2, queue_depth=30,
                queued_tokens=9000, shed_count=4, p95_latency_s=2.0)

        def scale_hint(self, name):
            return self.hint

        def scale(self, name, n):
            self.scaled.append((name, n))

    backend = FakeBackend()
    ctrl = FinetuneJobController(backend)
    job = FinetuneJob(
        metadata=ObjectMeta(name="j1", namespace="default"),
        spec={"serveConfig": {"replicas": 2, "gateway": True,
                              "minReplicas": 1, "maxReplicas": 5}},
    )
    changed = ctrl._reconcile_autoscale(job)
    assert changed
    assert backend.scaled == [("j1", 3)]
    assert job.status["result"]["serving"]["desiredReplicas"] == 3

    # maxReplicas caps the hint → no scale call when already at the cap
    backend.scaled.clear()
    job.spec["serveConfig"]["maxReplicas"] = 2
    ctrl._reconcile_autoscale(job)
    assert backend.scaled == []


# ------------------------------------------------------------ http surface
@pytest.fixture()
def http_gateway():
    made = []

    def start(engines, **kw):
        gw = make_gateway(engines, **kw)
        srv = serve(gw, port=0, host="127.0.0.1")
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        made.append((gw, srv))
        return gw, f"http://127.0.0.1:{srv.server_port}"

    yield start
    for gw, srv in made:
        srv.shutdown()
        gw.close()


def _post(url, path, payload, headers=None):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    return urllib.request.urlopen(req, timeout=30)


def test_http_chat_round_trip_with_trace_id(http_gateway):
    gw, url = http_gateway([FakeEngine("r0", reply="pong")])
    with _post(url, "/v1/chat/completions",
               {"messages": MSGS},
               {"X-DTX-Trace-Id": "trace-abc123"}) as r:
        body = json.load(r)
        assert r.headers["X-DTX-Trace-Id"] == "trace-abc123"
    assert body["choices"][0]["message"]["content"] == "pong"
    # absent header → gateway generates one
    with _post(url, "/chat/completions", {"messages": MSGS}) as r:
        assert r.headers["X-DTX-Trace-Id"].startswith("dtx-")


def test_http_midstream_failover_completes_stream(http_gateway):
    dying = FakeEngine("r0", reply="hello world", die_after_deltas=2)
    backup = FakeEngine("r1", reply="hello world")
    backup._slot_req[0] = object()
    gw, url = http_gateway([dying, backup])
    with _post(url, "/chat/completions",
               {"messages": MSGS, "stream": True}) as r:
        events = [line.decode().strip()[len("data: "):]
                  for line in r if line.strip().startswith(b"data: ")]
    assert events[-1] == "[DONE]"
    text = "".join(
        json.loads(e)["choices"][0]["delta"].get("content", "")
        for e in events[:-1] if not e.startswith("[")
    )
    assert text == "hello world"
    assert dying.dead and backup.calls == 1


def test_http_overload_returns_429_while_inflight_completes(http_gateway):
    slow = FakeEngine("r0", reply="slow answer", delay=0.5)
    gw, url = http_gateway(
        [slow], admission=AdmissionController(max_queue=1, token_budget=10**6))

    results = {}

    def inflight():
        with _post(url, "/chat/completions", {"messages": MSGS}) as r:
            results["inflight"] = json.load(r)

    t = threading.Thread(target=inflight)
    t.start()
    # wait until the in-flight request holds the queue slot before poking
    deadline = time.monotonic() + 5
    while gw.admission.depth == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert gw.admission.depth == 1
    shed_status = None
    while time.monotonic() < deadline:
        # sustained overload: keep poking until admission sheds
        try:
            with _post(url, "/chat/completions",
                       {"messages": [{"role": "user", "content": "x"}]}):
                pass
        except urllib.error.HTTPError as e:
            shed_status = (e.code, e.headers.get("Retry-After"))
            break
        time.sleep(0.01)
    t.join(timeout=10)
    assert shed_status is not None, "overload never shed"
    code, retry_after = shed_status
    assert code == 429
    assert retry_after is not None and int(retry_after) >= 1
    # the in-flight request completed despite the shed
    assert results["inflight"]["choices"][0]["message"]["content"] == \
        "slow answer"
    assert gw.admission.shed_count >= 1


def test_http_metrics_report_queue_shed_and_circuit(http_gateway):
    flaky = FakeEngine("r0")
    flaky.dead = True
    steady = FakeEngine("r1")
    gw, url = http_gateway([flaky, steady])
    gw.pool.get("r0").breaker.cooldown_s = 60
    for i in range(4):
        _post(url, "/chat/completions",
              {"messages": [{"role": "user", "content": f"q{i}"}]}).read()
    gw.admission._shed = 2  # exercise the shed counter surface
    with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "# TYPE dtx_gateway_queue_depth gauge" in text
    assert "dtx_gateway_queue_depth 0" in text
    assert "dtx_gateway_shed_total 2" in text
    assert ('dtx_gateway_replica_circuit_state{replica="r0",state="open"} 1'
            in text)
    assert ('dtx_gateway_replica_circuit_state{replica="r1",state="closed"} 1'
            in text)
    assert "dtx_gateway_request_latency_seconds_bucket" in text


def test_http_healthz_autoscale_drain_and_404(http_gateway):
    gw, url = http_gateway([FakeEngine("r0"), FakeEngine("r1")])
    with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
        h = json.load(r)
    assert h["status"] == "HEALTHY" and h["available"] == 2
    with urllib.request.urlopen(url + "/autoscale", timeout=10) as r:
        hint = parse_hint(json.load(r))
    assert hint is not None and hint["replicas"] == 2
    with _post(url, "/admin/drain", {"replica": "r0"}) as r:
        assert json.load(r)["draining"] == "r0"
    with urllib.request.urlopen(url + "/autoscale", timeout=10) as r:
        assert json.load(r)["availableReplicas"] == 1
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url, "/admin/drain", {"replica": "ghost"})
    assert ei.value.code == 404
    # scale without a managed replica set → 501
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url, "/admin/scale", {"replicas": 3})
    assert ei.value.code == 501


def test_http_bad_requests(http_gateway):
    gw, url = http_gateway([FakeEngine("r0")])
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url, "/chat/completions", {"messages": []})
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(url, "/nope", {})
    assert ei.value.code == 404


def test_perplexity_client_error_does_not_trip_breaker():
    """A 400 from the replica is the CLIENT's fault: the gateway must map it
    to 400 (ValueError), not 502, and must not open the replica's circuit."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from datatunerx_tpu.gateway.replica_pool import HTTPReplica

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            body = json.dumps({"error": "completion is required"}).encode()
            self.send_response(400)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        replica = HTTPReplica("r0", f"http://127.0.0.1:{srv.server_port}")
        gw = Gateway(ReplicaPool([replica]))
        for _ in range(5):
            with pytest.raises(ValueError, match="completion is required"):
                gw.perplexity({"prompt": "p"})
        assert replica.breaker.state == "closed"
    finally:
        srv.shutdown()


# ------------------------------------------------------- subprocess replicas
@pytest.mark.slow
def test_local_backend_deploys_gateway_with_real_replicas(tmp_path):
    """LocalServingBackend spec.replicas=2 → gateway process fronting two
    serving.server subprocesses with real debug models: HEALTHY gate, chat
    round trip, autoscale hint, and graceful downscale via /admin/scale."""
    from datatunerx_tpu.serving.local_backend import LocalServingBackend

    backend = LocalServingBackend(
        str(tmp_path / "jobs"),
        extra_env={"JAX_PLATFORMS": "cpu"})
    backend.deploy("gwjob", {
        "model_path": "preset:debug", "template": "vanilla",
        "replicas": 2, "slots": 2,
    })
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if backend.status("gwjob") == "HEALTHY":
                break
            time.sleep(1)
        assert backend.status("gwjob") == "HEALTHY"
        url = backend.endpoint("gwjob")
        with _post(url, "/chat/completions", {
                "messages": [{"role": "user", "content": "ping"},],
                "max_tokens": 4}) as r:
            body = json.load(r)
        assert body["choices"][0]["message"]["content"] is not None
        hint = backend.scale_hint("gwjob")
        assert hint is not None and hint["replicas"] == 2
        assert backend.scale("gwjob", 1)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            hint = backend.scale_hint("gwjob")
            if hint and hint["replicas"] == 1:
                break
            time.sleep(0.5)
        assert hint and hint["replicas"] == 1
    finally:
        backend.delete("gwjob")


# --------------------------------------------------- drain reaping (PR 4)
class FakeProc:
    """subprocess.Popen stand-in: alive until terminate()/kill()."""

    def __init__(self):
        self.returncode = None
        self.terminated = False

    def poll(self):
        return self.returncode

    def terminate(self):
        self.terminated = True
        self.returncode = -15

    def kill(self):
        self.returncode = -9

    def wait(self, timeout=None):
        if self.returncode is None:
            raise subprocess.TimeoutExpired("fake", timeout or 0)
        return self.returncode


class WarmableFakeEngine(FakeEngine):
    """FakeEngine + the dynamic-adapter surface inheritance reads/writes:
    a resident warm set with checkpoints, and a load_adapter recorder."""

    def __init__(self, name, warm_set=None, **kw):
        super().__init__(name, adapters=tuple(warm_set or ()), **kw)
        self._warm = dict(warm_set or {})
        self.resident_adapters = dict.fromkeys(self._warm, 1)
        self.loaded: list = []

    def adapter_catalog(self):
        return dict(self._warm)

    def load_adapter(self, name, checkpoint, preload=True):
        self.loaded.append((name, checkpoint))
        self._warm[name] = checkpoint
        self.resident_adapters[name] = 1
        return {"name": name, "checkpoint": checkpoint}

    def healthy(self):
        return True


class FakeManagedReplicaSet(ManagedReplicaSet):
    """ManagedReplicaSet whose spawn() creates an in-process replica and a
    FakeProc instead of a real serving.server subprocess — the reap logic
    under test (drain → terminate → pool removal → replacement → weight +
    warm-set inheritance) is identical."""

    engine_factory = staticmethod(lambda name: FakeEngine(name))

    def spawn(self):
        with self._lock:
            idx = self._next_idx
            self._next_idx += 1
        name = f"replica-{idx}"
        with self._lock:
            self._procs[name] = FakeProc()
        replica = InProcessReplica(name, self.engine_factory(name))
        self._apply_inheritance(replica)
        self.pool.add(replica)
        return replica


def _wait_until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def test_admin_drain_reaps_process_and_restores_target(tmp_path):
    """ROADMAP bug: /admin/drain used to only set the draining flag — the
    subprocess and pool entry leaked, and reconcile grew the fleet past
    target by one zombie per drain. Now: drained replica's process is
    terminated, its pool entry removed, and the fleet returns to target."""
    pool = ReplicaPool()
    mrs = FakeManagedReplicaSet(pool, [], workdir=str(tmp_path / "w"),
                                drain_timeout_s=2.0, supervise_interval_s=0)
    gw = Gateway(pool)
    gw.replica_set = mrs
    try:
        mrs.scale(2)
        assert sorted(r.name for r in pool.replicas()) == [
            "replica-0", "replica-1"]
        proc0 = mrs._procs["replica-0"]

        assert gw.drain("replica-0")
        assert _wait_until(lambda: pool.get("replica-0") is None)
        assert proc0.terminated, "drained replica's subprocess must be reaped"
        assert "replica-0" not in mrs._procs, "no zombie pool entry"

        # replacement spawned: fleet back at target, not target+zombie
        mrs._reconcile()
        assert _wait_until(lambda: len(pool.replicas()) == 2)
        assert len(mrs._procs) == 2
        assert all(p.poll() is None for p in mrs._procs.values())

        # unknown replica still 404s through the gateway entry point
        assert not gw.drain("replica-404")
    finally:
        mrs.close()
        pool.close()


def test_drain_waits_for_inflight_before_reaping(tmp_path):
    pool = ReplicaPool()
    mrs = FakeManagedReplicaSet(pool, [], workdir=str(tmp_path / "w"),
                                drain_timeout_s=5.0, supervise_interval_s=0)
    try:
        mrs.scale(1)
        replica = pool.get("replica-0")
        replica.acquire()  # simulate an in-flight request
        assert mrs.drain("replica-0")
        time.sleep(0.3)
        assert pool.get("replica-0") is not None, \
            "reaper must wait for in-flight work"
        replica.release()
        assert _wait_until(lambda: pool.get("replica-0") is None)
        assert "replica-0" not in mrs._procs
    finally:
        mrs.close()
        pool.close()


def test_pool_level_drain_is_reaped_by_supervisor(tmp_path):
    """Safety net: a managed replica drained directly on the pool (old
    /admin/drain path) is picked up by the next reconcile tick."""
    pool = ReplicaPool()
    mrs = FakeManagedReplicaSet(pool, [], workdir=str(tmp_path / "w"),
                                drain_timeout_s=2.0, supervise_interval_s=0)
    try:
        mrs.scale(2)
        pool.drain("replica-1")  # bypasses ManagedReplicaSet.drain
        mrs._reconcile()  # what the supervisor thread runs periodically
        assert _wait_until(lambda: pool.get("replica-1") is None)
        assert "replica-1" not in mrs._procs
        assert _wait_until(lambda: len(pool.replicas()) == 2)
    finally:
        mrs.close()
        pool.close()


def test_drain_replacement_inherits_weight_and_warm_set(tmp_path):
    """Regression: the replacement spawned for a drained replica used to
    join at defaults (weight 1.0, cold adapter pool) — mid-promotion that
    skews the smooth-WRR shares, and every tenant pays load-on-miss again.
    Now it inherits the drained replica's traffic weight at spawn and
    rebuilds its resident warm set once healthy."""
    pool = ReplicaPool()
    mrs = FakeManagedReplicaSet(pool, [], workdir=str(tmp_path / "w"),
                                drain_timeout_s=2.0, supervise_interval_s=0)
    mrs.engine_factory = staticmethod(
        lambda name: WarmableFakeEngine(name))
    gw = Gateway(pool)
    gw.replica_set = mrs
    try:
        mrs.scale(2)
        drained = pool.get("replica-0")
        drained.weight = 0.25  # mid-promotion canary share
        drained.engine._warm = {"tenant-a": "/ckpts/a",
                                "tenant-b": "/ckpts/b"}
        drained.engine.resident_adapters = {"tenant-a": 1, "tenant-b": 1}

        assert gw.drain("replica-0")
        assert _wait_until(lambda: pool.get("replica-2") is not None)
        replacement = pool.get("replica-2")
        assert replacement.weight == 0.25, \
            "replacement must inherit the drained replica's traffic weight"
        assert _wait_until(
            lambda: sorted(replacement.engine.loaded) == [
                ("tenant-a", "/ckpts/a"), ("tenant-b", "/ckpts/b")]), \
            replacement.engine.loaded

        # a DOWNSCALED replica's state is NOT inherited: the next scale-up
        # spawn joins at defaults (no stale entry misapplied)
        pool.get("replica-1").weight = 0.5
        mrs.scale(1)
        assert _wait_until(lambda: len(pool.replicas()) == 1)
        mrs.scale(2)
        assert _wait_until(lambda: len(pool.replicas()) == 2)
        newest = max(pool.replicas(), key=lambda r: r.name)
        assert newest.weight == 1.0
    finally:
        mrs.close()
        pool.close()


# ------------------------------------- client errors vs replica faults (PR 4)
class ClientErrorEngine(FakeEngine):
    """Engine that rejects the REQUEST (unknown adapter / over-length
    prompt) — the engine contract raises ValueError/KeyError for these,
    never a replica-level fault."""

    def __init__(self, name, exc):
        super().__init__(name)
        self.exc = exc

    def chat(self, messages, **kw):
        self.calls += 1
        raise self.exc

    def chat_stream(self, messages, **kw):
        self.calls += 1
        raise self.exc
        yield  # pragma: no cover — make it a generator


@pytest.mark.parametrize("exc", [ValueError("prompt too long"),
                                 KeyError("unknown adapter 'x'")])
def test_inprocess_client_error_does_not_trip_breaker_or_fail_over(exc):
    bad = ClientErrorEngine("r0", exc)
    healthy = FakeEngine("r1", reply="ok")
    pool = ReplicaPool([InProcessReplica("r0", bad)])
    gw = Gateway(pool)
    with pytest.raises(ValueError):
        gw.chat({"messages": MSGS})
    assert pool.get("r0").breaker.state == "closed", \
        "a client error must not count against the replica"

    # with a healthy sibling available the request must STILL fail (the
    # request itself is bad) instead of failing over and masking the 400
    gw2 = make_gateway([ClientErrorEngine("r0", exc), healthy],
                       policy="round_robin")
    for _ in range(2):  # whichever replica round-robin picks first
        with pytest.raises(ValueError):
            gw2.chat({"messages": MSGS})
    assert healthy.calls <= 2  # served directly, never via failover retries

    with pytest.raises(ValueError):
        list(Gateway(ReplicaPool([InProcessReplica(
            "r2", ClientErrorEngine("r2", exc))])).chat_stream(
                {"messages": MSGS}))


def test_replica_types_agree_on_client_error_mapping():
    """InProcessReplica and HTTPReplica side by side: the same client
    mistake surfaces as ValueError (→ gateway 400) from both, not as
    ReplicaError (→ breaker trip + 503)."""

    class Reject400(BaseHTTPRequestHandler):
        def do_POST(self):
            body = json.dumps({"error": "unknown model/adapter 'x'"}).encode()
            self.send_response(400)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Reject400)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        replicas = [
            HTTPReplica("http0", f"http://127.0.0.1:{srv.server_port}"),
            InProcessReplica("proc0", ClientErrorEngine(
                "proc0", KeyError("unknown model/adapter 'x'"))),
        ]
        for replica in replicas:
            with pytest.raises(ValueError):
                replica.chat(MSGS, max_new_tokens=4)
            with pytest.raises(ValueError):
                list(replica.chat_stream(MSGS, max_new_tokens=4))

        # a genuine replica fault still raises ReplicaError from both
        dead_http = HTTPReplica("dead", "http://127.0.0.1:9")  # closed port
        with pytest.raises(ReplicaError):
            dead_http.chat(MSGS)
        dead_proc = InProcessReplica("deadp", FakeEngine("deadp"))
        dead_proc.engine.dead = True
        with pytest.raises(ReplicaError):
            dead_proc.chat(MSGS)
    finally:
        srv.shutdown()
