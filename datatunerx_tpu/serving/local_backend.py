"""Local serving backend: subprocess server per job + /healthz polling.

The ServingBackend implementation used by the local pipeline (CI/e2e/dev);
status() maps the server's health gate onto the vocabulary the FinetuneJob
controller polls (HEALTHY gate parity with reference
finetunejob_controller.go:423-424).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import urllib.error
import urllib.request
from typing import Dict, Optional

from datatunerx_tpu.serving import options


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class LocalServingBackend:
    def __init__(self, workdir: str, template: str = "vanilla",
                 extra_env: dict | None = None):
        self.workdir = os.path.abspath(workdir)
        os.makedirs(self.workdir, exist_ok=True)
        self.template = template
        self.extra_env = extra_env or {}
        self._procs: Dict[str, subprocess.Popen] = {}
        self._ports: Dict[str, int] = {}
        self._lock = threading.Lock()

    def deploy(self, name: str, spec: dict) -> None:
        with self._lock:
            if name in self._procs:
                return
            port = _free_port()
            appdir = os.path.join(self.workdir, f"serve-{name}")
            os.makedirs(appdir, exist_ok=True)
            log = open(os.path.join(appdir, "log.txt"), "w")
            replicas = int(spec.get("replicas") or 1)
            if replicas > 1 or spec.get("gateway"):
                # multi-replica serving: the gateway fronts N replica
                # subprocesses (routing/admission/failover, gateway/server.py)
                # behind the SAME /healthz + /chat/completions contract, so
                # status() and the scoring POST work unchanged
                argv = [
                    sys.executable, "-m", "datatunerx_tpu.gateway.server",
                    "--replicas", str(replicas),
                    "--policy", spec.get("policy") or "least_busy",
                    "--workdir", appdir,
                ]
                # disaggregation knobs are gateway-only: role here is a
                # comma cycle assigned across spawned replicas, and the
                # prefill threshold / fleet plane live in the router
                for key in ("role", "prefill_threshold", "fleet_prefix_mb",
                            "fleet_handoff", "fleet_spill"):
                    val = spec.get(key)
                    if val:
                        if isinstance(val, bool):
                            val = int(val)  # the gateway flags are ints
                        argv += [f"--{key}", str(val)]
            else:
                argv = [sys.executable, "-m", "datatunerx_tpu.serving.server"]
                if spec.get("role"):
                    # single server: one role (the webhook rejects cycles
                    # when there is no gateway to distribute them)
                    argv += ["--role", str(spec["role"])]
            # every engine option of the spec (serving/options.py); both
            # servers accept them, the gateway hands them to its replicas
            argv += ["--port", str(port), *options.argv(
                {"template": self.template, **spec})]
            from datatunerx_tpu.operator.backends import _pkg_root

            env = dict(os.environ)
            env["PYTHONPATH"] = _pkg_root() + os.pathsep + env.get("PYTHONPATH", "")
            env.update(self.extra_env)
            self._procs[name] = subprocess.Popen(
                argv, cwd=appdir, stdout=log, stderr=subprocess.STDOUT, env=env
            )
            self._ports[name] = port

    def status(self, name: str) -> str:
        with self._lock:
            proc = self._procs.get(name)
            port = self._ports.get(name)
        if proc is None:
            return "NotFound"
        if proc.poll() is not None:
            return "FAILED"
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            ) as resp:
                return json.load(resp).get("status", "PENDING")
        except urllib.error.HTTPError as e:
            # 503 LOADING and 500 FAILED state themselves in the body. A
            # server whose engine failed to load also exits (caught by
            # proc.poll() above); this covers the moment in between, so a
            # replica that could not open its chip never reads as loading
            try:
                failed = json.load(e).get("status") == "FAILED"
            except ValueError:
                failed = False
            return "FAILED" if failed else "PENDING"
        except (OSError, ValueError):  # not listening yet / half-written body
            return "PENDING"

    def endpoint(self, name: str) -> Optional[str]:
        port = self._ports.get(name)
        return f"http://127.0.0.1:{port}" if port else None

    # ----------------------------------------------- gateway autoscaling
    def scale_hint(self, name: str) -> Optional[dict]:
        """The gateway's /autoscale summary, or None for single-server
        deployments / unreachable gateways (controller skips scaling)."""
        from datatunerx_tpu.gateway.autoscale import parse_hint

        url = self.endpoint(name)
        if not url:
            return None
        try:
            with urllib.request.urlopen(f"{url}/autoscale", timeout=2) as r:
                return parse_hint(json.load(r))
        except Exception:  # noqa: BLE001 — no hint is a safe no-op
            return None

    def scale(self, name: str, replicas: int) -> bool:
        url = self.endpoint(name)
        if not url:
            return False
        req = urllib.request.Request(
            f"{url}/admin/scale",
            data=json.dumps({"replicas": int(replicas)}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status == 200
        except Exception:  # noqa: BLE001
            return False

    def delete(self, name: str) -> None:
        with self._lock:
            proc = self._procs.pop(name, None)
            self._ports.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
