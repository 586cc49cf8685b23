"""Cluster backends: where training and serving workloads actually run.

The reference delegates to KubeRay (RayJob for training,
finetune_controller.go:518-619; RayService for serving, generate.go:160-329).
Controllers here talk to two small interfaces instead, so the same state
machines drive:

- LocalProcessBackend — host subprocesses running the trainer CLI / serving
  server (CI, e2e tests, single-host dev);
- ManifestBackend — renders GKE JobSet/Deployment manifests targeting TPU node
  pools (``google.com/tpu`` resources + topology selectors, SURVEY.md §5.8);
  submission is `kubectl apply` territory outside this sandbox;
- FakeBackend — scripted transitions for controller unit tests (envtest-style,
  SURVEY.md §4.1).

Status vocabulary mirrors RayJob's deployment states the reference polls
(finetune_controller.go:169-199): Pending | Running | Succeeded | Failed.
"""

from __future__ import annotations

import json
import os
import shlex
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Protocol

from datatunerx_tpu.serving import options as serving_options


class TrainingBackend(Protocol):
    def submit(self, name: str, spec: dict) -> None: ...

    def status(self, name: str) -> str: ...

    def delete(self, name: str) -> None: ...


class ServingBackend(Protocol):
    def deploy(self, name: str, spec: dict) -> None: ...

    def status(self, name: str) -> str: ...  # HEALTHY | PENDING | FAILED

    def endpoint(self, name: str) -> Optional[str]: ...

    def delete(self, name: str) -> None: ...


def _pkg_root() -> str:
    """Directory containing the datatunerx_tpu package (for subprocess PYTHONPATH)."""
    import datatunerx_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(datatunerx_tpu.__file__)))


# ----------------------------------------------------------------- fakes

class FakeTrainingBackend:
    """Scripted backend: tests drive transitions explicitly."""

    def __init__(self):
        self.jobs: Dict[str, dict] = {}
        self.states: Dict[str, str] = {}
        self.deleted: List[str] = []

    def submit(self, name, spec):
        self.jobs[name] = spec
        self.states.setdefault(name, "Pending")

    def status(self, name):
        return self.states.get(name, "NotFound")

    def delete(self, name):
        self.deleted.append(name)
        self.states.pop(name, None)
        self.jobs.pop(name, None)

    # test helpers
    def set_state(self, name, state):
        self.states[name] = state


class FakeServingBackend:
    def __init__(self):
        self.apps: Dict[str, dict] = {}
        self.states: Dict[str, str] = {}
        self.deleted: List[str] = []

    def deploy(self, name, spec):
        self.apps[name] = spec
        self.states.setdefault(name, "PENDING")

    def status(self, name):
        return self.states.get(name, "NotFound")

    def endpoint(self, name):
        if self.states.get(name) == "HEALTHY":
            return f"http://{name}.default.svc:8000"
        return None

    def delete(self, name):
        self.deleted.append(name)
        self.states.pop(name, None)
        self.apps.pop(name, None)

    def set_state(self, name, state):
        self.states[name] = state


# ---------------------------------------------------------- local process

class _PendingGroup:
    """Placeholder for a multi-host process group queued behind the spawn
    gate; unique per submission (identity-compared) so stale spawn threads
    can never act on a resubmission under the same job name."""

    __slots__ = ("failed",)

    def __init__(self):
        self.failed = False


class LocalProcessBackend:
    """Runs the trainer CLI as subprocess(es) per job; completion detected via
    process exit + the completion manifest (training/checkpoint.py).

    ``spec["num_hosts"] > 1`` spawns that many processes wired together with
    the same DTX_* env contract the JobSet manifests set (DTX_COORDINATOR_
    ADDRESS/NUM_PROCESSES/PROCESS_ID, parallel/distributed.py) — the local
    backend is then a faithful multi-host simulator: one process per "host",
    jax.distributed bootstrap, cross-process collectives over local gRPC."""

    # Multi-host spawn stagger (seconds between JOBS' process-group spawns,
    # process-wide): gloo's cross-process rendezvous has a hard 30 s connect
    # timeout baked into XLA, and N jobs × H hosts of simultaneous jax
    # startups on shared cores skew past it — the late processes then fail
    # collectives init even though nothing is wrong (observed: the 4-job e2e
    # on a 1-core machine, where r4's fast-poll controllers un-staggered the
    # submissions that used to spread out naturally). Real clusters (kube
    # backend) are unaffected.
    _spawn_gate = threading.Lock()
    _last_group_spawn = [0.0]

    def __init__(self, workdir: str, extra_env: Optional[dict] = None):
        self.workdir = os.path.abspath(workdir)
        os.makedirs(self.workdir, exist_ok=True)
        self.extra_env = extra_env or {}
        self._procs: Dict[str, list] = {}  # job -> [Popen per host]
        self._lock = threading.Lock()

    @staticmethod
    def _free_port() -> int:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def submit(self, name: str, spec: dict) -> None:
        with self._lock:
            if name in self._procs:
                return
            jobdir = os.path.join(self.workdir, name)
            os.makedirs(jobdir, exist_ok=True)
            argv = [sys.executable, "-m", "datatunerx_tpu.tuning.train"] + [
                str(a) for a in spec["args"]
            ]
            with open(os.path.join(jobdir, "cmd.txt"), "w") as f:
                f.write(shlex.join(argv))
            env = dict(os.environ)
            env["PYTHONPATH"] = _pkg_root() + os.pathsep + env.get("PYTHONPATH", "")
            env.update(self.extra_env)
            env.update(spec.get("env", {}))

            hosts = max(1, int(spec.get("num_hosts", 1) or 1))
            if hosts == 1:
                log = open(os.path.join(jobdir, "log.txt"), "w")
                self._procs[name] = [subprocess.Popen(
                    argv, cwd=jobdir, stdout=log, stderr=subprocess.STDOUT,
                    env=env,
                )]
                return
            # multi-host: placeholder now (status() -> Pending), spawn the
            # process group off-thread behind the stagger gate. The token is
            # unique per submission so a queued thread from a deleted job can
            # never act on a later resubmission under the same name.
            token = _PendingGroup()
            self._procs[name] = token

        def _spawn_group():
            import time as _t

            stagger = float(os.environ.get("DTX_SIM_SUBMIT_STAGGER_S", "5"))
            ready_timeout = float(
                os.environ.get("DTX_SIM_SPAWN_READY_TIMEOUT_S", "300"))
            procs = []
            try:
                with LocalProcessBackend._spawn_gate:
                    wait = stagger - (
                        _t.monotonic()
                        - LocalProcessBackend._last_group_spawn[0])
                    if wait > 0:
                        _t.sleep(wait)
                    with self._lock:
                        if self._procs.get(name) is not token:
                            return  # deleted/replaced while queued
                    coord = f"127.0.0.1:{self._free_port()}"
                    for pid in range(hosts):
                        henv = dict(env)
                        henv.update({
                            "DTX_COORDINATOR_ADDRESS": coord,
                            "DTX_NUM_PROCESSES": str(hosts),
                            "DTX_PROCESS_ID": str(pid),
                        })
                        # simulated hosts share cores: a starved process must
                        # not be declared dead (its peer would fatally abort
                        # AFTER completing all work — parallel/distributed.py)
                        henv.setdefault("DTX_DIST_HEARTBEAT_S", "600")
                        henv.setdefault("DTX_DIST_SHUTDOWN_S", "600")
                        # pod-0 writes checkpoints/manifest; rest log beside
                        log_name = "log.txt" if pid == 0 else f"log.{pid}.txt"
                        log = open(os.path.join(jobdir, log_name), "w")
                        procs.append(subprocess.Popen(
                            argv, cwd=jobdir, stdout=log,
                            stderr=subprocess.STDOUT, env=henv,
                        ))
                    with self._lock:
                        if self._procs.get(name) is token:
                            self._procs[name] = procs
                        else:  # deleted during spawn: tear the group down
                            for p in procs:
                                p.terminate()
                            return
                    # hold the gate until this group survives startup: the
                    # first "[train]" line means jax.distributed + gloo
                    # rendezvous succeeded and the step loop runs. Only then
                    # may the next group pile onto the cores — startups
                    # serialize, TRAINING still overlaps fully.
                    log0 = os.path.join(jobdir, "log.txt")
                    deadline = _t.monotonic() + ready_timeout
                    while _t.monotonic() < deadline:
                        if any(p.poll() is not None for p in procs):
                            break  # died in startup; status() reports it
                        try:
                            with open(log0, errors="replace") as f:
                                if "[train]" in f.read():
                                    break
                        except OSError:
                            pass
                        _t.sleep(1.0)
                    LocalProcessBackend._last_group_spawn[0] = _t.monotonic()
            except BaseException:  # noqa: BLE001 — stuck-Pending is worse
                for p in procs:  # no orphans: reap anything already spawned
                    try:
                        p.terminate()
                    except OSError:
                        pass
                with self._lock:
                    if self._procs.get(name) is token:
                        token.failed = True  # status() -> Failed, retryable
                raise

        # The spawn worker is token-guarded (a delete or resubmission makes
        # it a no-op), bounded by ready_timeout, and the process group it
        # creates is reaped by delete().
        threading.Thread(target=_spawn_group, daemon=True).start()  # dtxlint: disable=DTX012 — fire-and-forget by design, see above

    def status(self, name: str) -> str:
        with self._lock:
            procs = self._procs.get(name)
        if procs is None:
            return "NotFound"
        if isinstance(procs, _PendingGroup):
            # multi-host group queued behind the spawn gate (or its spawn
            # thread died — surfaced as a normal, retryable job failure)
            return "Failed" if procs.failed else "Pending"
        rcs = [p.poll() for p in procs]
        if any(rc not in (None, 0) for rc in rcs):
            return "Failed"  # JobSet failure semantics: any host failing fails the job
        if any(rc is None for rc in rcs):
            return "Running"
        return "Succeeded"

    def delete(self, name: str) -> None:
        with self._lock:
            procs = self._procs.pop(name, None)
        if isinstance(procs, _PendingGroup):
            return  # nothing spawned yet; the popped token voids its thread
        for proc in procs or []:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()

    def has_active_jobs(self) -> bool:
        """True while any trainer subprocess is live or about to be (the
        device health probe must not contend with a job for the chip, which
        belongs to one process at a time). A multi-host group still queued
        behind the spawn gate counts: its processes start at any moment."""
        with self._lock:
            groups = list(self._procs.values())
        for procs in groups:
            if isinstance(procs, _PendingGroup):
                if not procs.failed:
                    return True
            elif any(p.poll() is None for p in procs):
                return True
        return False

    def metrics_series(self, name: str, max_points: int = 2000) -> dict:
        """Parsed trainer/eval jsonl curves for the UI (the data the reference
        surfaces via Prometheus + its web frontend, SURVEY.md §3.5)."""
        out = {"train": [], "eval": []}
        for key, fname in (("train", "trainer_log.jsonl"),
                           ("eval", "eval_log.jsonl")):
            path = os.path.join(self.workdir, name, "result", "watch", fname)
            try:
                with open(path) as f:
                    rows = [json.loads(line) for line in f if line.strip()]
                out[key] = rows[-max_points:]
            except (OSError, ValueError):
                pass
        return out

    def log_tail(self, name: str, n: int = 40, max_bytes: int = 256 * 1024) -> str:
        path = os.path.join(self.workdir, name, "log.txt")
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(size - max_bytes, 0))
                data = f.read().decode(errors="replace")
            return "".join(data.splitlines(keepends=True)[-n:])
        except OSError:
            return ""


# -------------------------------------------------------------- manifests

def jobset_state(status: dict) -> str:
    """JobSet status → backend state vocabulary (the feedback loop the
    reference runs on RayJob JobDeploymentStatus,
    finetune_controller.go:169-199). A 'Completed'=True condition is terminal
    success, 'Failed'=True terminal failure; any active/ready replicated job
    counts as Running; otherwise Pending."""
    for cond in status.get("conditions") or []:
        if str(cond.get("status")) != "True":
            continue
        t = cond.get("type", "")
        if t == "Completed":
            return "Succeeded"
        if t in ("Failed", "FailurePolicyComplete"):
            return "Failed"
    for rj in status.get("replicatedJobsStatus") or []:
        if (rj.get("active", 0) or 0) > 0 or (rj.get("ready", 0) or 0) > 0:
            return "Running"
    return "Pending"


def deployment_state(status: dict) -> str:
    for cond in status.get("conditions") or []:
        if (cond.get("type") == "ReplicaFailure"
                and str(cond.get("status")) == "True"):
            return "FAILED"
        # crash-looping pods never set ReplicaFailure; the deployment's
        # progress deadline (default 600s) is the terminal signal for them
        if (cond.get("type") == "Progressing"
                and str(cond.get("status")) == "False"
                and cond.get("reason") == "ProgressDeadlineExceeded"):
            return "FAILED"
    if (status.get("availableReplicas") or 0) >= 1:
        return "HEALTHY"
    return "PENDING"


class ManifestBackend:
    """Renders k8s manifests for GKE TPU node pools instead of submitting them.

    Training → JobSet-style Job per TPU host group (replacing the reference's
    RayCluster worker group with nvidia.com/gpu,
    finetune_controller.go:576-609); Serving → Deployment + Service.
    """

    def __init__(self, out_dir: str, accelerator: str = "tpu-v5-lite-podslice",
                 topology: str = "2x4"):
        self.out_dir = out_dir
        self.accelerator = accelerator
        self.topology = topology
        os.makedirs(out_dir, exist_ok=True)
        self._submitted: Dict[str, dict] = {}

    def render_training(self, name: str, spec: dict) -> dict:
        hosts = int(spec.get("num_hosts", 1))
        image = spec.get("image", "datatunerx-tpu/trainer:latest")
        args = [str(a) for a in spec["args"]]
        # per-job placement overrides (operator/placement.py SlicePool):
        # concurrent jobs land on disjoint sub-slices/node pools
        topology = spec.get("topology") or self.topology
        node_selector = {
            "cloud.google.com/gke-tpu-accelerator": self.accelerator,
            "cloud.google.com/gke-tpu-topology": topology,
            **(spec.get("node_selector") or {}),
        }
        return {
            "apiVersion": "jobset.x-k8s.io/v1alpha2",
            "kind": "JobSet",
            "metadata": {"name": name, "labels": spec.get("labels", {})},
            "spec": {
                "replicatedJobs": [{
                    "name": "tpu-hosts",
                    "replicas": 1,
                    "template": {
                        "spec": {
                            "parallelism": hosts,
                            "completions": hosts,
                            "backoffLimit": 0,
                            "template": {
                                "metadata": {"labels": spec.get("labels", {})},
                                "spec": {
                                    "restartPolicy": "Never",
                                    "nodeSelector": node_selector,
                                    "containers": [{
                                        "name": "trainer",
                                        "image": image,
                                        "command": ["python", "-m", "datatunerx_tpu.tuning.train"],
                                        "args": args,
                                        "env": [
                                            {"name": "DTX_COORDINATOR_ADDRESS",
                                             "value": f"{name}-tpu-hosts-0-0.{name}:8476"},
                                            {"name": "DTX_NUM_PROCESSES", "value": str(hosts)},
                                            {"name": "DTX_PROCESS_ID",
                                             "valueFrom": {"fieldRef": {"fieldPath": (
                                                 "metadata.annotations['batch.kubernetes.io/job-completion-index']")}}},
                                        ] + [
                                            {"name": k, "value": str(v)}
                                            for k, v in spec.get("env", {}).items()
                                        ],
                                        "resources": {"limits": {"google.com/tpu": "4"}},
                                    }],
                                },
                            },
                        },
                    },
                }],
            },
        }

    def render_serving(self, name: str, spec: dict) -> list:
        labels = {"app": name, **spec.get("labels", {})}
        deployment = {
            "apiVersion": "apps/v1",
            "kind": "Deployment",
            "metadata": {"name": name, "labels": labels},
            "spec": {
                # horizontal serving scale (gateway tier): the Service
                # spreads requests; in-cluster gateway deployment with
                # per-pod discovery is a ROADMAP open item
                "replicas": int(spec.get("replicas") or 1),
                "selector": {"matchLabels": {"app": name}},
                "template": {
                    "metadata": {"labels": labels},
                    "spec": {
                        "nodeSelector": {
                            "cloud.google.com/gke-tpu-accelerator": self.accelerator,
                            **spec.get("node_selector", {}),
                        },
                        "tolerations": spec.get("tolerations", []),
                        "containers": [{
                            "name": "server",
                            "image": spec.get("image", "datatunerx-tpu/serving:latest"),
                            "command": ["python", "-m", "datatunerx_tpu.serving.server"],
                            # every engine option generate_serving_spec
                            # rendered from the job's serveConfig
                            "args": ["--port", "8000",
                                     *serving_options.argv(spec)],
                            "ports": [{"containerPort": 8000}],
                            "readinessProbe": {
                                "httpGet": {"path": "/healthz", "port": 8000},
                                "periodSeconds": 5,
                            },
                            "resources": {"limits": {"google.com/tpu": "4"}},
                        }],
                    },
                },
            },
        }
        service = {
            "apiVersion": "v1",
            "kind": "Service",
            "metadata": {"name": name, "labels": labels},
            "spec": {
                "selector": {"app": name},
                "ports": [{"port": 8000, "targetPort": 8000}],
            },
        }
        return [deployment, service]

    def submit(self, name, spec):
        manifest = self.render_training(name, spec)
        self._submitted[name] = manifest
        with open(os.path.join(self.out_dir, f"{name}-jobset.json"), "w") as f:
            json.dump(manifest, f, indent=1)

    def status(self, name):
        """Render-only mode has no apiserver to poll; the feedback loop is a
        status file (`<name>-status.json`) dropped next to the manifest by
        whatever applied it — either `{"state": "Running"}` directly or a raw
        JobSet status object (mapped via jobset_state). Absent file = Pending.
        For a live apiserver loop use KubeTrainingBackend (kubebackends.py).
        """
        if name not in self._submitted:
            return "NotFound"
        path = os.path.join(self.out_dir, f"{name}-status.json")
        try:
            with open(path) as f:
                status = json.load(f)
        except (OSError, ValueError):
            return "Pending"
        if isinstance(status, dict) and isinstance(status.get("state"), str):
            return status["state"]
        return jobset_state(status if isinstance(status, dict) else {})

    def delete(self, name):
        self._submitted.pop(name, None)
        for suffix in ("-jobset.json", "-status.json"):
            try:
                os.remove(os.path.join(self.out_dir, f"{name}{suffix}"))
            except OSError:
                pass
