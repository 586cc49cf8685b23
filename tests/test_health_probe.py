"""Device health probe: while the accelerator is hung, failing, or silently
replaced by the CPU, new Finetunes hold in Pending rather than being
submitted; recovery resumes submission."""

from datatunerx_tpu.operator.api import Finetune, ObjectMeta
from datatunerx_tpu.operator.backends import FakeServingBackend, FakeTrainingBackend
from datatunerx_tpu.operator.health import DeviceHealthProbe, probe_device_once
from datatunerx_tpu.operator.manager import build_manager
from datatunerx_tpu.operator.store import ObjectStore
from tests.test_operator import _seed_deps


class FakeProbe:
    def __init__(self, healthy=True):
        self.healthy = healthy
        self.last_error = None if healthy else "device probe hung (> 90s)"


def _world(probe):
    store = ObjectStore()
    training = FakeTrainingBackend()
    mgr = build_manager(store, training, FakeServingBackend(),
                        storage_path="/tmp/x", with_scoring=False,
                        health_probe=probe)
    _seed_deps(store)
    return store, training, mgr


def _finetune(name="hrun"):
    return Finetune(metadata=ObjectMeta(name=name), spec={
        "llm": "llama2-7b", "dataset": "ds-a",
        "hyperparameter": {"hyperparameterRef": "hp-a"},
        "image": {"path": "/m"},
    })


def test_unhealthy_device_holds_submission():
    probe = FakeProbe(healthy=False)
    store, training, mgr = _world(probe)
    store.create(_finetune())
    mgr.run_until_idle()
    obj = store.get(Finetune, "hrun")
    assert obj.status["state"] == Finetune.STATE_PENDING
    assert "hung" in obj.status["backendUnavailable"]
    assert "hrun" not in training.jobs  # never handed to the backend

    # recovery: probe flips healthy → submission proceeds, note cleared
    probe.healthy = True
    probe.last_error = None
    mgr.enqueue("Finetune", "default", "hrun")
    mgr.drain_scheduled()
    obj = store.get(Finetune, "hrun")
    assert "hrun" in training.jobs
    assert "backendUnavailable" not in obj.status


def test_healthy_probe_does_not_interfere():
    store, training, mgr = _world(FakeProbe(healthy=True))
    store.create(_finetune("hrun2"))
    mgr.run_until_idle()
    assert "hrun2" in training.jobs


def test_probe_device_once_real_subprocess(monkeypatch, capfd):
    """The real subprocess matmul path, unmodified: the child inherits
    JAX_PLATFORMS=cpu (tests/conftest.py), so the CPU it lands on was asked
    for by name and the probe is healthy — and says which platform it ran
    on."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert probe_device_once(timeout_s=120.0) is None
    assert "platform=cpu" in capfd.readouterr().out


def test_probe_on_cpu_nobody_asked_for_is_unhealthy(monkeypatch):
    """JAX falls back to the CPU when it cannot open the chip; a probe that
    'passes' there would let jobs queue onto a device that is not present.
    The child is pinned to the CPU in code while the environment names no
    platform — exactly what a silent fallback looks like from outside."""
    import datatunerx_tpu.operator.health as health

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        health, "PROBE_CODE",
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        + health.PROBE_CODE)
    err = probe_device_once(timeout_s=120.0)
    assert err and "ran on the CPU" in err


def test_probe_detects_failure(monkeypatch):
    import datatunerx_tpu.operator.health as health

    monkeypatch.setattr(health, "PROBE_CODE", "import sys; sys.exit(3)")
    err = probe_device_once(timeout_s=30.0)
    assert err and "exited 3" in err

    p = DeviceHealthProbe(interval_s=999)
    assert p.healthy  # optimistic start
    p.check_now()
    assert not p.healthy and "exited 3" in p.last_error


def test_probe_skips_while_jobs_active(monkeypatch):
    """The probe must not contend with a running trainer for the
    single-client device: busy backend ⇒ no probe run that cycle."""
    import time

    import datatunerx_tpu.operator.health as health

    calls = {"n": 0}

    def fake_probe(timeout_s):
        calls["n"] += 1
        return None

    monkeypatch.setattr(health, "probe_device_once", fake_probe)
    busy = {"v": True}
    p = DeviceHealthProbe(interval_s=0.02, idle_check=lambda: not busy["v"])
    p.start()
    time.sleep(0.15)
    assert calls["n"] == 0  # never probed while busy
    busy["v"] = False
    deadline = time.time() + 2
    while calls["n"] == 0 and time.time() < deadline:
        time.sleep(0.02)
    p.stop()
    assert calls["n"] >= 1  # resumed once idle


def test_local_backend_has_active_jobs(tmp_path):
    import time

    from datatunerx_tpu.operator.backends import LocalProcessBackend

    b = LocalProcessBackend(str(tmp_path),
                            extra_env={"JAX_PLATFORMS": "cpu"})
    assert not b.has_active_jobs()
    b.submit("j1", {"args": ["--help"]})  # exits after argparse prints help
    assert b.has_active_jobs()  # live while the subprocess runs
    deadline = time.time() + 180  # jax import in the child is slow under load
    while b.status("j1") == "Running" and time.time() < deadline:
        time.sleep(0.1)
    assert not b.has_active_jobs()


def test_has_active_jobs_survives_a_queued_multihost_group(tmp_path):
    """has_active_jobs is the probe thread's idle_check: a multi-host job
    still queued behind the spawn gate is a _PendingGroup placeholder, not a
    list of processes — iterating it used to raise inside the probe thread.
    A queued group counts as active (its trainers start at any moment); a
    failed spawn does not; and deleting one is a no-op, not a TypeError."""
    from datatunerx_tpu.operator.backends import (
        LocalProcessBackend,
        _PendingGroup,
    )

    b = LocalProcessBackend(str(tmp_path))
    token = _PendingGroup()
    b._procs["queued"] = token
    assert b.status("queued") == "Pending"
    assert b.has_active_jobs()
    token.failed = True
    assert b.status("queued") == "Failed"
    assert not b.has_active_jobs()
    b.delete("queued")
    assert b.status("queued") == "NotFound"
