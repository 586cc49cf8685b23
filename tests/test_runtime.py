"""Start-up policy (utils/runtime.py): a CPU backend is an error unless it
was asked for by name, the compile cache is placed from outside or at one
fixed path, and a server that cannot load its engine exits instead of
answering 500 for ever. Each case needs its own interpreter — the platform
and the cache directory latch at first use."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code: str, env_set: dict, env_unset=()) -> subprocess.CompletedProcess:
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for k in env_unset:
        env.pop(k, None)
    env.update(env_set)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd="/tmp",
                          capture_output=True, text=True, timeout=300)


_PROBE = ("from datatunerx_tpu.utils import runtime\n"
          "import jax, json\n"
          "before = jax.config.jax_compilation_cache_dir\n"
          "used = runtime.configure_compile_cache()\n"
          "print('CACHE', json.dumps({'before': before, 'used': used,\n"
          "    'after': jax.config.jax_compilation_cache_dir,\n"
          "    'fixed': runtime.REPO_CACHE_DIR}), flush=True)\n"
          "print('DEVICE', json.dumps(runtime.require_backend()))\n")


def _tagged(out: subprocess.CompletedProcess, tag: str) -> dict:
    for ln in out.stdout.splitlines():
        if ln.startswith(tag + " "):
            return json.loads(ln[len(tag) + 1:])
    raise AssertionError(f"no {tag} line:\n{out.stdout}\n{out.stderr[-1500:]}")


@pytest.fixture(scope="module")
def named(tmp_path_factory):
    """Everything placed from outside: the CPU asked for by name, the cache
    directory given in the environment."""
    outside = str(tmp_path_factory.mktemp("cache") / "placed-from-outside")
    return outside, _py(_PROBE, {"JAX_PLATFORMS": "cpu",
                                 "JAX_COMPILATION_CACHE_DIR": outside})


@pytest.fixture(scope="module")
def unnamed():
    """Nothing named: no platform, no cache directory."""
    return _py(_PROBE, {}, env_unset=(
        "JAX_PLATFORMS", "JAX_PLATFORM_NAME", "JAX_COMPILATION_CACHE_DIR"))


def test_cpu_asked_for_by_name_runs(named):
    _, out = named
    assert out.returncode == 0, out.stderr[-1500:]
    assert _tagged(out, "DEVICE")["platform"] == "cpu"


def test_cpu_without_being_asked_for_is_an_error(unnamed):
    """No JAX_PLATFORMS and no chip: JAX falls back to the CPU with a log
    line. The guard turns that into a failure."""
    if unnamed.returncode == 0:
        assert _tagged(unnamed, "DEVICE")["platform"] != "cpu"
        pytest.skip("this machine has an accelerator")
    assert "JAX_PLATFORMS=cpu was not requested" in unnamed.stderr


def test_compile_cache_env_set_is_left_alone(named):
    outside, out = named
    doc = _tagged(out, "CACHE")
    assert doc["before"] == doc["after"] == doc["used"] == outside


def test_compile_cache_defaults_to_the_fixed_in_checkout_path(unnamed):
    doc = _tagged(unnamed, "CACHE")
    assert doc["before"] is None
    assert doc["used"] == doc["after"] == doc["fixed"]
    # a fixed path: the directory is part of the cache key, so nothing that
    # varies between runs (pid, time, host fingerprint, tmp name) may be in it
    assert doc["fixed"] == os.path.join(REPO, ".jax_compilation_cache")


_SCOPE_PROBE = (
    "import os, re, json, jax, jax.numpy as jnp\n"
    "from datatunerx_tpu.utils import runtime\n"
    "runtime.configure_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
    "def f(x):\n"
    "    with jax.named_scope(os.environ['PROBE_SCOPE']):\n"
    "        return jnp.tanh(x @ x)\n"
    "step = jax.jit(f)\n"
    "step(jnp.ones((32, 32))).block_until_ready()\n"
    "text = step.lower(jnp.ones((32, 32))).compile().as_text()\n"
    "print('SCOPES', json.dumps({'names': sorted(set(re.findall(\n"
    "    r'dtx\\.[a-z]+', text))), **runtime.compile_cache_stats()}))\n")


def test_a_cached_program_never_loads_with_names_it_was_not_traced_with(
        tmp_path):
    """A named scope is op metadata, which JAX's cache key leaves out by
    default: the same program under a renamed scope would load from the cache
    with the OLD name, and the profile's readers would find nothing
    (PR 24 met this on the chip: the training step came back unscoped)."""
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    docs = []
    for scope in ("dtx.before", "dtx.before", "dtx.after"):
        out = _py(_SCOPE_PROBE, dict(env, PROBE_SCOPE=scope))
        assert out.returncode == 0, out.stderr[-1500:]
        docs.append(_tagged(out, "SCOPES"))
    assert [d["names"] for d in docs] == [
        ["dtx.before"], ["dtx.before"], ["dtx.after"]]
    # the second run loaded everything it asked for; the renamed one did not
    assert docs[1]["requests"] > 0 and docs[1]["hits"] == docs[1]["requests"]
    assert docs[2]["hits"] < docs[2]["requests"]


def test_local_serving_backend_reports_failed_when_the_engine_cannot_load(
        tmp_path):
    """A replica whose engine fails to load (here: no such model; on a
    one-chip host: the chip is held by another replica) must EXIT with the
    real error in its log. It used to keep answering 500, which status()
    read as PENDING — a dead replica that looked like a loading one."""
    from datatunerx_tpu.serving.local_backend import LocalServingBackend

    backend = LocalServingBackend(str(tmp_path / "jobs"),
                                  extra_env={"JAX_PLATFORMS": "cpu"})
    backend.deploy("broken", {"model_path": str(tmp_path / "no-such-model"),
                              "template": "vanilla"})
    try:
        assert backend.status("broken") in ("PENDING", "FAILED")
        # the replica's exit is the event to wait on (the timeout only
        # guards a hang: a child that imports JAX beside six busy workers
        # has missed a 180 s poll)
        assert backend._procs["broken"].wait(timeout=900) == 1  # non-zero
        assert backend.status("broken") == "FAILED"
    finally:
        backend.delete("broken")


def test_launchers_do_not_take_the_chip():
    """One process per chip: a parent that has initialised a JAX backend
    holds the chip and the trainer or server it spawns cannot open it. The
    operator manager imports jax (through training/checkpoint.py), so what
    must hold is that neither importing the launchers nor constructing
    their backends initialises a backend."""
    code = (
        "import datatunerx_tpu.operator.manager\n"
        "import datatunerx_tpu.gateway.server, datatunerx_tpu.cli\n"
        "import datatunerx_tpu.experiment.runner, datatunerx_tpu.loadgen.replay\n"
        "from datatunerx_tpu.operator.backends import LocalProcessBackend\n"
        "from datatunerx_tpu.serving.local_backend import LocalServingBackend\n"
        "import sys, tempfile\n"
        "d = tempfile.mkdtemp()\n"
        "LocalProcessBackend(d); LocalServingBackend(d)\n"
        "if 'jax' in sys.modules:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "print('CLEAN')\n")
    out = _py(code, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-1500:]
    assert "CLEAN" in out.stdout
