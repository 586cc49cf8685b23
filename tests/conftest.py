"""Test bootstrap: force an 8-device virtual CPU mesh before tests touch JAX.

SURVEY.md §4.3: multi-host sharding is tested without hardware via a virtual
multi-device CPU platform — the same pjit/GSPMD programs that run on a TPU slice
run unchanged over 8 local CPU devices.
"""

import os

os.environ.setdefault("TF_ENABLE_ONEDNN_OPTS", "0")

# The CPU, asked for by name, for this process (config below) AND for every
# trainer / server / probe the tests spawn: children inherit the environment,
# and utils/runtime.require_backend refuses a CPU nobody asked for.
os.environ["JAX_PLATFORMS"] = "cpu"

# XLA compilation cache, one fresh directory per test session: the suite's
# dominant cost is recompiling the same debug-model programs — in-process
# jits AND every spawned tuning.train / serving.server subprocess — and a
# shared cache (keyed by HLO+config, so correctness-neutral) lets each program
# compile once per session. The env vars inherit, so children share it.
#
# Per SESSION, never a directory left by an earlier one: a persistent
# directory can hold XLA:CPU AOT blobs compiled on another machine (this VM
# migrates between hosts with different CPU features), and deserializing one
# segfaults inside jax's compilation_cache (ROADMAP D8(b)). The pytest
# process therefore never reads a cache this session did not write. A
# JAX_COMPILATION_CACHE_DIR set from outside is left to the children; this
# process still uses its own.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_session_cache = tempfile.mkdtemp(prefix="dtx-pytest-jax-cache-")
atexit.register(shutil.rmtree, _session_cache, ignore_errors=True)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _session_cache)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

# Fast-poll the controller state machines (VERDICT r3 #7): the suite spent
# most of its 17 min in 3-30s requeue sleeps. The reference-parity defaults
# are unchanged in production; these envs only shrink the WAITS — every
# transition and assertion is identical. Must be set before any
# datatunerx_tpu.operator import reads them at module load.
for _k, _v in (
    ("DTX_POLL_INTERVAL_S", "0.1"),
    ("DTX_RUNNING_POLL_S", "0.2"),
    ("DTX_EXPERIMENT_POLL_S", "0.1"),
    ("DTX_SERVE_POLL_S", "0.1"),
    ("DTX_SCORING_RETRY_S", "0.2"),
    ("DTX_RECALIBRATE_REQUEUE_S", "0.2"),
    ("DTX_ERROR_REQUEUE_S", "0.3"),
    ("DTX_IDLE_HORIZON_S", "0.05"),
):
    os.environ.setdefault(_k, _v)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# (jax read the env at import; pin THIS process to the session directory
# even when a directory was named from outside)
jax.config.update("jax_compilation_cache_dir", _session_cache)

import pytest  # noqa: E402

# dtxsan (runtime sanitizer plane): opt-in via DTX_SAN=1 (or a class list,
# e.g. DTX_SAN=lock,compile). The plugin installs the lock-order / thread-leak
# / compile-budget instrumentation at configure time and reports via the
# dtxlint-style baseline contract at session finish. Must be declared here
# (top-level conftest) so pytest_configure runs before any test imports spawn
# threads or take locks.
if os.environ.get("DTX_SAN", "").strip().lower() not in ("", "0", "off"):
    pytest_plugins = ("datatunerx_tpu.analysis.sanitizers.plugin",)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
