"""Device health probe: don't queue training onto an accelerator that is
not there.

A device that hangs, errors, or silently is not the one expected (JAX falls
back to the CPU when libtpu cannot open the chip) makes a submitted job burn
its whole backoff budget producing nothing. The probe runs a tiny device
matmul in a SUBPROCESS (a hung probe must not poison the operator) on an
interval and reports the platform it ran on; while it fails, the Finetune
controller holds new submissions in Pending instead of handing them to the
backend (finetune_controller.py). The reference has no analogue — Ray would
simply run the job into the broken GPU.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Optional

PROBE_CODE = (
    "import jax, jax.numpy as jnp;"
    "x = jnp.ones((256, 256), jnp.float32);"
    "print(jax.devices()[0].platform, float((x @ x)[0, 0]))"
)


def probe_device_once(timeout_s: float = 90.0) -> Optional[str]:
    """Run one subprocess probe; returns None when healthy, else the failure
    description. A probe that ran on the CPU is a failure unless the
    environment it inherits asked for the CPU by name (``JAX_PLATFORMS=cpu``)
    — the same rule ``utils.runtime.require_backend`` holds trainers and
    servers to."""
    try:
        p = subprocess.run([sys.executable, "-c", PROBE_CODE],
                           timeout=timeout_s, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return f"device probe hung (> {timeout_s:.0f}s)"
    if p.returncode != 0:
        return f"device probe exited {p.returncode}: {p.stderr[-200:]}"
    platform, _, value = p.stdout.strip().rpartition("\n")[2].partition(" ")
    if value != "256.0":
        return f"device probe wrong result: {p.stdout[-100:]!r}"
    print(f"[device-health] probe ran on platform={platform}", flush=True)
    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if platform == "cpu" and not cpu_asked:
        return ("device probe ran on the CPU: no accelerator could be "
                "opened and JAX_PLATFORMS=cpu was not requested")
    return None


class DeviceHealthProbe:
    """Background prober with a sticky last-known state.

    Starts optimistic (healthy) so the first reconcile isn't blocked behind a
    cold probe; flips unhealthy as soon as a probe fails.
    """

    def __init__(self, interval_s: float = 300.0, timeout_s: float = 90.0,
                 idle_check=None):
        self.interval_s = interval_s
        self.timeout_s = timeout_s
        # idle_check() -> bool: probe ONLY while no training job is running —
        # a chip belongs to one process at a time, so a probe against a busy
        # device reads as a false failure
        self.idle_check = idle_check
        self.healthy = True
        self.last_error: Optional[str] = None
        self.last_checked: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def check_now(self) -> bool:
        err = probe_device_once(self.timeout_s)
        self.last_error = err
        self.healthy = err is None
        self.last_checked = time.time()
        return self.healthy

    def start(self):
        if self._thread is not None:
            return self

        def loop():
            while not self._stop.is_set():
                if self.idle_check is None or self.idle_check():
                    self.check_now()
                if self._stop.wait(self.interval_s):
                    return

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="device-health-probe")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
