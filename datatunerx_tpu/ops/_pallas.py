"""Shared interpret-mode gate for the Pallas kernels.

Default: interpret (emulate with standard JAX ops) everywhere except on a
real TPU backend — CPU tests exercise kernel numerics without Mosaic.

``DTX_PALLAS_INTERPRET=0`` forces REAL Mosaic lowering regardless of the
default backend: deviceless AOT certification (scripts/aot_certify.py,
tests/test_aot_certify.py) compiles against a TPU topology from a process
whose platform is the CPU, where ``default_backend()`` says "cpu" but the
compile target is the real XLA-TPU/Mosaic pipeline — without the override
the certification would silently compile the emulation path and prove
nothing. The resolved value is part of the trainer's and the engine's
start-up log line (utils/runtime.py ``describe``), and ``chip_smoke.py``
refuses to run with the variable set.
"""

from __future__ import annotations

import os

import jax


def pick_block_n(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is ≤ cap and lane-aligned (multiple of
    128), preferred; else the largest power-of-two divisor ≤ cap.

    ``min(cap, n)`` + divisibility assert is NOT enough in general: real
    model dims are not all multiples of 256 (Qwen1.5-14B intermediate size
    13696 = 128 × 107 broke the nf4 path's ``assert N % 256 == 0`` — caught
    by AOT certification)."""
    cap = min(cap, n)
    for bn in range(cap - cap % 128, 0, -128):
        if n % bn == 0:
            return bn
    bn = 1
    while bn * 2 <= cap and n % (bn * 2) == 0:
        bn *= 2
    return bn


def interpret_default() -> bool:
    env = (os.environ.get("DTX_PALLAS_INTERPRET") or "").strip()
    if env:  # empty/unset -> backend default ("VAR= cmd" must not force Mosaic)
        return env.lower() not in ("0", "false", "no")
    return jax.default_backend() != "tpu"
