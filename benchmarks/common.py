"""Small things every kind of run needs: the run's context, the compile
counter, the profiler window, the device's peak memory."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import threading
import time

_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def open_device() -> dict:
    """The program's own start-up policy, for every entry of the benchmark: the
    compile cache at its fixed path inside the checkout (every program kept,
    however quick its compile: sub-second ones recompile on a warm start
    otherwise), and a CPU nobody asked for is an error. Returns the device as
    JAX reports it."""
    import jax

    from datatunerx_tpu.utils import runtime

    runtime.configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    info = runtime.require_backend()
    return {"platform": info["platform"], "kind": info["device_kind"], "count": info["count"]}


def exit_now(code: int) -> None:
    """Leave without waiting for daemon threads (request watchers, the engine's
    scheduler): they hold nothing worth waiting for."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


class CompileCounter:
    """Counts lowerings and backend compiles through jax.monitoring (what
    ``analysis/sanitizers/compile.py`` counts), so that a run can say that
    nothing compiled inside its window."""

    def __init__(self):
        import jax

        self._mu = threading.Lock()
        self.lowerings = 0
        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, *a, **kw):
        if event == _LOWER_EVENT:
            with self._mu:
                self.lowerings += 1
        elif event == _BACKEND_EVENT:
            with self._mu:
                self.backend += 1

    def total(self) -> int:
        with self._mu:
            return self.lowerings + self.backend


@dataclasses.dataclass
class Context:
    cell: object
    seed: int
    seconds: float
    trace: bool
    on_cpu: bool
    device: dict
    t_process: float            # perf_counter at process start
    trace_dir: str
    counter: CompileCounter
    setup_s: float | None = None
    _compiles_at_start: int = 0

    def mark_window_start(self) -> None:
        """Set-up ends and the measured window starts NOW."""
        self.setup_s = time.perf_counter() - self.t_process
        self._compiles_at_start = self.counter.total()

    def compiles_in_window(self) -> int:
        return self.counter.total() - self._compiles_at_start


@dataclasses.dataclass
class Observed:
    """What the per-layer metric readers may read."""
    cell: object
    records: list = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)
    engine_info: dict = dataclasses.field(default_factory=dict)
    reduced: dict = dataclasses.field(default_factory=dict)
    train: dict = dataclasses.field(default_factory=dict)
    check: dict = dataclasses.field(default_factory=dict)
    flat: dict | None = None        # flattened profiler trace (trace_reduce.flatten)
    trace_window: tuple | None = None   # the traced part of the window, benchmark's clock
    trace_clock: tuple | None = None    # the same, on the trace's clock
    peaks: dict | None = None


@contextlib.contextmanager
def tracing(trace_dir: str, span: str):
    """Profile what runs inside, with the benchmark's own span around it so the
    reduction finds the window on the trace's clock. No Python tracer: it would
    slow the host whose gaps the trace is there to show."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation(span)
    ann.__enter__()
    try:
        yield
    finally:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


def peak_memory_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
