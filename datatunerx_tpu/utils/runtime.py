"""Start-up policy for every process that runs programs on a device: which
backend it may use, where compiled programs are kept, and one line that
says what it got.

Called once at each entry point (``tuning/train.py run``, ``serving/server.py
main``, ``benchmarks/``, ``chip_smoke.py``'s kernel child) before anything is
jitted:

- ``configure_compile_cache()`` — the persistent XLA compilation cache. When
  ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and nothing is
  touched here; otherwise the cache lives at a FIXED path inside the checkout
  (the directory is part of every cache key, so a path that moves never
  hits). Spawned trainers and servers resolve the same directory on their
  own. The key covers op metadata (named scopes), so that a cached program
  never shows a profile names it was not traced with.
- ``require_backend()`` — JAX falls back to the CPU with one log line when
  libtpu cannot open the chip (absent, or held by another process). A
  trainer or server that silently continues there looks healthy and is not,
  so a CPU backend is an error unless ``JAX_PLATFORMS=cpu`` asked for it by
  name.
- ``describe()`` — platform, device kind and count, jax and libtpu versions,
  the resolved Pallas interpret mode and the cache directory, as one dict
  the caller logs so that a process outside can assert on it.
"""

from __future__ import annotations

import collections
import json
import os
import re
from importlib import metadata

import jax

from datatunerx_tpu.ops._pallas import interpret_default

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REPO_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_compilation_cache")

_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
}
_cache_counts: "collections.Counter[str]" = collections.Counter()
_listener_on = False  # entry points call in from one thread, once


def _count_cache_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        _cache_counts[key] += 1


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and start counting its hits.
    Returns the directory in use. Must run before the process's first
    compile: JAX latches the cache state at first use."""
    global _listener_on
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # The names a profile shows inside a program (jax.named_scope: dtx.attn,
    # dtx.sample, ...) are op metadata, which the cache's key leaves out by
    # default: a program cached before a scope was added or renamed would
    # load with the old names, and the trace's readers would find nothing.
    # Key on the metadata too, with source paths taken relative to the
    # checkout so that a checkout at another path still hits.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(REPO_ROOT + os.sep))
    if not _listener_on:
        jax.monitoring.register_event_listener(_count_cache_event)
        _listener_on = True
    return path


def compile_cache_stats() -> dict:
    """Compiles that consulted the persistent cache, and how many of them
    loaded instead of compiling, since ``configure_compile_cache()``."""
    return {"dir": jax.config.jax_compilation_cache_dir,
            "requests": _cache_counts["requests"],
            "hits": _cache_counts["hits"]}


def cpu_requested() -> bool:
    """True only when the CPU platform was selected by name (the
    ``JAX_PLATFORMS`` variable or the equivalent config option) — not when
    JAX fell back to it."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}


def require_backend() -> dict:
    """Initialise the backend and refuse a CPU nobody asked for. Returns
    ``device_info()``."""
    info = device_info()
    if info["platform"] == "cpu" and not cpu_requested():
        raise RuntimeError(
            "JAX initialised the CPU backend but JAX_PLATFORMS=cpu was not "
            "requested: no accelerator could be opened (absent, or held by "
            "another process). Set JAX_PLATFORMS=cpu to run on the CPU on "
            "purpose.")
    return info


def _libtpu_version() -> str:
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "none"


def describe() -> dict:
    """What this process runs on, for its first log line."""
    out = {"jax": jax.__version__, "libtpu": _libtpu_version(),
           "backend": jax.default_backend()}
    out.update(device_info())
    out["pallas_interpret"] = interpret_default()
    out["compile_cache"] = jax.config.jax_compilation_cache_dir
    return out


def announce(tag: str, **extra) -> dict:
    """Print the ``[runtime] <tag> {...}`` line (``describe()`` plus the
    caller's own facts) and return what it said."""
    info = dict(describe(), **extra)
    print(f"[runtime] {tag} {json.dumps(info, sort_keys=True)}", flush=True)
    return info


def startup(tag: str) -> dict:
    """The entry-point sequence: cache, guard, and the ``[runtime]`` line."""
    configure_compile_cache()
    require_backend()
    return announce(tag)
