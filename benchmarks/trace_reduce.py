"""From a profiler trace (``*.xplane.pb``) to numbers: device busy and idle
seconds, time per compiled program and per kernel, and what the host was doing
in the longest idle gaps. Only JAX is needed to read the file.

A trace is first flattened to plain lists of ``(name, start_s, dur_s)`` so that
the arithmetic below can be checked on a small recorded trace
(``tests/data/trace_small.txt``, a text-format XSpace).

What the planes of a TPU trace hold (read off a v5e trace, PR 23):
  ``/device:TPU:<n>``  lines ``XLA Ops`` (one event per executed HLO op or
                       fusion or custom call), ``XLA Modules`` (one event per
                       executed program, named ``jit_<fn>(<hash>)``), ``Steps``.
  ``/host:CPU``        one line per host thread; ``TraceAnnotation`` spans of
                       the program and of the benchmark appear by their names.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def flatten(profile) -> dict:
    """ProfileData -> {"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [(name, start_s, dur_s), ...]} with times in seconds."""
    devices, host = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return flatten(ProfileData.from_text_proto(f.read()))
    return flatten(ProfileData.from_file(path))


def union_seconds(events, lo=None, hi=None):
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((s, s + d) for _, s, d in events)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in spans:
        if lo is not None:
            s, e = max(s, lo), max(e, lo)
        if hi is not None:
            s, e = min(s, hi), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events, lo, hi):
    """Idle intervals [(start, end)] of [lo, hi] not covered by any event."""
    out, cur = [], lo
    for s, e in sorted((s, s + d) for _, s, d in events):
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def window_of(flat: dict, host_span: str | None = None):
    """The traced window [lo, hi]: the benchmark's own span where it is in the
    trace, else first device op to last."""
    if host_span:
        spans = [(s, s + d) for n, s, d in flat["host"] if n == host_span]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
    ops = [ev for dev in flat["devices"].values() for ev in dev["ops"]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def busy_idle(flat: dict, lo: float, hi: float) -> dict:
    """Seconds in which an operation ran, averaged over the chips traced."""
    if not flat["devices"]:
        raise ValueError("the trace holds no device plane")
    busy = [union_seconds(dev["ops"], lo, hi) for dev in flat["devices"].values()]
    return {"busy_s": sum(busy) / len(busy), "window_s": hi - lo, "chips": len(busy)}


def _first_device(flat):
    return flat["devices"][sorted(flat["devices"])[0]]


def program_times(flat: dict, needle: str, lo=None, hi=None) -> list:
    """Device durations of every execution of the programs whose name holds ``needle``."""
    return [d for n, s, d in _first_device(flat)["modules"]
            if needle in n and (lo is None or s >= lo) and (hi is None or s + d <= hi)]


def op_times(flat: dict, match, lo=None, hi=None) -> list:
    """Durations of the device ops whose trace name ``match`` accepts. On this
    chip an op's name is its whole HLO line (``%name = type op(operands)``), so
    a kernel with no stable name can still be found by its operand shapes."""
    return [d for n, s, d in _first_device(flat)["ops"]
            if match(n) and (lo is None or s >= lo) and (hi is None or s + d <= hi)]


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12 fusion``."""
    head, _, rest = name.partition(" = ")
    kind = re.search(r"[\s)]([a-z][\w.\-]*)\(", " " + rest)
    return (head.lstrip("%") + (" " + kind.group(1) if kind else ""))[:96]


def self_times(events) -> list:
    """(name, self seconds) per event: its duration less what the events nested
    inside it cover. The ops line nests (a while loop holds its body's ops), so
    plain durations would count the same time at every level."""
    out, stack = [], []  # stack of [name, end, self]
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= d
        stack.append([n, s + d, d])
    out += [(n, t) for n, _, t in stack]
    return out


def top_ops(flat: dict, lo: float, hi: float, k: int = 10) -> list:
    tot = {}
    inside = [e for e in _first_device(flat)["ops"] if e[1] >= lo and e[1] + e[2] <= hi]
    for n, t in self_times(inside):
        key = short_name(n)
        tot[key] = tot.get(key, 0.0) + t
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps_by_host_span(flat: dict, lo: float, hi: float, names, k: int = 10) -> list:
    """The device's idle time in [lo, hi], attributed to the named host span
    that covers the middle of each gap ('' where none does), summed by name."""
    spans = [(n, s, s + d) for n, s, d in flat["host"] if n in names]
    tot = {}
    for g0, g1 in gaps(_first_device(flat)["ops"], lo, hi):
        mid = 0.5 * (g0 + g1)
        # the innermost (shortest) covering span names the gap
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        name = min(cover)[1] if cover else "(no host span)"
        tot[name] = tot.get(name, 0.0) + (g1 - g0)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def inventory(flat: dict, k: int = 40) -> dict:
    """Names and total seconds, for reading a new trace by hand."""
    out = {"planes": sorted(flat["devices"]), "host_spans": {}, "ops": {}, "modules": {}}
    for n, _, d in flat["host"]:
        out["host_spans"][n] = out["host_spans"].get(n, 0.0) + d
    if flat["devices"]:
        dev = _first_device(flat)
        for n, _, d in dev["modules"]:
            out["modules"][n] = out["modules"].get(n, 0.0) + d
        for n, t in self_times(dev["ops"]):
            out["ops"][n[:400]] = out["ops"].get(n[:400], 0.0) + t
    for key in ("host_spans", "ops", "modules"):
        out[key] = dict(sorted(out[key].items(), key=lambda kv: -kv[1])[:k])
    return out
