"""Device time by the names the program gives its own regions.

``models/llama.py`` wraps each region of a layer in a ``jax.named_scope``
(``dtx.qkv``, ``dtx.kv_write``, ``dtx.attn``, ``dtx.attn_out``, ``dtx.mlp`` under
``dtx.layers``; ``dtx.unembed`` after the scan; ``dtx.sample`` in the engine's
decode step), and every ``pallas_call`` has a ``name=``. Where these land in a
v5e trace (read off by hand, PR 24):

* a kernel's name becomes the custom call's instruction name, so it is in the
  op event's ``name`` (``%dtx_paged_decode.3 = ... custom-call(...)``), which
  ``trace_reduce.flatten`` keeps;
* a scope is in NO stat of the op event and not in its name. It is in the
  ``op_name`` metadata of the instruction inside the ``Hlo Proto`` that the
  profiler stores per program in the ``/host:metadata`` plane, keyed by the
  same ``jit_<fn>(<hash>)`` that names the program's ``XLA Modules`` events.
  ``jax.profiler.ProfileData`` does not expose that plane's event metadata, so
  this module reads the ``.xplane.pb`` a second time, with a protobuf wire
  reader of its own (nothing but the standard library), and takes from each
  program's HLO the pairs (instruction name, op_name).

An op's region is the first ``dtx.`` scope of its op_name other than
``dtx.layers``; ``dtx.layers`` alone is what the layer scan itself moves (a
layer's slice of the KV pool out of the scan's operands and back); no ``dtx.``
scope at all is unscoped. Seconds are self times (``trace_reduce.self_times``):
a while loop's time is its body's ops, not counted again at the loop.
"""

from __future__ import annotations

import bisect
import os
import re

import readers
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS = "dtx.layers"
_SCOPE = re.compile(r"dtx\.[a-z_]+")
REMAT_MARK = "rematted_computation"
# regions of a decode step, by what they stream
KV_POOL = ("dtx.kv_write", LAYERS)
WEIGHTS = ("dtx.qkv", "dtx.attn_out", "dtx.mlp", "dtx.unembed")


# ------------------------------------------------------- protobuf, by hand

def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one message: an int for a varint, (start, end)
    into ``buf`` for a length-delimited field; fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _sub(buf, span, *path):
    """The length-delimited fields reached from the message at ``span`` by
    following the field numbers of ``path``, one level of nesting each."""
    spans = [span]
    for number in path:
        spans = [v for sp in spans for n, v in _fields(buf, *sp)
                 if n == number and isinstance(v, tuple)]
    return spans


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def hlo_op_names(buf) -> dict:
    """{program (``jit_<fn>(<hash>)``): {instruction name: op_name}} from a
    serialized XSpace. Field numbers: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4 (map: value 2); XEventMetadata.name 2, .stats 5;
    XStat.bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    buf = memoryview(buf)
    out = {}
    for plane in _sub(buf, (0, len(buf)), 1):
        if [_text(buf, n) for n in _sub(buf, plane, 2)] != ["/host:metadata"]:
            continue
        for meta in _sub(buf, plane, 4, 2):
            table = {}
            for ins in _sub(buf, meta, 5, 6, 1, 3, 2):
                name, op = _sub(buf, ins, 1), _sub(buf, ins, 7, 2)
                if name and op and op[0][1] > op[0][0]:
                    table[_text(buf, name[0])] = _text(buf, op[0])
            program = _sub(buf, meta, 2)
            if program and table:
                out[_text(buf, program[0])] = table
    return out


# ------------------------------------------------------------ from a trace

def xplane_path(obs) -> str:
    """The run's trace file: still on disk while readers run (``run.py`` removes
    the directory after the last of them)."""
    return getattr(obs, "xplane", None) or trace_reduce.find_xplane(
        os.path.join(HERE, ".trace", obs.cell.name))


def _load_bytes(path: str) -> bytes:
    if path.endswith(".txt"):
        from jax.profiler import ProfileData

        with open(path) as f:
            return ProfileData.text_proto_to_serialized_xspace(f.read())
    with open(path, "rb") as f:
        return f.read()


def region_of(op_name) -> str | None:
    scopes = _SCOPE.findall(op_name or "")
    for s in scopes:
        if s != LAYERS:
            return s
    return LAYERS if scopes else None


def whole_runs(obs) -> list:
    """[(program, start, duration)] of the program executions that lie in the
    traced window and were traced whole. The execution in flight when the
    profiler's session opens or closes is in the trace with the part of it that
    the session saw: one shorter than 0.8 of its program's median is left out."""
    lo, hi = obs.trace_clock
    runs = [m for m in trace_reduce._first_device(obs.flat)["modules"]
            if m[1] >= lo and m[1] + m[2] <= hi]
    by_program = {}
    for program, _, d in runs:
        by_program.setdefault(program, []).append(d)
    median = {p: sorted(ds)[len(ds) // 2] for p, ds in by_program.items()}
    return [m for m in runs if m[2] >= 0.8 * median[m[0]]]


def scoped_ops(obs) -> list:
    """[(program, op_name or None, self seconds)] for every device op that ran
    inside one of ``whole_runs``; ``None`` where the trace's programs carry no
    ``dtx.`` scope at all (a program from before the scopes existed)."""
    cached = getattr(obs, "_scoped_ops", False)
    if cached is not False:
        return cached
    obs._scoped_ops = None
    if obs.flat is None or not obs.flat["devices"]:
        return None
    try:
        tables = hlo_op_names(_load_bytes(xplane_path(obs)))
    except (OSError, ValueError, IndexError):
        return None
    if not any(_SCOPE.search(op) for t in tables.values() for op in t.values()):
        return None
    ops = sorted(trace_reduce._first_device(obs.flat)["ops"], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    out = []
    for program, s, d in whole_runs(obs):
        table = tables.get(program, {})
        inside = [e for e in ops[bisect.bisect_left(starts, s):bisect.bisect_right(starts, s + d)]
                  if e[1] + e[2] <= s + d]
        for name, t in trace_reduce.self_times(inside):
            instruction = name.partition(" = ")[0].strip().lstrip("%")
            out.append((program, table.get(instruction), t))
    obs._scoped_ops = out
    return out


def _decode_ops(obs):
    ops = scoped_ops(obs)
    if ops is None:
        return None
    return [(op, t) for program, op, t in ops if readers.DECODE_PROGRAM in program]


def _decode_runs(obs) -> list:
    return [d for program, _, d in whole_runs(obs) if readers.DECODE_PROGRAM in program]


def decode_region_ms(obs, regions):
    """Self time of the decode program's ops whose region is one of ``regions``,
    per token step (whole executions times the steps each scans), in milliseconds."""
    ops = _decode_ops(obs)
    if not ops:
        return None
    steps = len(_decode_runs(obs)) * obs.engine_info["chunk"]
    return sum(t for op, t in ops if region_of(op) in regions) * 1e3 / steps


def decode_unscoped_share(obs):
    ops = _decode_ops(obs)
    if not ops:
        return None
    return 100.0 * sum(t for op, t in ops if region_of(op) is None) / sum(_decode_runs(obs))


def busy_share(obs, pick):
    """Self seconds of the ops whose op_name ``pick`` accepts, over the busy
    seconds (all self seconds) of the same whole executions."""
    ops = scoped_ops(obs)
    if not ops:
        return None
    return 100.0 * sum(t for _, op, t in ops if op and pick(op)) / sum(t for _, _, t in ops)
