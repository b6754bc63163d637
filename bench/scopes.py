"""What the reduction in ``trace.py`` leaves out of a traced run's profile:
each operation's scope and the engine's own host spans.

An operation's scope is the name-stack path of the program line that
produced it (``jit(_tick_fn)/while/body/.../attention/...``), where
``jax.named_scope`` names the pieces of a step (``models/``,
``serve/engine.py``). On the TPU the ``XLA Ops`` events carry no scope (no
``tf_op`` stat, no metadata in their HLO text); the profiler keeps each
program's optimized HLO (an ``HloProto``) in the ``/host:metadata`` plane,
and an operation's scope is its instruction's ``op_name`` there. The
engine's spans (``engine.*``, ``jax.profiler.TraceAnnotation``) lie on the
host plane beside the benchmark's and share the trace's clock.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
from dataclasses import dataclass, field

from bench import trace

ENGINE_PREFIX = "engine."
# the ``jax.named_scope`` names of one step's pieces in the programs; the
# layer scan's own slicing and write-back of the stacked cache sits under
# none of them
STEP_SCOPES = ("attn_in", "kv_write", "attention", "attn_out", "mlp", "head", "sample")
METADATA_PLANE = "/host:metadata"


@dataclass
class Extra:
    """Per program, as the trace names it (``jit__tick_fn(<id>)``), each
    HLO instruction's ``op_name``; and the engine's host spans."""

    hlo: dict[str, dict[str, str]] = field(default_factory=dict)
    spans: list[trace.Event] = field(default_factory=list)


def extract(pd, hlo: dict | None = None) -> Extra:
    """The engine's spans of a ``ProfileData``; ``hlo`` is :func:`hlo_scopes`
    of the same profile."""
    spans = [trace.Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(ENGINE_PREFIX)]
    return Extra(hlo or {}, spans)


def load(directory: str) -> Extra:
    """Read the newest ``.xplane.pb`` under ``directory``, as
    :func:`trace.load` does; nothing where there is none."""
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return Extra()
    return _load(paths[-1], os.stat(paths[-1]).st_mtime_ns)


@functools.lru_cache(maxsize=1)  # one profile, read once for all readers
def _load(path: str, mtime_ns: int) -> Extra:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    return extract(ProfileData.from_serialized_xspace(raw), hlo_scopes(raw))


def of(run) -> Extra:
    """What the traced run's profile holds beyond ``run.trace``."""
    from bench import harness

    return load(str(harness.TRACE_DIR))


# ------------------------------------------------- the programs' HLO, raw
#
# ``ProfileData`` does not expose the metadata plane's stat values, so the
# few fields needed are read off the protobuf wire format. Field numbers:
# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map: value 2),
# .stat_metadata 5 (map: value 2); XEventMetadata.name 2, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2.


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of each field of a protobuf message: an int for
    a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _first(b, number: int, default=b""):
    return next((v for f, v in _fields(b) if f == number), default)


def _op_names(hlo_proto) -> dict[str, str]:
    """Each instruction's ``op_name``, keyed by instruction name."""
    out = {}
    for f, comp in _fields(_first(hlo_proto, 1)):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g == 2:
                name = bytes(_first(ins, 1)).decode()
                out[name] = bytes(_first(_first(ins, 7), 2)).decode()
    return out


def hlo_scopes(raw: bytes) -> dict[str, dict[str, str]]:
    """Per program of a serialized ``XSpace``, the ``op_name`` of each of its
    HLO instructions; empty where the profile kept no HLO."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1 or bytes(_first(plane, 2)).decode() != METADATA_PLANE:
            continue
        stat_names = {_first(m, 1, 0): bytes(_first(m, 2)).decode()
                      for m in (_first(v, 2) for g, v in _fields(plane) if g == 5)}
        for e in (_first(v, 2) for g, v in _fields(plane) if g == 4):
            name = bytes(_first(e, 2)).decode()
            for g, stat in _fields(e):
                if g == 5 and stat_names.get(_first(stat, 1, 0)) == "Hlo Proto":
                    out[name] = _op_names(_first(stat, 6))
    return out


# ------------------------------------------------------- for the readers


def in_scopes(scope: str, scopes) -> bool:
    """Whether the name-stack path ``scope`` lies under one of ``scopes``: a
    component of the path, not a substring (``decode_attention`` is not
    ``attention``)."""
    return any(part in scopes for part in scope.split("/"))


def program_ops(tr: trace.Trace, hlo: dict, device: int, tag: str) -> list[tuple]:
    """(operation, scope) of each operation on ``device`` inside the window
    that ran in a program whose name contains ``tag``, loops and calls left
    out (their body's operations are counted). The scope is the ``op_name``
    of the operation's instruction in its program's HLO, or ``""``."""
    mods = sorted(tr.modules.get(device, []), key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in tr.ops.get(device, []):
        if (tag not in e.module or e.name.split(".")[0] in trace.CONTAINERS
                or e.start < tr.t0 or e.end > tr.t1):
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        out.append((e, hlo.get(mods[i].name, {}).get(e.name, "")))
    return out


def span_events(tr: trace.Trace, spans: list[trace.Event], name: str) -> list[trace.Event]:
    """The spans called ``name`` inside the window."""
    return [s for s in spans if s.name == name and s.start >= tr.t0 and s.end <= tr.t1]
