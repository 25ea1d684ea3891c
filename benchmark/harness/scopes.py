"""Device time of a program by the named scopes of its operations.

Every device operation in a profiler trace refers to metadata that holds
the scope path XLA keeps for it (the ``tf_op`` stat, such as
``jit(<lambda>)/vmap(jit(dt_watershed))/ws.flood/while``): the program's
``jax.named_scope`` names, with nothing compiled or fetched to map them.
``jax.profiler.ProfileData`` shows an event's name and stats but not its
metadata's stats, so this module reads the ``.xplane.pb`` protocol
buffer itself, with a descriptor of the few XPlane fields it needs
(``tsl/profiler/protobuf/xplane.proto``; unknown fields are skipped).

Operations nest on a device's op line (a ``while`` and the operations of
its body); each instant of device time goes to the outermost operation
running then, and that operation's phase is the outermost scope of its
path that starts with the program's prefix (``ws.`` or ``cc.``), or where
its path has none, that of the operations nested in it or around it
(``outermost``).  Time left with no such scope is "unscoped".  All times are nanoseconds
on the trace's clock, the clock of ``harness.xtrace``.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.harness import window as window_mod
from benchmark.harness import xtrace

WS_PROGRAM = ("jit__lambda",)  # the fused kernel of tasks/watershed.py
CC_PROGRAM = ("jit__components_batch",)  # tasks/thresholded_components.py
UNSCOPED = ""  # the phase of an operation outside every scope

_TYPES = {"int64": 3, "uint64": 4, "string": 9, "message": 11}
# message -> (field, number, type, repeated, message type)
_FIELDS = {
    "XSpace": [("planes", 1, "message", True, "XPlane")],
    "XPlane": [("name", 2, "string", False, None),
               ("lines", 3, "message", True, "XLine"),
               ("event_metadata", 4, "message", True, "EventMetadataEntry"),
               ("stat_metadata", 5, "message", True, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", False, None),
                          ("value", 2, "message", False, "XStatMetadata")],
    "XLine": [("name", 2, "string", False, None),
              ("timestamp_ns", 3, "int64", False, None),
              ("events", 4, "message", True, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", False, None),
               ("offset_ps", 2, "int64", False, None),
               ("duration_ps", 3, "int64", False, None)],
    "XEventMetadata": [("id", 1, "int64", False, None),
                       ("name", 2, "string", False, None),
                       ("stats", 5, "message", True, "XStat")],
    "XStat": [("metadata_id", 1, "int64", False, None),
              ("str_value", 5, "string", False, None),
              ("ref_value", 7, "uint64", False, None)],
    "XStatMetadata": [("id", 1, "int64", False, None),
                      ("name", 2, "string", False, None)],
}
_PACKAGE = "ctt_bench_xplane"


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3")
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind, repeated, type_name in fields:
            f = m.field.add(name=name, number=number, type=_TYPES[kind],
                            label=3 if repeated else 1)
            if type_name:
                f.type_name = f".{_PACKAGE}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


@dataclass
class DeviceOps:
    """The programs and operations of one device."""

    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[float, float, str]] = field(
        default_factory=list)  # (start, duration, scope path)


def decode(data: bytes) -> Dict[int, DeviceOps]:
    """Device number -> its programs ``(name, start, duration)`` and
    operations ``(start, duration, scope path)`` of a serialized XSpace."""
    space = _xspace_class().FromString(data)
    out: Dict[int, DeviceOps] = {}
    for plane in space.planes:
        dev = xtrace._device_id(plane.name)
        if dev is None:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        names, paths = {}, {}
        for entry in plane.event_metadata:
            meta = entry.value
            names[entry.key] = meta.name
            for st in meta.stats:
                if st.metadata_id in tf_op:
                    paths[entry.key] = (st.str_value
                                        or stat_names.get(st.ref_value, ""))
        d = out.setdefault(dev, DeviceOps())
        for line in plane.lines:
            base = float(line.timestamp_ns)
            if line.name == xtrace.MODULES:
                d.modules.extend(
                    (names.get(e.metadata_id, ""), base + e.offset_ps / 1e3,
                     e.duration_ps / 1e3) for e in line.events)
            elif line.name == xtrace.OPS:
                d.ops.extend(
                    (base + e.offset_ps / 1e3, e.duration_ps / 1e3,
                     paths.get(e.metadata_id, "")) for e in line.events)
    return out


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int, size: int) -> Dict[int, DeviceOps]:
    with open(path, "rb") as f:
        return decode(f.read())


def _newest(profile_dir: str) -> Optional[Tuple[str, int, int]]:
    """``(path, mtime, size)`` of the newest ``.xplane.pb`` under
    ``profile_dir`` (the key of the decode caches), or None."""
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    st = os.stat(paths[-1])
    return paths[-1], st.st_mtime_ns, st.st_size


@functools.lru_cache(maxsize=None)  # one entry per distinct scope path
def phase_of(path: str, prefix: str) -> str:
    """The outermost scope of ``path`` that starts with ``prefix`` (a
    scope may appear wrapped by a transform, as in ``vmap(ws.reclose)``),
    or ``UNSCOPED``."""
    m = re.search(r"(?:^|[/(])(%s[A-Za-z0-9_]+)(?=[)/:]|$)"
                  % re.escape(prefix), path)
    return m.group(1) if m else UNSCOPED


def phase_ns(devices: Dict[int, DeviceOps], programs: Sequence[str],
             prefix: str, window: Tuple[float, float]) -> Dict[str, float]:
    """Device nanoseconds of the programs called one of ``programs``
    inside ``window``, by phase (``UNSCOPED`` for the rest), summed over
    the devices; see ``outermost`` for the attribution."""
    out: Dict[str, float] = {}
    for _, phase, _, ns in outermost(devices, programs, prefix, window):
        out[phase] = out.get(phase, 0.0) + ns
    return out


def outermost(devices: Dict[int, DeviceOps], programs: Sequence[str],
              prefix: str, window: Tuple[float, float]) -> List[list]:
    """``[execution, phase, rule, nanoseconds]`` of every outermost
    operation of the programs called one of ``programs``, in device order,
    clipped to ``window``.

    An operation belongs to the program running on its device when it
    starts; nested operations are covered by the outermost one, so the
    nanoseconds add up to the union of the programs' operations.  The
    phase of an outermost operation comes from, by ``rule``:

    * ``own``: its own scope path;
    * ``nested``: the operations nested in it, weighted by their time —
      the TPU compiler keeps no name on a ``while``, while the operations
      of its body and condition, nested in it on the op line, keep theirs;
    * ``between``: the operations around it — an unnamed run of outermost
      operations (the layout copies and the scatter's sort the compiler
      adds) between two of one phase, in one execution, is that phase's;
    * ``none``: nothing, and it stays ``UNSCOPED``."""
    lo, hi = window
    tops: List[list] = []
    for dev, d in devices.items():
        mods = sorted((s, s + dur) for name, s, dur in d.modules
                      if name.split("(")[0] in programs)
        if not mods:
            continue
        starts = [m[0] for m in mods]
        inside = []
        for s, dur, path in d.ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            a, b = max(s, lo), min(s + dur, hi, mods[i][1])
            if b > a:
                inside.append((a, b, path, (dev, i)))
        inside.sort(key=lambda x: (x[0], -x[1]))
        covered = float("-inf")
        for k, (a, b, path, execution) in enumerate(inside):
            if b <= covered:
                continue
            phase, rule = phase_of(path, prefix), "own"
            if not phase:
                phase = _nested_phase(inside, k, prefix)
                rule = "nested" if phase else "none"
            tops.append([execution, phase, rule, b - max(a, covered)])
            covered = b
    last = None  # the last outermost operation with a phase
    for k, (execution, phase, _, _) in enumerate(tops):
        if not phase:
            continue
        if (last is not None and tops[last][0] == execution
                and tops[last][1] == phase):
            for j in range(last + 1, k):
                tops[j][1:3] = [phase, "between"]
        last = k
    return tops


def _nested_phase(ops, k: int, prefix: str) -> str:
    """The phase of most of the time of the operations nested in
    ``ops[k]`` (those after it, in start order, that start before it
    ends), or ``UNSCOPED``."""
    end = ops[k][1]
    votes: Dict[str, float] = {}
    for j in range(k + 1, len(ops)):
        a, b, path, _ = ops[j]
        if a >= end:
            break
        phase = phase_of(path, prefix)
        if phase:
            votes[phase] = votes.get(phase, 0.0) + min(b, end) - a
    return max(votes, key=votes.get) if votes else UNSCOPED


def profile_dir(ctx) -> str:
    """Where the run keeps its profile: ``profile/`` beside the output."""
    return os.path.join(os.path.dirname(ctx.output_path), "profile")


@functools.lru_cache(maxsize=8)
def _program_phases(path, mtime_ns, size, programs, prefix, window):
    tops = outermost(_load(path, mtime_ns, size), programs, prefix, window)
    got: Dict[str, float] = {}
    rules: Dict[str, float] = {}
    for _, phase, rule, ns in tops:
        got[phase] = got.get(phase, 0.0) + ns
        rules[rule] = rules.get(rule, 0.0) + ns
    total = sum(got.values())
    if total > 0:
        parts = ", ".join(f"{k or 'unscoped'} {v / 1e9:.3f} s"
                          for k, v in sorted(got.items()))
        by = ", ".join(f"{k} {100.0 * v / total:.3f}%"
                       for k, v in sorted(rules.items()))
        print(f"[bench] scopes of {'/'.join(programs)} over "
              f"{total / 1e9:.3f} s: {parts}; phase by rule: {by}",
              file=sys.stderr, flush=True)
    return got


def program_phases(ctx, programs: Sequence[str],
                   prefix: str) -> Optional[Dict[str, float]]:
    """Device nanoseconds of a program by phase over the run's traced
    window; None where the run has no trace or the program no operation
    in a scope of ``prefix`` (a program without named scopes)."""
    newest = _newest(profile_dir(ctx)) if ctx.trace is not None else None
    if newest is None:
        return None
    got = _program_phases(*newest, tuple(programs), prefix,
                          tuple(ctx.trace.window))
    if not any(k != UNSCOPED for k in got):
        return None
    return got


def ms_per_mvox(ctx, programs: Sequence[str], prefix: str,
                phase: str) -> Optional[float]:
    """Milliseconds of device time of the programs in ``phase`` (0 where
    no operation ran in it) per Mvox of the window's jobs' ROIs."""
    got = program_phases(ctx, programs, prefix)
    if got is None:
        return None
    mvox = sum(window_mod.voxels((job["begin"], job["end"]))
               for job in ctx.jobs) / 1e6
    return got.get(phase, 0.0) / 1e6 / mvox if mvox > 0 else None
