"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The device planes (``/device:TPU:<n>``) carry one line of whole programs
(``XLA Modules``) and one of the operations inside them (``XLA Ops``).
Busy time is the union of the operation intervals of a device; idle time
is the rest of the traced window.  Host spans (the benchmark's own job
annotations, and the program's spans placed on the same clock through a
marker) attribute each idle gap to what the host was doing in it.
All times here are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

MODULES = "XLA Modules"
OPS = "XLA Ops"
SYNC = "ctt_bench_sync"

Interval = Tuple[float, float]


@dataclass
class DeviceTrace:
    """What one trace says about the devices and the host."""

    window: Interval
    modules: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)  # device -> (name, start, duration)
    ops: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def devices(self) -> List[int]:
        return sorted(set(self.modules) | set(self.ops))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_intervals(trace: DeviceTrace, device: int) -> List[Interval]:
    events = trace.ops.get(device) or trace.modules.get(device) or []
    return union(clip([(s, s + d) for _, s, d in events], trace.window))


def busy_ns(trace: DeviceTrace) -> float:
    """Busy nanoseconds, averaged over the devices in the trace."""
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(trace, d))
               for d in devs) / len(devs)


def window_ns(trace: DeviceTrace) -> float:
    return trace.window[1] - trace.window[0]


def idle_gaps(trace: DeviceTrace, device: int) -> List[Interval]:
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in busy_intervals(trace, device):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def program_time(trace: DeviceTrace, names: Sequence[str]) -> Tuple[int, float]:
    """(executions, device nanoseconds) of the programs whose module name,
    without its ``(<id>)``, is one of ``names``, on all devices, inside the
    window."""
    inside = [e - s for events in trace.modules.values()
              for name, s0, d in events if name.split("(")[0] in names
              for s, e in clip([(s0, s0 + d)], trace.window)]
    return len(inside), sum(inside)


def once_per_block(trace: DeviceTrace, names: Sequence[str],
                   blocks: int) -> float:
    """Device nanoseconds of the program called one of ``names``, which has
    to have run once for each of the window's ``blocks`` blocks.  A program
    that did not run, was renamed, or shares its name with another program
    raises, rather than leaving its metric silent or counting too much."""
    n, ns = program_time(trace, names)
    if n != blocks or ns <= 0:
        raise ValueError(
            f"program {' or '.join(names)} ran {n} times in the window for "
            f"{blocks} blocks; match it anew in its metric's file")
    return ns


def top_ops(trace: DeviceTrace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` operations with the most device time, in seconds, summed
    over executions and averaged over devices."""
    per: Dict[str, float] = {}
    for events in trace.ops.values():
        for name, s, d in events:
            per[name] = per.get(name, 0.0) + d
    n_dev = max(len(trace.ops), 1)
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [(name, t / n_dev / 1e9) for name, t in top]


def attribute_gaps(trace: DeviceTrace, k: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds of the first device, by the innermost host span open
    over each stretch of idle time; the ``k`` names with most."""
    devs = trace.devices
    if not devs:
        return []
    spans = sorted(trace.host, key=lambda h: h[2])  # shortest first
    per: Dict[str, float] = {}
    for gs, ge in idle_gaps(trace, devs[0]):
        # cut the gap at span edges, give each piece to its innermost span
        cuts = sorted({gs, ge} | {t for _, s, d in spans
                                  for t in (s, s + d) if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            name = next((n for n, s, d in spans if s <= mid < s + d),
                        "no host span")
            per[name] = per.get(name, 0.0) + (b - a)
    top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [(name, t / 1e9) for name, t in top]


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%while.279 = (...)``
    becomes ``%while.279``."""
    return name.split(" = ", 1)[0]


def _in_modules(ops, modules):
    """Each operation named ``<program>/<operation>`` after the program
    (module) running on its device when it started."""
    mods = sorted((s, s + d, name.split("(")[0]) for name, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1]:
            name = f"{mods[i][2]}/{name}"
        out.append((name, s, d))
    return out


def _device_id(plane_name: str) -> Optional[int]:
    prefix = "/device:TPU:"
    if not plane_name.startswith(prefix):
        return None
    tail = plane_name[len(prefix):]
    return int(tail) if tail.isdigit() else None


def load(profile_dir: str, host_offset_ns: Optional[float] = None,
         extra_host: Sequence[Tuple[str, float, float]] = ()) -> DeviceTrace:
    """Read the newest ``.xplane.pb`` under ``profile_dir``.

    ``extra_host`` holds host spans on the monotonic clock in nanoseconds;
    they are moved onto the trace's clock through the sync marker, which
    the harness records at a known monotonic time ``host_offset_ns``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    return from_profile(data, host_offset_ns, extra_host)


def from_profile(data, host_offset_ns=None, extra_host=()) -> DeviceTrace:
    """The trace's device programs, operations and host spans; the window
    spans the device events until the caller narrows it."""
    modules: Dict[int, list] = {}
    ops: Dict[int, list] = {}
    host: List[Tuple[str, float, float]] = []
    sync_at = None
    for plane in data.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            for line in plane.lines:
                target = {MODULES: modules, OPS: ops}.get(line.name)
                if target is None:
                    continue
                target.setdefault(dev, []).extend(
                    (_short(e.name), float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue  # the interpreter's thread: "python3" on a TPU host
                for e in line.events:
                    if e.name == SYNC:
                        sync_at = float(e.start_ns)
                    elif not e.name.startswith("$"):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    for dev, events in ops.items():
        ops[dev] = _in_modules(events, modules.get(dev, []))
    if extra_host and sync_at is not None and host_offset_ns is not None:
        shift = sync_at - host_offset_ns
        host.extend((n, s + shift, d) for n, s, d in extra_host)
    events = [(s, s + d) for evs in list(modules.values()) + list(ops.values())
              for _, s, d in evs]
    window = (min(s for s, _ in events), max(e for _, e in events)) \
        if events else (0.0, 0.0)
    return DeviceTrace(window=window, modules=modules, ops=ops, host=host)


def host_window(trace: DeviceTrace, name: str) -> Optional[Interval]:
    """Interval spanned by the host annotations called ``name``."""
    ev = [(s, s + d) for n, s, d in trace.host if n == name]
    if not ev:
        return None
    return min(s for s, _ in ev), max(e for _, e in ev)
