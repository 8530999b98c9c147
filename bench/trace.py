"""Reduce a profiler trace (``.xplane.pb``) to device time per host span.

The benchmark wraps every call it makes in a ``jax.profiler.TraceAnnotation``
named ``ops.<family>`` (a compiler call) or ``glue.<name>`` (its own jitted
``jax.numpy``).  On the host, each program launch (``DoEnqueueProgram``)
carries a ``run_id``, and so does the program's event on the device line
``XLA Modules``; the launch lies inside the span of the call that issued
it.  So every device program is attributed to the benchmark's own span
names, whatever the program calls its kernels.

Device and host timestamps are not on one clock, so nothing here compares
a device time with a host time: an idle gap on the device is attributed to
the span that launched the program which ended the gap, i.e. what the host
was still doing while the device waited.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Substrings of an HLO instruction that mark a collective.
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")

Interval = Tuple[float, float]


@dataclass
class DeviceTrace:
    """One device's programs and operations, in seconds on its own clock."""

    name: str
    #: (start, end, span name) of each program the device ran
    modules: List[Tuple[float, float, str]] = field(default_factory=list)
    #: (start, end, short op name) of each operation
    ops: List[Tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Reduced:
    devices: List[DeviceTrace]

    def window_s(self) -> float:
        """From a device's first program's start to its last one's end,
        the longest over the devices (each on its own clock)."""
        lengths = [max(m[1] for m in d.modules) - min(m[0] for m in d.modules)
                   for d in self.devices if d.modules]
        return max(lengths) if lengths else 0.0

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(_union_length([(s, e) for s, e, _ in d.ops])
                   for d in self.devices) / len(self.devices)

    def span_device_s(self) -> Dict[str, float]:
        """Device seconds of the programs each span launched, averaged over
        the devices."""
        out: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for s, e, span in d.modules:
                out[span] += (e - s) / len(self.devices)
        return dict(out)

    def top_ops(self, n: int = 10) -> List[List]:
        """The operations that took most device time (summed, averaged over
        the devices), named ``<span> <op>``."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for s, e, name in d.ops:
                tot[name] += (e - s) / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Device idle time between operations, summed by the span that
        launched the program whose operation ended each gap, averaged over
        the devices."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            busy = _merge([(s, e) for s, e, _ in d.ops])
            for (_s0, prev_end), (nxt, _e1) in zip(busy, busy[1:]):
                tot[_owner(d.modules, nxt)] += (nxt - prev_end) / len(
                    self.devices)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def collective_exposed_s(self) -> Optional[float]:
        """Seconds of collective operations during which no other operation
        ran on that device, averaged over the devices; ``None`` when the
        trace holds no collective."""
        found = False
        total = 0.0
        for d in self.devices:
            coll = [(s, e) for s, e, n in d.ops if _is_collective(n)]
            other = [(s, e) for s, e, n in d.ops if not _is_collective(n)]
            if coll:
                found = True
            total += _union_length(coll) - _overlap(_merge(coll), _merge(other))
        return total / len(self.devices) if found and self.devices else None


def _is_collective(op: str) -> bool:
    return any(c in op for c in COLLECTIVES)


def _merge(iv: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _union_length(iv: Iterable[Interval]) -> float:
    return sum(e - s for s, e in _merge(iv))


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def short_op(hlo: str) -> str:
    """``%name = type[shape]`` of an HLO instruction's text."""
    head = hlo.split("(", 1)[0] if " = (" not in hlo else hlo.split(" = (")[0]
    head = head.split("{", 1)[0]
    return head.strip()


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_xplane(path: str, span_names: Iterable[str]) -> Reduced:
    """Read ``path`` and attribute each device program to the benchmark span
    (one of ``span_names``, which never overlap one another) that launched
    it; programs launched outside every such span are named ``other``."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    launches: List[Tuple[float, str]] = []          # (host start, run_id)
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in wanted:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
                elif ev.name == "DoEnqueueProgram":
                    stats = dict(ev.stats)
                    if "run_id" in stats:
                        launches.append((ev.start_ns, str(stats["run_id"])))
    spans.sort()
    # one run_id per execution, shared by every device it runs on; the
    # launches for other devices may come after the span has returned, so
    # the first launch inside a span names the program
    launcher: Dict[str, str] = {}
    for t, rid in sorted(launches):
        if rid not in launcher:
            span = _launching_span(spans, t)
            if span is not None:
                launcher[rid] = span

    devices = []
    for plane in sorted(device_planes, key=lambda p: p.name):
        d = DeviceTrace(plane.name)
        mods: List[Tuple[float, float, str]] = []
        raw_ops: List[Tuple[float, float, str]] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    rid = str(dict(ev.stats).get("run_id", ""))
                    mods.append((ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9,
                                 launcher.get(rid) or "other"))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    raw_ops.append((ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9,
                                    short_op(ev.name)))
        mods.sort()
        d.ops = [(s, e, f"{_owner(mods, s)} {op}") for s, e, op in raw_ops]
        d.modules = mods
        if mods:
            devices.append(d)
    return Reduced(devices)


def _owner(modules: Sequence[Tuple[float, float, str]], t: float) -> str:
    """Span of the program (sorted by start) that runs at device time t."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and t <= modules[i][1] else "other"


def _launching_span(spans: Sequence[Tuple[float, float, str]],
                    t: float) -> Optional[str]:
    """Name of the span (sorted, non-overlapping) that holds host time t."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    return spans[i][2] if i >= 0 and t <= spans[i][1] else None
