"""The compiler's own spans in a traced run, on the device trace's clock.

The program opens a profiler span at each step of its dispatch path
(``repro.kernels.registry`` and ``frontend``, ``repro.core.lowering``):

* ``ssr.call``   — one compiler call through ``registry.dispatch``;
* ``ssr.launch`` — inside it, the call into a cached jitted pipeline until
  JAX returns (under ``jax.vmap``, the batching rule too);
* ``ssr.build``  — inside it instead, a pipeline-cache miss up to the
  return of the new pipeline's first call;
* ``ssr.lookup`` / ``ssr.lower`` — inside a build, the schedule resolution
  and the planning, lowering and kernel emission.

They land in the same trace as the benchmark's own ``ops.*``/``glue.*``
spans.  This module reads them with their thread and the span that encloses
each on that thread, and maps device idle time onto them.

Device and host timestamps are not on one clock.  Every device program
shares a ``run_id`` with its launch (``DoEnqueueProgram``) on the host, and
no program starts before its launch does; so the offset δ of a device
plane (device time − host time) is at most min(program start − launch
start) over the pairs, and that lower envelope is the estimate: the
program that started soonest after its launch is taken to have started at
once.  An idle gap [a, b] on the device is the host interval [a − δ, b − δ].
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .trace import _merge, _overlap, find_xplane

#: The program's span names.
SSR_SPANS = ("ssr.call", "ssr.launch", "ssr.build", "ssr.lookup",
             "ssr.lower")

#: Where ``bench/run.py`` writes each cell's ``--trace 1`` profile.
TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"

Interval = Tuple[float, float]


@dataclass
class Span:
    """One host span, in nanoseconds on the host's clock."""

    name: str
    start: float
    end: float
    thread: str
    parent: Optional[int] = None          # index of the enclosing span
    children: List[int] = field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Device:
    """One device plane, in nanoseconds on its own clock."""

    name: str
    busy: List[Interval]                  # merged ``XLA Ops`` intervals
    #: (program start, its launch's host start) for every paired run_id
    pairs: List[Tuple[float, float]]

    def offset(self) -> Optional[float]:
        """δ: the lower envelope of program start − launch start."""
        return offset(self.pairs)

    def idle(self) -> List[Interval]:
        """Gaps between operations, first to last (as ``busy_s`` sees)."""
        return [(a[1], b[0]) for a, b in zip(self.busy, self.busy[1:])]


@dataclass
class ProgramTrace:
    spans: List[Span]                     # sorted by start, then longest
    devices: List[Device]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def self_ns(self, span: Span, minus: Sequence[str]) -> float:
        """The span's duration less its direct children named ``minus``."""
        return span.dur - sum(self.spans[c].dur for c in span.children
                              if self.spans[c].name in minus)

    def idle_in(self, name: str) -> Optional[float]:
        """Device idle ns, averaged over the devices, whose host interval
        (after each device's offset) lies inside a span named ``name``."""
        cover = _merge((s.start, s.end) for s in self.named(name))
        devs = [d for d in self.devices if d.offset() is not None]
        if not cover or not devs:
            return None
        tot = 0.0
        for d in devs:
            delta = d.offset()
            tot += _overlap([(a - delta, b - delta) for a, b in d.idle()],
                            cover)
        return tot / len(devs)

    def innermost(self) -> List[Tuple[float, float, Optional[str]]]:
        """The host window of the thread that holds most spans (its first
        span's start to its last one's end) cut into segments, each
        labelled with the innermost span holding it (``None`` outside
        every span)."""
        counts: Dict[str, int] = defaultdict(int)
        for s in self.spans:
            counts[s.thread] += 1
        if not counts:
            return []
        thread = max(counts, key=counts.get)
        mine = [s for s in self.spans if s.thread == thread]
        top = [s for s in mine if s.parent is None]
        segs = _holes(min(s.start for s in top), max(s.end for s in top),
                      top, None)
        for s in mine:
            segs += _holes(s.start, s.end,
                           [self.spans[c] for c in s.children], s.name)
        return sorted(segs)

    def breakdown(self) -> Dict[str, Dict[str, float]]:
        """Host ns and device idle ns (averaged over the devices) by the
        innermost span, grouped as ``ssr.launch``, ``ssr.call self``,
        ``ssr.build`` (a build and the spans inside it), ``ops.* outside
        ssr.call``, ``glue.*`` and ``outside every span``."""
        segs = [(a, b, _group(n)) for a, b, n in self.innermost()]
        host: Dict[str, float] = defaultdict(float)
        for a, b, g in segs:
            host[g] += b - a
        idle: Dict[str, float] = defaultdict(float)
        devs = [d for d in self.devices if d.offset() is not None]
        for d in devs:
            delta = d.offset()
            gaps = [(a - delta, b - delta) for a, b in d.idle()]
            for g, ns in _overlap_by_label(gaps, segs).items():
                idle[g] += ns / len(devs)
        return {g: {"host_ns": host.get(g, 0.0), "idle_ns": idle.get(g, 0.0)}
                for g in sorted(set(host) | set(idle))}


def _group(name: Optional[str]) -> str:
    if name is None:
        return "outside every span"
    if name == "ssr.launch":
        return "ssr.launch"
    if name == "ssr.call":
        return "ssr.call self"
    if name.startswith("ssr."):
        return "ssr.build"
    if name.startswith("ops."):
        return "ops.* outside ssr.call"
    if name.startswith("glue."):
        return "glue.*"
    return name


def offset(pairs: Iterable[Tuple[float, float]]) -> Optional[float]:
    """min(program start − launch start); ``None`` with no pair."""
    diffs = [dev - host for dev, host in pairs]
    return min(diffs) if diffs else None


def drift(pairs: Sequence[Tuple[float, float]]) -> Optional[float]:
    """δ of the second half of the programs (by start) less δ of the
    first half: how far the two clocks move apart over the window."""
    ordered = sorted(pairs)
    half = len(ordered) // 2
    if half == 0:
        return None
    return offset(ordered[half:]) - offset(ordered[:half])


def _overlap_by_label(a: Sequence[Interval],
                      segs: Sequence[Tuple[float, float, str]]
                      ) -> Dict[str, float]:
    """Intersection of the intervals ``a`` with each label's segments."""
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(a) and j < len(segs):
        lo, hi = max(a[i][0], segs[j][0]), min(a[i][1], segs[j][1])
        if hi > lo:
            out[segs[j][2]] += hi - lo
        if a[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    return dict(out)


def _holes(lo: float, hi: float, inner: Sequence[Span],
           label: Optional[str]) -> List[Tuple[float, float, Optional[str]]]:
    """[lo, hi] less the (disjoint) spans ``inner``, labelled."""
    out = []
    t = lo
    for s in sorted(inner, key=lambda s: s.start):
        if s.start > t:
            out.append((t, s.start, label))
        t = max(t, s.end)
    if hi > t:
        out.append((t, hi, label))
    return out


def nest(spans: List[Span]) -> List[Span]:
    """Sort ``spans`` and set each one's parent: the innermost span on the
    same thread that holds it."""
    spans.sort(key=lambda s: (s.start, -s.end))
    open_by_thread: Dict[str, List[int]] = defaultdict(list)
    for i, s in enumerate(spans):
        stack = open_by_thread[s.thread]
        while stack and spans[stack[-1]].end < s.end:
            stack.pop()
        if stack:
            s.parent = stack[-1]
            spans[stack[-1]].children.append(i)
        stack.append(i)
    return spans


def _wanted(name: str) -> bool:
    return name in SSR_SPANS or name.startswith(("ops.", "glue."))


def read(path: str) -> ProgramTrace:
    """Parse one ``.xplane.pb``: the benchmark's and the program's spans,
    and each device's operations and program/launch pairs."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Span] = []
    launch: Dict[str, float] = {}               # run_id -> first host start
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if _wanted(ev.name):
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      line.name))
                elif ev.name == "DoEnqueueProgram":
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        rid = str(rid)
                        launch[rid] = min(launch.get(rid, ev.start_ns),
                                          ev.start_ns)
    devices = []
    for plane in sorted(device_planes, key=lambda p: p.name):
        ops: List[Interval] = []
        pairs: List[Tuple[float, float]] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    rid = str(dict(ev.stats).get("run_id", ""))
                    if rid in launch:
                        pairs.append((ev.start_ns, launch[rid]))
            elif line.name == "XLA Ops":
                ops += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        if ops:
            devices.append(Device(plane.name, _merge(ops), pairs))
    return ProgramTrace(nest(spans), devices)


_CACHE = "_program_trace"


def of(ctx) -> Optional[ProgramTrace]:
    """The traced run's program trace, parsed once and kept on ``ctx``;
    ``None`` where the run was not traced or left no profile."""
    if ctx.trace is None:
        return None
    if _CACHE not in vars(ctx):
        try:
            found = read(find_xplane(str(TRACE_DIR / ctx.cell.name)))
        except FileNotFoundError:
            found = None
        setattr(ctx, _CACHE, found)
    return getattr(ctx, _CACHE)


def per_step_ms(ns: Optional[float], ctx) -> Optional[float]:
    if ns is None or ctx.traced_steps == 0:
        return None
    return 1e-6 * ns / ctx.traced_steps


def report(pt: ProgramTrace, steps: int) -> List[str]:
    """Lines for ``PERF.md``: each device's offset and drift, the host
    and idle time per step by innermost span, and each span's total."""
    lines = []
    for d in pt.devices:
        dr = drift(d.pairs)
        lines.append(f"{d.name}: pairs={len(d.pairs)} offset_us="
                     f"{1e-3 * d.offset():.3f} drift_us="
                     f"{'-' if dr is None else f'{1e-3 * dr:.3f}'}")
    for g, v in pt.breakdown().items():
        lines.append(f"{g}: host_ms/step={1e-6 * v['host_ns'] / steps:.4f} "
                     f"idle_ms/step={1e-6 * v['idle_ns'] / steps:.4f}")
    for name in SSR_SPANS:
        got = pt.named(name)
        lines.append(f"{name}: n={len(got)} total_ms="
                     f"{1e-6 * sum(s.dur for s in got):.3f}")
    return lines


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="Report the compiler spans "
                                 "of one .xplane.pb")
    ap.add_argument("xplane")
    ap.add_argument("--steps", type=int, default=1)
    a = ap.parse_args()
    print("\n".join(report(read(a.xplane), a.steps)))
