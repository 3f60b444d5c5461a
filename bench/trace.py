"""Reduction of a profiler trace to device busy time, idle gaps, time per
XLA module, and the host spans of the harness and of the program.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per operation run and whose ``XLA Modules`` line holds
one event per executable run; the harness's ``TraceAnnotation`` spans are
events named ``bench.<what>`` on the host plane, and the served path's own
are named ``repro.<step>``.  Device and host events share the host's
clock, so spans and device intervals can be intersected.
Times here are in nanoseconds unless a name says seconds.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
_RUN_ID = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    """What the reduction needs of one trace: per device, the merged
    intervals in which an operation ran; every module run as (name,
    start, end); every harness and program span as (name, start, end), by
    start."""
    busy: List[List[Interval]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def span(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.spans if n == name]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the part of ``[lo, hi]`` that ``merged`` covers."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def module_name(event_name: str) -> str:
    """An XLA module's name without the run id the trace appends."""
    return _RUN_ID.sub("", event_name)


def from_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    tr = Trace()
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.start_ns, ev.end_ns) for ev in line.events]
                elif line.name == MODULES_LINE:
                    tr.modules += [(module_name(ev.name), ev.start_ns,
                                    ev.end_ns) for ev in line.events]
            tr.busy.append(merge(ops))
    tr.spans = sorted(((ev.name, ev.start_ns, ev.end_ns) for ev in
                       host_events(profile, (SPAN_PREFIX, PROGRAM_PREFIX))),
                      key=lambda x: x[1])
    return tr


def host_events(profile, prefixes: Tuple[str, ...]):
    """The host plane's events whose names start with one of
    ``prefixes``."""
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                yield from (ev for ev in line.events
                            if ev.name.startswith(prefixes))


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def find_xplane(directory: Path) -> Path:
    """The newest ``.xplane.pb`` the profiler wrote under ``directory``."""
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


# ------------------------------------------------------------ reductions
def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi]`` in which an operation ran, averaged over
    the devices traced."""
    if not tr.busy:
        return 0.0
    return sum(overlap(b, lo, hi) for b in tr.busy) / len(tr.busy) / 1e9


def busy_within_s(tr: Trace, windows: Sequence[Interval]) -> float:
    """Device-busy seconds inside the given host windows, averaged over
    devices."""
    return sum(busy_s(tr, s, e) for s, e in merge(windows))


def module_seconds(tr: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds of each XLA module whose run lies in ``[lo, hi]``,
    summed over its runs (and devices)."""
    out: Dict[str, float] = {}
    for name, s, e in tr.modules:
        if s >= lo and e <= hi:
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def idle_gaps(tr: Trace, lo: float, hi: float, labels: Sequence[str],
              top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps with no operation on device 0, each named
    by the harness span (of ``labels``) that covers most of it, or
    ``"other"``."""
    if not tr.busy:
        return []
    named = {lab: merge(tr.span(lab)) for lab in labels}
    out = []
    for s, e in gaps(tr.busy[0], lo, hi):
        cover = {lab: overlap(iv, s, e) for lab, iv in named.items()}
        best = max(cover, key=cover.get) if cover else None
        lab = best if best is not None and cover[best] > 0 else "other"
        out.append((lab.removeprefix(SPAN_PREFIX), (e - s) / 1e9))
    out.sort(key=lambda x: -x[1])
    return out[:top]
