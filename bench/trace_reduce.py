"""Reduction of a `jax.profiler` trace (`*.xplane.pb`) to the numbers the
per-layer metrics read: device busy time as the union of operation
intervals, a kernel's summed device time found by name, the top device
operations, and the longest idle gaps labelled by what the host was
doing.

Device operations are the events of the "XLA Ops" line of each TPU
device plane.  A trace with no TPU plane (one recorded on the CPU, as the
tests do) takes the host-thread events that carry an `hlo_op` stat
instead, so the reduction can be checked without a chip; the benchmark
itself never reduces a trace from anything but a TPU.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

# host annotations the benchmark wraps around its calls into the engine
ANNOTATION_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    text: str           # name plus every string stat, for name matching


@dataclasses.dataclass
class Trace:
    device_ops: List[Event]
    host: List[Event]           # events of the host thread that ran Python
    n_devices: int


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return found[-1]


def _short(name: str) -> str:
    """An HLO op event's instruction name: "%fusion.3 = bf16[...] ..." ->
    "fusion.3"."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        strs = [str(v) for _, v in e.stats if isinstance(v, str)]
        out.append(Event(_short(e.name), e.start_ns,
                         e.start_ns + e.duration_ns,
                         " ".join([e.name] + strs)))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        # the profile reader's stat type warns on every access
        warnings.simplefilter("ignore", DeprecationWarning)
        return _load(ProfileData.from_file(path))


def _load(pd) -> Trace:
    device, host, n_dev = [], [], 0
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.extend(_events(line))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = _events(line)
                # the thread that ran Python ("python", "python3", ...)
                if line.name.startswith("python"):
                    host.extend(evs)
                cpu_ops.extend(e for e, raw in zip(evs, line.events)
                               if any(k == "hlo_op" for k, _ in raw.stats))
    if n_dev == 0:
        device, n_dev = cpu_ops, 1
    device.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(device, host, n_dev)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Nanoseconds in [lo, hi) in which some device operation ran,
    averaged over the devices."""
    busy = union(clip([(e.start, e.end) for e in trace.device_ops], lo, hi))
    return sum(e - s for s, e in busy) / trace.n_devices


def kernel_ns(trace: Trace, pattern: str, lo: float, hi: float) -> float:
    """Summed device time of the operations whose name or string stats
    match `pattern` (a regular expression), inside [lo, hi)."""
    rx = re.compile(pattern)
    return sum(e - s for s, e in clip(
        [(ev.start, ev.end) for ev in trace.device_ops
         if rx.search(ev.text)], lo, hi))


# control-flow operations whose bodies are listed as operations of their
# own: counting both would count the body twice
CONTAINERS = ("while", "conditional", "call")


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """[name, seconds] of the n device operations that took most time,
    control-flow containers left out."""
    tot: Dict[str, float] = {}
    for ev in trace.device_ops:
        if ev.name.split(".")[0] in CONTAINERS:
            continue
        for s, e in clip([(ev.start, ev.end)], lo, hi):
            tot[ev.name] = tot.get(ev.name, 0.0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in best]


def gaps(trace: Trace, lo: float, hi: float) -> List[Interval]:
    """Intervals of [lo, hi) in which no device operation ran."""
    busy = union(clip([(e.start, e.end) for e in trace.device_ops], lo, hi))
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(trace: Trace, t: float) -> str:
    """What the host was doing at time t: the benchmark's annotation that
    encloses it and the innermost other host event inside that, as
    "bench.step > PjitFunction(...)"."""
    outer: Optional[Event] = None
    inner: Optional[Event] = None
    for ev in trace.host:
        if ev.start > t:
            break
        if ev.end < t:
            continue
        if ev.name.startswith(ANNOTATION_PREFIX):
            if outer is None or ev.end - ev.start < outer.end - outer.start:
                outer = ev
        elif inner is None or ev.end - ev.start < inner.end - inner.start:
            inner = ev
    parts = [ev.name for ev in (outer, inner) if ev is not None]
    return " > ".join(parts) if parts else "none"


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10
              ) -> List[Tuple[str, float]]:
    """[label, seconds] of the n longest idle gaps, each labelled by the
    host's activity at its midpoint."""
    longest = sorted(gaps(trace, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[host_label(trace, (s + e) / 2), (e - s) * 1e-9]
            for s, e in longest]


def annotation_span(trace: Trace, name: str) -> Optional[Interval]:
    """(first start, last end) of the host annotations called `name`: the
    traced window as the benchmark's own calls bound it."""
    evs = [ev for ev in trace.host if ev.name == name]
    if not evs:
        return None
    return (evs[0].start, max(ev.end for ev in evs))
