"""Reduce a JAX profiler trace to the device's busy time, program times and
idle gaps, each gap labelled by the harness span that covers it.

A trace is read into flat :class:`Event` rows (plane, line, name, start,
duration) so the arithmetic below is independent of the file format and
is tested on a recorded trace and on hand-made rows alike.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

#: the label of an idle gap that no harness span covers
HOST_LOOPS = "lane event loops (host)"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_events(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [Event(p.name, ln.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for p in data.planes for ln in p.lines for ev in ln.events]


def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def is_device_op(ev: Event) -> bool:
    """An operation that ran on a TPU core (the ``XLA Ops`` line)."""
    return ev.plane.startswith("/device:TPU:") and ev.line == "XLA Ops"


def is_device_program(ev: Event) -> bool:
    """One run of a compiled program on a TPU core (``XLA Modules``)."""
    return ev.plane.startswith("/device:TPU:") and ev.line == "XLA Modules"


_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """``jit_maxmin_batch(1234)`` -> ``maxmin_batch``."""
    name = _SUFFIX.sub("", name.strip())
    return name[4:] if name.startswith("jit_") else name


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, merged ``[start, end]`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a: float, b: float, merged: List[List[float]],
            starts: List[float]) -> float:
    """Length of ``[a, b]`` covered by sorted, merged intervals."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        s, e = merged[i]
        total += max(0.0, min(b, e) - max(a, s))
        i += 1
    return total


def op_name(name: str) -> str:
    """``%fusion.93 = pred[8,128] fusion(...)`` -> ``%fusion.93``."""
    return name.split(" = ", 1)[0]


@dataclass
class Summary:
    """What one traced window says about the device."""

    busy_s: float                      # union of op intervals, per chip
    n_devices: int
    programs: Dict[str, List[float]] = field(default_factory=dict)
    top_ops: List[List] = field(default_factory=list)     # [name, seconds]
    idle_gaps: List[List] = field(default_factory=list)   # [label, seconds]
    gap_totals: Dict[str, float] = field(default_factory=dict)

    def program(self, name: str) -> Optional[Tuple[float, int]]:
        """(device seconds, runs) of the named program, None if absent."""
        hit = self.programs.get(name)
        return None if hit is None else (hit[0], int(hit[1]))


def summarize(events: Sequence[Event], spans: Sequence[str],
              is_op: Callable[[Event], bool] = is_device_op,
              is_program: Callable[[Event], bool] = is_device_program,
              top: int = 10) -> Summary:
    """Busy time (union of ``is_op`` intervals, averaged over devices),
    device seconds and runs per program, the ``top`` ops by time, and the
    idle time between busy intervals split by what the host was doing.

    ``spans`` names host events innermost first (a later name's events
    contain an earlier name's): each gap's time is given to the innermost
    span that covers it, and time no span covers to :data:`HOST_LOOPS`.
    ``idle_gaps`` lists the total per label (``all gaps: <label>``), then
    the longest single gaps under the label that covers most of each."""
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    programs: Dict[str, List[float]] = {}
    for ev in events:
        if is_op(ev):
            per_dev[ev.plane].append((ev.start_ns, ev.end_ns))
            op_time[op_name(ev.name)] += ev.dur_ns * 1e-9
        if is_program(ev):
            p = programs.setdefault(program_name(ev.name), [0.0, 0])
            p[0] += ev.dur_ns * 1e-9
            p[1] += 1
    n_dev = len(per_dev)
    merged = {d: union(iv) for d, iv in per_dev.items()}
    busy = sum(e - s for m in merged.values() for s, e in m)
    # cumulative unions: level k covers spans[0..k]
    levels = []
    acc: List[Tuple[float, float]] = []
    for name in spans:
        acc = acc + [(ev.start_ns, ev.end_ns) for ev in events
                     if ev.name == name and not is_op(ev)
                     and not is_program(ev)]
        m = union(acc)
        levels.append((m, [iv[0] for iv in m]))
    totals: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for m in merged.values():
        for (_, a), (b, _) in zip(m, m[1:]):
            prev, share = 0.0, {}
            for name, (lm, ls) in zip(spans, levels):
                cov = overlap(a, b, lm, ls)
                share[name] = cov - prev
                prev = cov
            share[HOST_LOOPS] = (b - a) - prev
            for label, ns in share.items():
                totals[label] += ns * 1e-9
            gaps.append(((b - a) * 1e-9, max(share, key=share.get)))
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    listed = [[f"all gaps: {label}", sec] for label, sec in sorted(
        totals.items(), key=lambda kv: -kv[1])]
    listed += [[label, sec] for sec, label in gaps]
    return Summary(
        busy_s=busy * 1e-9 / n_dev if n_dev else 0.0,
        n_devices=n_dev,
        programs=programs,
        top_ops=[[name, sec] for name, sec in ops],
        idle_gaps=listed[:top],
        gap_totals=dict(totals))
