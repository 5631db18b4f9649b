"""The lockstep path's own spans and round log.

Every round that :class:`~repro.core.alloc_jax.LockstepDispatcher` serves
becomes one row of a process-wide ring (:data:`LOG`): when the serving
thread began to wait at the barrier, when it called the allocator and when
the answers came back, what the round carried, where the allocator's time
went, and how much CPU the lanes spent on the round's requests.

The time inside a round is measured by :func:`span`, a context manager
named ``dfrs.*`` that does two things: it enters
``jax.profiler.TraceAnnotation(name)``, so the span lands on the host plane
of any profiler trace beside the device's ops, and it adds its
``time.perf_counter`` duration to the serving thread's open round.  With no
trace running an annotation costs well under a microsecond; the log is
always on and adds a few microseconds to a round of milliseconds.

The spans, innermost first:

* ``dfrs.fetch`` — waiting for an OPT=MIN solve and copying it back;
* ``dfrs.dispatch`` — moving the padded batch to the device and launching
  the solve;
* ``dfrs.lam`` — the OPT=AVG floor's device call and fetch;
* ``dfrs.lp`` — one host LP of an OPT=AVG request (counts the LPs that
  got the floor because HiGHS did not report them optimal);
* ``dfrs.pad`` — compacting and padding a batch (counts its padded
  B·N·W cells and the nonzeros they carry);
* ``dfrs.allocate`` — the whole allocator call of a round;
* ``dfrs.barrier_wait`` — the serving thread waiting for every live lane's
  request (disjoint from ``dfrs.allocate``).

Each lane's engine counts into a :class:`Meter`: the waiting jobs its
resume scans walked, put through ``greedy_place`` and started, its MCB8
packs, its pauses and migrations.
In a lane's own thread the meter is a :class:`LaneMeter`, which also times
each resume scan and each pack with one pair of ``time.thread_time()``
reads under a ``dfrs.resume_scan`` / ``dfrs.pack`` profiler annotation; the
lane hands what it counted to the barrier with each request, as it hands
its CPU, and the round's row carries the sums.  Elsewhere (the numpy path)
a count is one attribute increment and nothing is timed.

An operator reads :func:`lockstep_totals` (sums since process start) or
:func:`lockstep_rounds` (the rows of a stretch of the host's
``perf_counter`` clock).  Nothing here imports jax until a span is entered:
spans sit only on the device path, which needs jax anyway.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence

__all__ = ["Round", "RoundLog", "LOG", "span", "count", "Meter",
           "LaneMeter", "meter_for", "lockstep_rounds", "lockstep_totals"]

#: rows the ring keeps: several minutes of rounds at 200 rounds/s
CAPACITY = 1 << 16


class Round(NamedTuple):
    """One lockstep round as the serving thread saw it.

    Times are host ``time.perf_counter`` seconds.  The last wait of a pass,
    which ends with every lane finished, is a row with no request and
    ``alloc_t0 == alloc_t1``."""

    wait_t0: float          # the serving thread starts waiting at the barrier
    alloc_t0: float         # the barrier is full: the allocator is called
    alloc_t1: float         # the allocator has answered
    requests: int
    min_requests: int       # of them OPT=MIN
    cells: int              # padded B·N·W cells of the round's batches
    nnz: int                # the nonzeros those batches carry
    pad_s: float
    dispatch_s: float
    fetch_s: float
    lam_s: float
    lp_s: float
    lps: int                # host LPs solved
    lane_cpu_s: float       # Σ of the requests' lane CPU since their last answer
    lp_nonoptimal: int = 0  # of the LPs, those HiGHS did not solve to optimal
    # what the requests' lanes did since their last answer (their Meters)
    scan_cpu_s: float = 0.0  # lane CPU inside resume scans
    scanned: int = 0         # waiting jobs the resume scans walked
    started: int = 0         # of them, started
    placed: int = 0          # of them, put through greedy_place on a copy
    pack_cpu_s: float = 0.0  # lane CPU inside MCB8 packs
    packs: int = 0
    pauses: int = 0
    migrations: int = 0

    @property
    def wait_s(self) -> float:
        return self.alloc_t0 - self.wait_t0

    @property
    def alloc_s(self) -> float:
        return self.alloc_t1 - self.alloc_t0


#: the span whose seconds fill each timed field of a row
_TIMED = {"dfrs.pad": "pad_s", "dfrs.dispatch": "dispatch_s",
          "dfrs.fetch": "fetch_s", "dfrs.lam": "lam_s", "dfrs.lp": "lp_s"}
#: what a lane's engine counts, in the order :meth:`Meter.take` gives it
LANE_FIELDS = ("scan_cpu_s", "scanned", "started", "placed", "pack_cpu_s",
               "packs", "pauses", "migrations")
_SUMMED = ("requests", "min_requests", "cells", "nnz", "pad_s",
           "dispatch_s", "fetch_s", "lam_s", "lp_s", "lps", "lane_cpu_s",
           "lp_nonoptimal") + LANE_FIELDS


class RoundLog:
    """A bounded ring of :class:`Round` rows and running totals."""

    def __init__(self, capacity: int = CAPACITY):
        self._rows: deque = deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = dict.fromkeys(
            ("rounds", "wait_s", "alloc_s") + _SUMMED, 0)

    def append(self, row: Round) -> None:
        with self._lock:
            self._rows.append(row)
            t = self._totals
            t["rounds"] += 1
            t["wait_s"] += row.wait_s
            t["alloc_s"] += row.alloc_s
            for key in _SUMMED:
                t[key] += getattr(row, key)

    def rounds(self, t0: float, t1: float) -> List[Round]:
        """The rows whose allocator call lies in ``[t0, t1]``, judged by its
        midpoint: a caller's own timing around the call lies a few
        microseconds inside this log's, and a midpoint gives every row to
        exactly one of two adjacent stretches."""
        with self._lock:
            rows = list(self._rows)
        return [r for r in rows if t0 <= 0.5 * (r.alloc_t0 + r.alloc_t1) <= t1]

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)


LOG = RoundLog()


def lockstep_rounds(t0: float, t1: float) -> List[Round]:
    """The process's rounds whose allocator call lies in ``[t0, t1]``."""
    return LOG.rounds(t0, t1)


def lockstep_totals() -> Dict[str, float]:
    """Sums over every round since process start: ``rounds``, ``wait_s``,
    ``alloc_s`` and each counter and span of :class:`Round`."""
    return LOG.totals()


# --------------------------------------------------------------------------- #
# spans and counters of the serving thread's open round                       #
# --------------------------------------------------------------------------- #
_LOCAL = threading.local()
_ANNOTATION: List = []


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    if not _ANNOTATION:
        from jax.profiler import TraceAnnotation

        _ANNOTATION.append(TraceAnnotation)
    return _ANNOTATION[0]


class _Open:
    """What the spans and counters of one round have added so far."""

    __slots__ = ("seconds", "calls", "counts")

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        self.counts: Dict[str, int] = {}


class span:
    """Time a stretch of the lockstep path under ``name``: a profiler
    annotation, and seconds added to this thread's open round (if one is
    open).  The span keeps its ``t0``/``t1`` for the caller."""

    __slots__ = ("name", "t0", "t1", "_note")

    def __init__(self, name: str):
        self.name = name
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._note = _annotation()(self.name)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._note.__exit__(*exc)
        acc: Optional[_Open] = getattr(_LOCAL, "round", None)
        if acc is not None:
            acc.seconds[self.name] = (acc.seconds.get(self.name, 0.0)
                                      + self.t1 - self.t0)
            acc.calls[self.name] = acc.calls.get(self.name, 0) + 1
        return False


def count(**counters: int) -> None:
    """Add to this thread's open round's counters (none open: a no-op)."""
    acc: Optional[_Open] = getattr(_LOCAL, "round", None)
    if acc is not None:
        for key, n in counters.items():
            acc.counts[key] = acc.counts.get(key, 0) + int(n)


def open_round() -> _Open:
    """Open a round on this thread: its spans and counters add to it."""
    acc = _Open()
    _LOCAL.round = acc
    return acc


def close_round() -> None:
    _LOCAL.round = None


def record(wait_t0: float, alloc_t0: float, alloc_t1: float, requests: int,
           min_requests: int, lane_cpu_s: float,
           acc: Optional[_Open] = None,
           lanes: Sequence[Sequence[float]] = ()) -> Round:
    """Append one round to :data:`LOG` and return it; ``lanes`` are the
    :meth:`Meter.take` tuples the lanes handed in, summed into the row."""
    acc = acc if acc is not None else _Open()
    row = Round(wait_t0=wait_t0, alloc_t0=alloc_t0, alloc_t1=alloc_t1,
                requests=int(requests), min_requests=int(min_requests),
                cells=acc.counts.get("cells", 0),
                nnz=acc.counts.get("nnz", 0),
                lps=acc.calls.get("dfrs.lp", 0), lane_cpu_s=float(lane_cpu_s),
                lp_nonoptimal=acc.counts.get("lp_nonoptimal", 0),
                **{field: acc.seconds.get(name, 0.0)
                   for name, field in _TIMED.items()},
                **{field: sum(lane[k] for lane in lanes)
                   for k, field in enumerate(LANE_FIELDS)})
    LOG.append(row)
    return row


# --------------------------------------------------------------------------- #
# a lane's engine: its resume scans, packs, pauses and migrations              #
# --------------------------------------------------------------------------- #
_UNTIMED = contextlib.nullcontext()


class Meter:
    """An engine's counts of what its policy did: the waiting jobs its
    resume scans walked (``scanned``), put through ``greedy_place``
    (``placed``) and started, its MCB8 packs, its pauses and migrations.
    This one times nothing: :meth:`clock` is a no-op."""

    __slots__ = LANE_FIELDS

    def __init__(self):
        for field in LANE_FIELDS:
            setattr(self, field, 0)

    def clock(self, name: str, field: str):
        """Time a stretch of the engine's work into ``field`` (a lane
        meter); here a no-op."""
        return _UNTIMED

    def take(self) -> tuple:
        """The counts since the last take, in :data:`LANE_FIELDS` order;
        the meter starts again from zero."""
        out = tuple(getattr(self, field) for field in LANE_FIELDS)
        for field in LANE_FIELDS:
            setattr(self, field, 0)
        return out


class _Clock:
    """One thread-CPU reading pair under a profiler annotation."""

    __slots__ = ("meter", "name", "field", "_note", "_t0")

    def __init__(self, meter: "Meter", name: str, field: str):
        self.meter, self.name, self.field = meter, name, field

    def __enter__(self) -> None:
        self._note = _annotation()(self.name)
        self._note.__enter__()
        self._t0 = time.thread_time()

    def __exit__(self, *exc) -> bool:
        dt = time.thread_time() - self._t0
        setattr(self.meter, self.field, getattr(self.meter, self.field) + dt)
        self._note.__exit__(*exc)
        return False


class LaneMeter(Meter):
    """The meter of a lockstep lane's engine, made in the lane's thread:
    :meth:`clock` adds the thread's CPU seconds of the stretch to a field
    and annotates it for the profiler."""

    __slots__ = ()

    def clock(self, name: str, field: str) -> _Clock:
        return _Clock(self, name, field)


def bind_lane_meter(meter: LaneMeter) -> None:
    """Make ``meter`` the calling thread's lane meter."""
    _LOCAL.lane_meter = meter


def unbind_lane_meter(meter: LaneMeter) -> None:
    """If ``meter`` is the calling thread's lane meter, the thread serves
    no lane any more."""
    if getattr(_LOCAL, "lane_meter", None) is meter:
        _LOCAL.lane_meter = None


def meter_for(backend) -> Meter:
    """The meter an engine with allocation backend ``backend`` counts into:
    the calling thread's lane meter when the thread serves a lockstep lane
    and the engine has a backend, else a meter of the engine's own."""
    lane = getattr(_LOCAL, "lane_meter", None)
    return lane if lane is not None and backend is not None else Meter()
