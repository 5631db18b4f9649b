"""Arithmetic of the benchmark's metrics: rates, tails and the span ratios.

Pure functions of numbers the harness recorded; nothing here reads a clock
or a device, so the tests check every formula on the CPU.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Iterable, Optional, Sequence, Tuple

#: bytes of one float64 weight read and of one float64 yield written
F64_BYTES = 8


@dataclass(frozen=True)
class Span:
    """One call from the lockstep barrier into the allocator.

    ``t0``/``t1`` are host ``perf_counter`` seconds.  ``padded`` lists the
    (B, N, W) of each device solve the call made; ``nnz_min``/``cols_min``
    count the nonzeros and columns of its OPT=MIN requests."""

    t0: float
    t1: float
    n_requests: int
    padded: Tuple[Tuple[int, int, int], ...] = ()
    nnz_min: int = 0
    cols_min: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def rate(count: int, seconds: float) -> float:
    """Work completed per second over all the time it took."""
    if seconds <= 0:
        raise ValueError(f"a rate needs a positive time, got {seconds!r}")
    return count / seconds


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default): rank ``q/100 * (n - 1)``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


#: the percentiles a tail metric may carry, highest first
TAIL_PERCENTILES = (99, 95, 90, 80, 75, 70, 60, 50)


def tail_percentile(n: int, beyond: int = 10) -> Optional[int]:
    """The highest percentile in :data:`TAIL_PERCENTILES` with at least
    ``beyond`` of ``n`` samples above it; None when even the median has
    fewer."""
    for q in TAIL_PERCENTILES:
        if n * (100 - q) / 100.0 >= beyond:
            return q
    return None


_TAIL_NAME = re.compile(r"_p(\d{2})_")


def percentile_of_name(name: str) -> int:
    """``race_p80_s`` -> 80: a tail metric's name carries its percentile."""
    m = _TAIL_NAME.search(name)
    if m is None:
        raise ValueError(f"metric {name!r} names no percentile (_pNN_)")
    return int(m.group(1))


def in_window(spans: Iterable[Span], t0: float, t1: float) -> list:
    """The spans that lie wholly inside ``[t0, t1]``."""
    return [s for s in spans if s.t0 >= t0 and s.t1 <= t1]


def lanes_per_round(spans: Sequence[Span]) -> Optional[float]:
    """Requests per lockstep round, as a mean over the rounds."""
    if not spans:
        return None
    return sum(s.n_requests for s in spans) / len(spans)


def alloc_share_pct(spans: Sequence[Span], window_s: float) -> Optional[float]:
    """Share of the window the barrier's serving thread spent in the allocator."""
    if not spans or window_s <= 0:
        return None
    return 100.0 * sum(s.seconds for s in spans) / window_s


def alloc_round_ms(spans: Sequence[Span]) -> Optional[float]:
    """Mean milliseconds of one allocator round."""
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)


def pad_fill_pct(spans: Sequence[Span]) -> Optional[float]:
    """The OPT=MIN requests' nonzeros over the padded B*N*W cells that
    carried them; None where no OPT=MIN batch was solved."""
    cells = sum(b * n * w for s in spans for b, n, w in s.padded)
    if cells == 0:
        return None
    return 100.0 * sum(s.nnz_min for s in spans) / cells


def solve_bytes(nnz: int, cols: int) -> int:
    """The bytes an OPT=MIN solve must move, counted from its requests:
    each nonzero's float64 weight in and each column's float64 yield out.
    Padding is not work, so a solve that pads less reads the same bytes."""
    return F64_BYTES * (int(nnz) + int(cols))


def roofline_pct(bytes_moved: float, bytes_per_s: float,
                 kernel_s: float) -> Optional[float]:
    """Least time (bytes over peak bandwidth) over the kernel's time."""
    if kernel_s <= 0 or bytes_moved <= 0:
        return None
    if bytes_per_s <= 0:
        raise ValueError(f"a peak bandwidth must be positive: {bytes_per_s!r}")
    return 100.0 * (bytes_moved / bytes_per_s) / kernel_s
