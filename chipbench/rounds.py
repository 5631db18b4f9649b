"""The program's own round log over a traced window.

The program keeps one row per lockstep round
(``repro.core.alloc_jax.lockstep_rounds``), on the host ``perf_counter``
clock that the harness's spans use.  A reader takes the rows of the
envelope of the window's spans, the first span's start to the last span's
end.  A program without the round log gives no rows, and the readers of
its metrics return None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def envelope(ctx) -> Optional[Tuple[float, float]]:
    """The first span's start and the last span's end; None without spans."""
    if not ctx.spans:
        return None
    return min(s.t0 for s in ctx.spans), max(s.t1 for s in ctx.spans)


def window_rounds(ctx) -> Optional[List]:
    """The program's rounds inside the envelope, None where there are none
    or the program keeps no round log."""
    env = envelope(ctx)
    if env is None:
        return None
    try:
        from repro.core import alloc_jax
    except ImportError:
        return None
    read = getattr(alloc_jax, "lockstep_rounds", None)
    if read is None:
        return None
    return read(*env) or None


def barrier_wait_s(ctx, rows) -> float:
    """Seconds the serving thread waited at the barrier inside the
    envelope (the first round's wait may begin before it)."""
    t0 = envelope(ctx)[0]
    return sum(r.alloc_t0 - max(r.wait_t0, t0) for r in rows)


def mean_ms(rows, field: str, served_by: str) -> Optional[float]:
    """Mean milliseconds of ``field`` over the rounds whose ``served_by``
    count is not zero; None where there are none."""
    hit = [getattr(r, field) for r in rows or () if getattr(r, served_by)]
    return 1e3 * sum(hit) / len(hit) if hit else None
