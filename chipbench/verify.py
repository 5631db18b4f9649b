"""What decides ``correct``: the window's answers against the references.

Each comparison is a number held against a limit from the cell's traffic
file (``PERF.md`` gives the readings each limit was set from):

* ``min_yield_gap`` / ``avg_yield_gap`` — the widest gap between a yield a
  lane received and the plain reference's yield for the same request
  (:mod:`chipbench.reference`), over a sample of the window's requests
  drawn from the seed, the widest request among them.  This covers the
  device solve and the lockstep barrier: a lane handed another lane's
  answer, a stale one or an altered one reads wrong here;
* ``record_ref_gap`` — each sampled cell's outcome record against the plain
  DFRS simulator (:mod:`chipbench.dfrs_reference`) on the same trace and
  policy, as the widest relative gap over the fields the simulator
  computes; for a race decision the same over its winner, every rung's
  variants and scores and the final rung's records, against the
  simulator's own race from its own run to the same fork;
* ``record_rel_gap`` — a second witness: the same records against the
  program's serial numpy path, over every field that is not a wall time
  (metrics, counts, labels, fingerprints).  It shares the program's engine,
  so it catches what the device lane changes and nothing the two paths
  share.

A label or flag that differs reads infinite.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import reference

#: record keys that are wall-clock measurements or the backend's label
NOT_OUTCOMES = ("sim_wall_s", "wall_s", "backend")


class Capture:
    """A lane's requests and answers, kept one in ``stride`` (offset drawn
    from the seed) plus the widest the lane asked."""

    __slots__ = ("stride", "offset", "count", "kept", "widest")

    def __init__(self, stride: int, offset: int):
        self.stride, self.offset = int(stride), int(offset)
        self.count = 0
        self.kept: List[Tuple] = []
        self.widest = None

    def add(self, inc, cols, opt, y) -> None:
        item = (inc.n_nodes, inc.indptr, inc.indices, inc.data,
                np.array(cols), opt, y)
        if self.count % self.stride == self.offset:
            self.kept.append(item)
        if self.widest is None or len(cols) > len(self.widest[4]):
            self.widest = item
        self.count += 1


def sample_requests(captures: Sequence[Capture], n: int,
                    rng: np.random.Generator) -> List[Tuple]:
    """``n`` kept requests drawn by ``rng``, and the widest of the window."""
    pool = [item for c in captures for item in c.kept]
    pick = [pool[i] for i in sorted(rng.choice(
        len(pool), size=min(n, len(pool)), replace=False))] if pool else []
    widest = [c.widest for c in captures if c.widest is not None]
    if widest:
        pick.append(max(widest, key=lambda item: len(item[4])))
    return pick


def yield_gaps(requests: Sequence[Tuple], dtype=np.float64
               ) -> Dict[str, float]:
    """Widest |served - reference| yield gap per OPT over ``requests``."""
    gaps = {"MIN": 0.0, "AVG": 0.0}
    counts = {"MIN": 0, "AVG": 0}
    for n_nodes, indptr, indices, data, cols, opt, y in requests:
        ref = reference.solve(indptr, indices, data, n_nodes, cols, opt,
                              dtype=dtype)
        got = np.asarray(y, dtype=np.float64)
        counts[opt] += 1
        if got.shape != ref.shape:
            gaps[opt] = math.inf
            continue
        gaps[opt] = max(gaps[opt], float(np.max(
            np.abs(got - ref.astype(np.float64)), initial=0.0)))
    out = {}
    for opt, key in (("MIN", "min_yield_gap"), ("AVG", "avg_yield_gap")):
        if counts[opt]:
            out[key] = gaps[opt]
    return out


def _rel(a, b) -> float:
    """Relative gap of two outcome values: 0 when equal, the relative
    difference of two numbers, infinite for anything else that differs."""
    if a == b:
        return 0.0
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        if math.isnan(a) or math.isnan(b):
            return 0.0 if math.isnan(a) and math.isnan(b) else math.inf
        return abs(a - b) / max(abs(b), 1e-300)
    return math.inf


def record_gap(got: Sequence[dict], ref: Sequence[dict]) -> float:
    """The widest relative gap over every outcome field of paired records
    (a missing record, or a label, flag or fingerprint that differs, reads
    infinite)."""
    if len(got) != len(ref):
        return math.inf
    worst = 0.0
    for g, r in zip(got, ref):
        for k in (set(g) | set(r)) - set(NOT_OUTCOMES):
            worst = max(worst, _rel(g.get(k), r.get(k)))
    return worst


def outcome_gap(got: Sequence[dict], ref: Sequence[dict]) -> float:
    """The widest relative gap over every field of the reference records
    (a field or a record the program lacks reads infinite)."""
    if len(got) != len(ref):
        return math.inf
    worst = 0.0
    for g, r in zip(got, ref):
        for k, v in r.items():
            worst = max(worst, _rel(g.get(k, math.nan), v))
    return worst


def race_ref_gap(got: Sequence, ref: Sequence[dict]) -> float:
    """The widest relative gap between the program's race results and the
    simulator's: winner, every rung's variants and scores, and the final
    rung's records."""
    if len(got) != len(ref):
        return math.inf
    worst = 0.0
    for g, r in zip(got, ref):
        worst = max(worst, _rel(g.winner.label, r["winner"]),
                    _rel(len(g.rungs), len(r["rungs"])),
                    outcome_gap(g.records, r["records"]))
        for gr, rr in zip(g.rungs, r["rungs"]):
            worst = max(worst, _rel(gr["variants"], rr["variants"]),
                        _rel(len(gr["scores"]), len(rr["scores"])))
            for a, b in zip(gr["scores"], rr["scores"]):
                worst = max(worst, _rel(float(a), float(b)))
    return worst


def race_gap(got: Sequence, ref: Sequence) -> float:
    """The widest relative gap between paired race results: every rung's
    variants and scores, the winner, and the final rung's branch records."""
    if len(got) != len(ref):
        return math.inf
    worst = 0.0
    for g, r in zip(got, ref):
        worst = max(worst, _rel(g.winner.label, r.winner.label),
                    _rel(len(g.rungs), len(r.rungs)),
                    record_gap(g.records, r.records))
        for gr, rr in zip(g.rungs, r.rungs):
            worst = max(worst, _rel(gr["variants"], rr["variants"]))
            for a, b in zip(gr["scores"], rr["scores"]):
                worst = max(worst, _rel(float(a), float(b)))
    return worst


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Hold every number against its limit.  A number without a limit, or a
    limit without a number, fails: nothing passes by being left out."""
    checks, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value = numbers.get(name, math.inf)
        limit = limits.get(name, -math.inf)
        checks[name] = {"value": value, "limit": limit}
        ok &= value <= limit
    return ok, checks
