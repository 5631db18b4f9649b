"""The plain reference of the allocation each lane asks for (paper §4.6).

Independent of the program: it imports nothing of it and takes only the
question a lane put to the allocator, a node-major incidence in CSR form
(``indptr``, ``indices``, ``data`` with ``data = cpu_need * multiplicity``)
and the running columns ``cols``, and answers it from first principles:

* OPT=MIN is max-min fairness by water-filling: raise the yield of every
  unfrozen job together until some node is full, freeze the jobs on the
  bottleneck nodes, repeat; a yield never exceeds 1;
* OPT=AVG maximizes the sum of yields (LP (2)) subject to every node's
  capacity, with every yield at least ``1 / max(1, L)``, where ``L`` is the
  largest node load at full yield.

``dtype`` sets the arithmetic: float64 is what the configuration states,
float32 is the benchmark's control (the precision one step below).
"""
from __future__ import annotations

import numpy as np

_EPS = 1e-12       # a node with less unfrozen need than this binds nothing
_TIE = 1e-15       # bottleneck levels this close bind together
_CAP = 1e-12       # a level this close to 1 caps every open job at 1


def dense(indptr, indices, data, n_nodes, cols, dtype=np.float64):
    """The (n_nodes, len(cols)) load matrix of the running columns."""
    a = np.zeros((int(n_nodes), len(cols)), dtype=dtype)
    rows = np.repeat(np.arange(int(n_nodes)), np.diff(indptr))
    pos = np.searchsorted(cols, indices)
    a[rows, pos] = np.asarray(data, dtype=dtype)
    return a


def maxmin(a: np.ndarray) -> np.ndarray:
    """Max-min fair yields of the jobs (columns) of load matrix ``a``."""
    dt = a.dtype.type
    m = a.shape[1]
    y = np.zeros(m, dtype=a.dtype)
    frozen = np.zeros(m, dtype=bool)
    for _ in range(m + 1):
        if frozen.all():
            break
        f_use = a @ np.where(frozen, y, dt(0))
        u_need = a @ (~frozen).astype(a.dtype)
        valid = u_need > _EPS
        levels = np.maximum(dt(0), dt(1) - f_use[valid]) / u_need[valid]
        best = min(dt(1), levels.min()) if valid.any() else dt(1)
        if best >= 1.0 - _CAP:
            best = dt(1)
            newly = ~frozen
        else:
            binding = np.nonzero(valid)[0][np.abs(levels - best) <= _TIE]
            newly = ~frozen & (a[binding] > 0).any(axis=0)
        y[~frozen] = best
        if not newly.any():
            newly = ~frozen
        frozen |= newly
    return np.clip(y, 0.0, 1.0)


def avg(a: np.ndarray) -> np.ndarray:
    """Sum-of-yields optimal yields of load matrix ``a`` above the floor."""
    from scipy.optimize import linprog

    m = a.shape[1]
    load = a.sum(axis=1, dtype=a.dtype)
    floor = a.dtype.type(1) / max(a.dtype.type(1), load.max())
    res = linprog(c=-np.ones(m), A_ub=a.astype(np.float64),
                  b_ub=np.ones(a.shape[0]),
                  bounds=[(float(floor), 1.0)] * m, method="highs")
    if not res.success:
        return np.full(m, floor, dtype=a.dtype)
    return np.clip(res.x, 0.0, 1.0).astype(a.dtype)


def solve(indptr, indices, data, n_nodes, cols, opt, dtype=np.float64):
    """The yields of ``cols``, in order, for one OPT=MIN or OPT=AVG request."""
    if len(cols) == 0:
        return np.zeros(0, dtype=dtype)
    a = dense(indptr, indices, data, n_nodes, cols, dtype)
    if opt == "MIN":
        return maxmin(a)
    if opt == "AVG":
        return avg(a)
    raise ValueError(f"unknown OPT {opt!r}")
