"""Vectorized allocation kernels over a job×node CSR incidence matrix.

The §4.6 yield allocation and §4.7 stretch passes all reduce to the same
question: *per node, how much CPU do the resident tasks of each job use?*
The pre-vectorization code answered it by rebuilding per-node dict tables
from every job's task mapping on every scheduling event and then running
nested Python loops over them — the profile-dominant cost of a simulation
cell.  This module replaces that with:

* :class:`CSRIncidence` — an immutable node-major CSR snapshot
  (``indptr``/``indices``/``data``) where row = node, column = job index and
  ``data = cpu_need * multiplicity``;
* :class:`NodeIncidence` — the engine-owned *incremental* structure: per-node
  ``{job: multiplicity}`` counts updated on start/pause/migrate/complete,
  with dirty-row tracking so a CSR snapshot costs only the changed rows;
* :func:`maxmin_yields_csr` — §4.6 water-filling as whole-array sparse
  matvecs (per-node frozen use and unfrozen need) with one freeze round per
  pass instead of nested per-item Python loops;
* :func:`lp2_yields` — the OPT=AVG LP (2), handed straight to HiGHS as the
  model and options ``scipy.optimize.linprog(method="highs")`` would build,
  without its Python front end.

Bit-identity contract: every kernel here reproduces the reference
implementations in :mod:`repro.core.alloc_reference` *bit for bit*.  The
row sums use a sequential (left-to-right, column-ascending) CSR matvec —
NOT ``np.sum``/``np.dot``, whose pairwise summation rounds differently —
so each per-node accumulation performs the identical IEEE operation
sequence as the original dict-loop code.  Masked-out terms contribute an
exact ``+ 0.0``, which never changes a finite non-negative partial sum.

:func:`reference_kernels` flips the whole engine (yield_alloc, greedy,
mcb8, stretch_opt) onto the reference implementations; the golden
equivalence tests run every cell both ways and require identical
``SimResult``s.
"""
from __future__ import annotations

import contextlib
from functools import lru_cache
from typing import Iterator, List, Optional, Sequence

import numpy as np

from . import roundlog

__all__ = [
    "CSRIncidence",
    "NodeIncidence",
    "build_csr",
    "maxmin_yields_csr",
    "avg_yields_csr",
    "lp2_yields",
    "reference_kernels",
    "reference_kernels_active",
]

_EPS = 1e-12

_EMPTY_I = np.zeros(0, dtype=np.int64)
_EMPTY_F = np.zeros(0, dtype=np.float64)

# --------------------------------------------------------------------------- #
# reference-mode switch                                                        #
# --------------------------------------------------------------------------- #
_REFERENCE = False


def reference_kernels_active() -> bool:
    """True while the engine is forced onto the pre-vectorization oracle."""
    return _REFERENCE


@contextlib.contextmanager
def reference_kernels() -> Iterator[None]:
    """Run everything under the :mod:`repro.core.alloc_reference` oracle.

    Used by the golden equivalence tests: a simulation executed inside this
    context takes the original dict/loop allocation paths end to end, so its
    ``SimResult`` is the ground truth the vectorized hot path must match
    bit for bit.
    """
    global _REFERENCE
    prev = _REFERENCE
    _REFERENCE = True
    try:
        yield
    finally:
        _REFERENCE = prev


# --------------------------------------------------------------------------- #
# sequential CSR matvec (bitwise-equal to the reference Python accumulation)   #
# --------------------------------------------------------------------------- #
try:  # scipy's C kernel accumulates strictly left to right — exactly what
    # the dict-loop reference does.  Private but stable; guarded fallback.
    from scipy.sparse import _sparsetools as _sptools

    def _seq_matvec(indptr, indices, data, x, out):
        out[:] = 0.0
        _sptools.csr_matvec(indptr.shape[0] - 1, x.shape[0],
                            indptr, indices, data, x, out)
        return out
except Exception:  # pragma: no cover - depends on scipy version
    def _seq_matvec(indptr, indices, data, x, out):
        out[:] = 0.0
        np.add.at(out, np.repeat(np.arange(indptr.shape[0] - 1),
                                 np.diff(indptr)), data * x[indices])
        return out


class CSRIncidence:
    """Immutable node-major CSR snapshot of the job×node incidence.

    ``data[k]`` is ``cpu_need[j] * multiplicity`` for job ``j = indices[k]``
    on the row's node; columns are ascending within each row, which fixes
    the accumulation order of every kernel to the reference order.
    """

    __slots__ = ("n_nodes", "width", "indptr", "indices", "data")

    def __init__(self, n_nodes: int, width: int,
                 indptr: np.ndarray, indices: np.ndarray, data: np.ndarray):
        self.n_nodes = n_nodes
        self.width = width          # number of job columns (dense job space)
        self.indptr = indptr
        self.indices = indices
        self.data = data

    def matvec(self, x: np.ndarray, out: Optional[np.ndarray] = None) -> np.ndarray:
        """Per-node sequential row sums of ``data * x[indices]``."""
        if out is None:
            out = np.empty(self.n_nodes)
        return _seq_matvec(self.indptr, self.indices, self.data, x, out)

    def row_jobs(self, node: int) -> np.ndarray:
        """Job columns resident on ``node`` (ascending)."""
        return self.indices[self.indptr[node]:self.indptr[node + 1]]


def build_csr(cpu_need: Sequence[float],
              mappings: Sequence[Sequence[int]],
              n_nodes: int) -> CSRIncidence:
    """From-scratch CSR for the public (specs, mappings) API: column ``j`` is
    position ``j`` in ``mappings``; rows hold ascending columns, mirroring the
    sorted per-node tables of the reference implementation."""
    per_node: List[dict] = [dict() for _ in range(n_nodes)]
    for ji, mapping in enumerate(mappings):
        for node in mapping:
            per_node[node][ji] = per_node[node].get(ji, 0) + 1
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    idx_rows: List[np.ndarray] = []
    dat_rows: List[np.ndarray] = []
    cpu = np.asarray(cpu_need, dtype=np.float64)
    for node, d in enumerate(per_node):
        if d:
            items = sorted(d.items())
            ji = np.array([i for i, _ in items], dtype=np.int64)
            mult = np.array([m for _, m in items], dtype=np.float64)
            idx_rows.append(ji)
            dat_rows.append(cpu[ji] * mult)
        else:
            idx_rows.append(_EMPTY_I)
            dat_rows.append(_EMPTY_F)
        indptr[node + 1] = indptr[node] + idx_rows[-1].shape[0]
    indices = np.concatenate(idx_rows) if idx_rows else _EMPTY_I
    data = np.concatenate(dat_rows) if dat_rows else _EMPTY_F
    return CSRIncidence(n_nodes, len(mappings), indptr, indices, data)


class NodeIncidence:
    """Incrementally maintained job×node incidence.

    The engine calls :meth:`place` / :meth:`remove` on every
    start/pause/migrate/complete transition (mirroring its ``NodePool``
    bookkeeping), so at any scheduling event the CSR snapshot of the
    *currently running* tasks is available without rescanning any mapping.
    Rows are rebuilt lazily and only when dirty; the concatenated snapshot
    is cached until the next structural change.
    """

    def __init__(self, n_nodes: int, cpu_need: np.ndarray):
        self.n_nodes = int(n_nodes)
        # owned geometric buffer; cpu_need is the width-sized head view
        self._cpu_buf = np.array(cpu_need, dtype=np.float64)
        self._width = int(self._cpu_buf.shape[0])
        self.cpu_need = self._cpu_buf[: self._width]
        self.rows: List[dict] = [dict() for _ in range(self.n_nodes)]
        self._row_idx: List[np.ndarray] = [_EMPTY_I] * self.n_nodes
        self._row_dat: List[np.ndarray] = [_EMPTY_F] * self.n_nodes
        self._dirty: set = set()
        self._snap: Optional[CSRIncidence] = None

    def place(self, job: int, mapping: Sequence[int]) -> None:
        rows = self.rows
        for node in mapping:
            r = rows[node]
            r[job] = r.get(job, 0) + 1
        self._dirty.update(mapping)
        self._snap = None

    def remove(self, job: int, mapping: Sequence[int]) -> None:
        rows = self.rows
        for node in mapping:
            r = rows[node]
            m = r[job] - 1
            if m:
                r[job] = m
            else:
                del r[job]
        self._dirty.update(mapping)
        self._snap = None

    def extend(self, cpu_need_tail: np.ndarray) -> None:
        """Grow the job-column space (streaming sessions append jobs).

        Existing rows keep their cached arrays — old column data is
        untouched — but the cached CSR snapshot is invalidated because the
        matrix ``width`` (dense job count) changes.  Appends land in a
        geometrically doubled buffer (amortized O(1) per job).
        """
        tail = np.asarray(cpu_need_tail, dtype=np.float64)
        need = self._width + int(tail.shape[0])
        if need > self._cpu_buf.shape[0]:
            buf = np.empty(max(need, 2 * self._cpu_buf.shape[0], 16))
            buf[: self._width] = self._cpu_buf[: self._width]
            self._cpu_buf = buf
        self._cpu_buf[self._width:need] = tail
        self._width = need
        self.cpu_need = self._cpu_buf[:need]
        self._snap = None

    def compact(self, keep: np.ndarray, new_of_old: np.ndarray) -> None:
        """Drop evicted job columns (``EngineState.compact``).

        ``keep`` — ascending surviving dense indices; ``new_of_old`` — the
        old→new column map.  Every resident task belongs to a RUNNING job,
        so all occupied columns survive; the remap is monotone, which keeps
        each row's ``sorted(d.items())`` order — and therefore the CSR data
        order every kernel accumulates in — exactly what a from-scratch
        build over the compacted state would produce.
        """
        m = int(keep.shape[0])
        self._cpu_buf[:m] = self._cpu_buf[: self._width][keep]
        self._width = m
        self.cpu_need = self._cpu_buf[:m]
        for node, d in enumerate(self.rows):
            if d:
                self.rows[node] = {
                    int(new_of_old[j]): mult for j, mult in d.items()}
                self._dirty.add(node)
        self._snap = None

    def csr(self) -> CSRIncidence:
        if self._snap is not None:
            return self._snap
        cpu = self.cpu_need
        for node in self._dirty:
            d = self.rows[node]
            if d:
                items = sorted(d.items())
                ji = np.array([i for i, _ in items], dtype=np.int64)
                mult = np.array([m for _, m in items], dtype=np.float64)
                self._row_idx[node] = ji
                self._row_dat[node] = cpu[ji] * mult
            else:
                self._row_idx[node] = _EMPTY_I
                self._row_dat[node] = _EMPTY_F
        self._dirty.clear()
        indptr = np.zeros(self.n_nodes + 1, dtype=np.int64)
        np.cumsum([r.shape[0] for r in self._row_idx], out=indptr[1:])
        indices = np.concatenate(self._row_idx) if self.n_nodes else _EMPTY_I
        data = np.concatenate(self._row_dat) if self.n_nodes else _EMPTY_F
        self._snap = CSRIncidence(self.n_nodes, self.cpu_need.shape[0],
                                  indptr, indices, data)
        return self._snap


# --------------------------------------------------------------------------- #
# §4.6 kernels                                                                 #
# --------------------------------------------------------------------------- #
def maxmin_yields_csr(inc: CSRIncidence, active: np.ndarray) -> np.ndarray:
    """OPT=MIN water-filling over the incidence matrix.

    ``active`` flags the job columns that participate (the running set);
    inactive columns must have no incidence entries.  Returns the full-width
    yield vector (zeros at inactive columns).  Each freeze round is two
    sequential matvecs (frozen use, unfrozen need) plus an O(n_nodes) scan —
    the per-item Python loops of the reference are gone, the float operation
    sequence per node is unchanged.
    """
    w = inc.width
    y = np.zeros(w)
    n_active = int(active.sum())
    if n_active == 0:
        return y
    frozen = ~active
    indptr, indices = inc.indptr, inc.indices
    f_use = np.empty(inc.n_nodes)
    u_need = np.empty(inc.n_nodes)
    for _ in range(n_active + 1):
        if frozen.all():
            break
        inc.matvec(np.where(frozen, y, 0.0), out=f_use)
        inc.matvec((~frozen).astype(np.float64), out=u_need)
        valid = np.nonzero(u_need > _EPS)[0]
        levels = np.maximum(0.0, 1.0 - f_use[valid]) / u_need[valid]
        # Sequential bottleneck scan in node order: replicates the reference's
        # tolerance-updated running minimum (order-dependent when two levels
        # sit within 1e-15 of each other, so it cannot be a plain argmin).
        best_level = 1.0
        binding: List[int] = []
        for node, level in zip(valid.tolist(), levels.tolist()):
            if level < best_level - 1e-15:
                best_level = level
                binding = [node]
            elif abs(level - best_level) <= 1e-15:
                binding.append(node)
        newly = np.zeros(w, dtype=bool)
        if best_level >= 1.0 - 1e-12:
            best_level = 1.0
            newly |= ~frozen  # everyone capped
        else:
            for node in binding:
                sl = indices[indptr[node]:indptr[node + 1]]
                newly[sl[~frozen[sl]]] = True
        y[~frozen] = best_level
        if not newly.any():          # numerical safety
            newly |= ~frozen
        frozen |= newly
    return np.clip(y, 0.0, 1.0)


def avg_yields_csr(inc: CSRIncidence, cols: np.ndarray) -> np.ndarray:
    """OPT=AVG over the incidence matrix: LP (2) with the constraint matrix
    sliced straight out of the CSR snapshot (no lil_matrix rebuild).

    ``cols`` — sorted job columns participating (the running set).  Returns
    yields aligned with ``cols``.
    """
    if cols.shape[0] == 0:
        return np.zeros(0)
    load_need = inc.matvec(np.ones(inc.width))
    lam = float(load_need.max()) if inc.n_nodes else 0.0
    return lp2_yields(inc, cols, 1.0 / max(1.0, lam))


@lru_cache(maxsize=None)
def _highs():
    """scipy's HiGHS core and the options ``linprog(method="highs")`` sets,
    imported and built on the first LP: importing ``scipy.optimize`` adds
    about half a second to the program's start (x86 CPU host), which a run
    without OPT=AVG need not pay."""
    # The HiGHS core that scipy bundles and that linprog itself calls.
    # The module is private to scipy; tests/test_alloc_lp.py pins every
    # answer of lp2_yields bit for bit to linprog's.
    from scipy.optimize._highspy import _core as highs

    opts = highs.HighsOptions()
    opts.presolve = "on"
    opts.simplex_strategy = int(
        highs.simplex_constants.SimplexStrategy.kSimplexStrategyDual)
    opts.highs_debug_level = int(highs.HighsDebugLevel.kHighsDebugLevelNone)
    opts.output_flag = False
    opts.log_to_console = False
    return highs, opts


def lp2_yields(inc: CSRIncidence, cols: np.ndarray, y_min: float) -> np.ndarray:
    """LP (2) with the floor given: maximize the sum of the yields of
    ``cols`` subject to every node's load ``<= 1`` and ``y_min <= y <= 1``.

    One HiGHS solve of the very model ``linprog(method="highs")`` builds —
    the constraint matrix in canonical CSC, rows bounded by ``(-inf, 1]`` —
    under the same options, on a fresh solver, so no basis carries from one
    LP to the next.  A solve HiGHS does not report optimal gets the floor
    and counts in the round's ``lp_nonoptimal``.  Returns yields aligned
    with ``cols``.
    """
    highs, opts = _highs()
    m = int(cols.shape[0])
    n = inc.n_nodes
    # CSR -> CSC: a stable sort by column keeps each column's rows ascending
    pos = np.searchsorted(cols, inc.indices)
    order = np.argsort(pos, kind="stable")
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(inc.indptr))
    start = np.zeros(m + 1, dtype=np.int32)
    np.cumsum(np.bincount(pos, minlength=m), out=start[1:])

    lp = highs.HighsLp()
    lp.num_col_ = m
    lp.num_row_ = n
    lp.col_cost_ = np.full(m, -1.0)
    lp.col_lower_ = np.full(m, y_min)
    lp.col_upper_ = np.ones(m)
    lp.row_lower_ = np.full(n, -highs.kHighsInf)
    lp.row_upper_ = np.ones(n)
    a = lp.a_matrix_
    a.num_col_ = m
    a.num_row_ = n
    a.format_ = highs.MatrixFormat.kColwise
    a.start_ = start
    a.index_ = rows[order]
    a.value_ = inc.data[order]

    solver = highs._Highs()
    error = highs.HighsStatus.kError
    if (solver.passOptions(opts) == error
            or solver.passModel(lp) == error or solver.run() == error
            or solver.getModelStatus() != highs.HighsModelStatus.kOptimal):
        roundlog.count(lp_nonoptimal=1)
        return np.full(m, y_min)
    return np.clip(np.array(solver.getSolution().col_value), 0.0, 1.0)
