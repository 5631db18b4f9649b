"""OPT=AVG's LP (2) through the direct HiGHS call, pinned to ``linprog``.

``alloc_kernels.lp2_yields`` hands HiGHS the model and options that
``scipy.optimize.linprog(method="highs")`` builds, without linprog's Python
front end.  Every case here solves the same LPs both ways and requires
*bitwise* equal yields (``np.array_equal``, not allclose): the recorded LPs
of a whole Lublin cell, seeded random incidences, and degenerate models
whose optimum is not a unique vertex.  A model HiGHS cannot solve to
optimal gets the floor, as ``linprog``'s failure does, and is counted in
the round log's ``lp_nonoptimal``.
"""
import numpy as np
import pytest
from scipy.optimize import linprog
from scipy.sparse import csr_matrix

from repro import api
from repro.core import alloc_kernels, roundlog
from repro.core.alloc_kernels import CSRIncidence, build_csr, lp2_yields
from repro.workloads.registry import WorkloadSpec


def _scipy_csr(inc, cols):
    """The constraint matrix restricted to ``cols``, as linprog was given it
    (it equals the reference's lil-built matrix)."""
    pos = np.searchsorted(cols, inc.indices)
    return csr_matrix((inc.data, pos, inc.indptr),
                      shape=(inc.n_nodes, cols.shape[0]))


def _linprog_yields(inc, cols, y_min):
    """LP (2) the way the program solved it before: linprog, then the clip
    or the floor."""
    m = cols.shape[0]
    res = linprog(c=-np.ones(m), A_ub=_scipy_csr(inc, cols),
                  b_ub=np.ones(inc.n_nodes), bounds=[(y_min, 1.0)] * m,
                  method="highs")
    if not res.success:
        return np.full(m, y_min)
    return np.clip(res.x, 0.0, 1.0)


def _floor(inc):
    lam = float(inc.matvec(np.ones(inc.width)).max()) if inc.n_nodes else 0.0
    return 1.0 / max(1.0, lam)


def _lp(cpu_need, mappings, n_nodes, y_min=None):
    inc = build_csr(cpu_need, mappings, n_nodes)
    cols = np.arange(len(mappings), dtype=np.int64)
    return inc, cols, _floor(inc) if y_min is None else y_min


# --------------------------------------------------------------------------- #
# the cases                                                                    #
# --------------------------------------------------------------------------- #
def _lublin_cell():
    """Every LP of a numpy ``GreedyP */OPT=AVG`` cell, as the engine asked."""
    seen = []
    inner = alloc_kernels.lp2_yields

    def recorded(inc, cols, y_min):
        seen.append((inc, cols.copy(), y_min))
        return inner(inc, cols, y_min)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(alloc_kernels, "lp2_yields", recorded)
        api.simulate(WorkloadSpec("lublin", n_jobs=100, n_nodes=32, seed=0),
                     "GreedyP */OPT=AVG")
    assert len(seen) > 50
    return seen


def _random(m, n_nodes, seed, lps=4):
    """Seeded incidences of ``m`` running jobs on ``n_nodes`` nodes, some in
    a wider job space whose other columns are not running."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(lps):
        cpu = rng.choice([0.25, 0.37, 0.5, 0.75, 1.0], size=m)
        maps = [list(rng.integers(0, n_nodes, size=rng.integers(1, 9)))
                for _ in range(m)]
        inc, cols, y_min = _lp(cpu, maps, n_nodes)
        if k % 2:                       # running columns of a wider space
            width = 2 * m + 1
            cols = np.sort(rng.choice(width, size=m, replace=False))
            wide = [[] for _ in range(width)]
            wcpu = np.zeros(width)
            for j, c in enumerate(cols):
                wide[c], wcpu[c] = maps[j], cpu[j]
            inc = build_csr(wcpu, wide, n_nodes)
        out.append((inc, cols, y_min))
    return out


def _ties():
    # equal needs, two or three jobs to a node: many optimal vertices
    return [_lp(np.full(16, 0.5), [[j % 8] for j in range(16)], 8),
            _lp(np.full(24, 0.5), [[j % 8, (j + 1) % 8] for j in range(24)], 8)]


def _spanning():
    # one job on every node, beside single-node jobs
    maps = [list(range(12))] + [[j % 12] for j in range(20)]
    return [_lp(np.full(21, 0.37), maps, 12),
            _lp(np.r_[1.0, np.full(20, 0.25)], maps, 12)]


def _single():
    return [_lp([0.25], [[0]], 1), _lp([0.5], [[0, 0, 0]], 4),
            _lp([1.0], [[0, 1, 2]], 8)]


def _floor_one():
    # every node within capacity: the floor is 1 and so is every yield
    return [_lp([0.25, 0.5], [[0], [0, 1]], 4, y_min=1.0),
            _lp(np.full(8, 0.125), [[0]] * 8, 2, y_min=1.0)]


def _overloaded():
    # one node carries 2.5 units: the floor 1/2.5 binds its jobs
    maps = [[0]] * 5 + [[1], [2], [1, 2]]
    return [_lp(np.r_[np.full(5, 0.5), 0.25, 0.5, 0.25], maps, 4),
            _lp(np.full(6, 1.0), [[0, 0], [0], [0, 1], [1], [2], [3]], 4)]


CASES = {
    "lublin-32x100": _lublin_cell,
    **{f"random-m{m}-n{n}": (lambda m=m, n=n: _random(m, n, seed=m * 1000 + n))
       for m, n in [(1, 8), (2, 8), (3, 128), (6, 16), (8, 32), (16, 8),
                    (24, 64), (32, 128), (48, 16), (64, 128)]},
    "ties": _ties,
    "spanning-job": _spanning,
    "m1": _single,
    "floor-one": _floor_one,
    "overloaded-node": _overloaded,
}


@pytest.mark.parametrize("case", list(CASES))
def test_lp2_yields_bit_equal_to_linprog(case):
    lps = CASES[case]()
    for inc, cols, y_min in lps:
        want = _linprog_yields(inc, cols, y_min)
        got = lp2_yields(inc, cols, y_min)
        assert np.array_equal(got, want), (case, y_min, got, want)
    if case == "overloaded-node":
        assert any(np.min(lp2_yields(*lp)) == lp[2] < 1.0 for lp in lps)


def test_nonoptimal_lp_gets_the_floor_and_is_counted(monkeypatch):
    monkeypatch.setattr(roundlog, "LOG", roundlog.RoundLog(capacity=4))
    # a floor of 1 on a node loaded to 1.5 leaves no feasible point
    inc = CSRIncidence(1, 2, np.array([0, 2]), np.array([0, 1]),
                       np.array([0.75, 0.75]))
    cols = np.arange(2)
    assert not linprog(c=-np.ones(2), A_ub=_scipy_csr(inc, cols), b_ub=[1.0],
                       bounds=[(1.0, 1.0)] * 2, method="highs").success
    acc = roundlog.open_round()
    try:
        got = lp2_yields(inc, cols, 1.0)
    finally:
        roundlog.close_round()
    assert np.array_equal(got, np.ones(2))
    assert np.array_equal(got, _linprog_yields(inc, cols, 1.0))
    assert acc.counts == {"lp_nonoptimal": 1}
    roundlog.record(0.0, 0.0, 0.0, 1, 0, 0.0, acc)
    assert roundlog.lockstep_totals()["lp_nonoptimal"] == 1
