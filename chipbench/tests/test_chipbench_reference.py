"""The plain reference agrees with the program's numpy kernels."""
import numpy as np
import pytest

from chipbench import reference
from repro.core.alloc_kernels import avg_yields_csr, build_csr, maxmin_yields_csr


def _instance(rng, n_nodes=32, width=24):
    running = rng.random(width) < 0.8
    running[0] = True
    cpu = rng.choice([0.1, 0.25, 0.3, 0.5, 0.7, 1.0], width)
    maps = [list(rng.integers(0, n_nodes, int(rng.integers(1, 6))))
            if running[j] else [] for j in range(width)]
    return build_csr(cpu, maps, n_nodes), np.nonzero(running)[0]


def _solve(inc, cols, opt, dtype=np.float64):
    return reference.solve(inc.indptr, inc.indices, inc.data, inc.n_nodes,
                           cols, opt, dtype=dtype)


@pytest.mark.parametrize("seed", range(6))
def test_maxmin_matches_program(seed):
    inc, cols = _instance(np.random.default_rng(seed))
    active = np.zeros(inc.width, bool)
    active[cols] = True
    want = maxmin_yields_csr(inc, active)[cols]
    np.testing.assert_allclose(_solve(inc, cols, "MIN"), want, rtol=0,
                               atol=1e-14)


@pytest.mark.parametrize("seed", range(3))
def test_avg_matches_program(seed):
    inc, cols = _instance(np.random.default_rng(100 + seed))
    np.testing.assert_allclose(_solve(inc, cols, "AVG"),
                               avg_yields_csr(inc, cols), rtol=0, atol=1e-12)


def test_maxmin_is_fair_and_feasible():
    inc, cols = _instance(np.random.default_rng(7), n_nodes=8, width=30)
    a = reference.dense(inc.indptr, inc.indices, inc.data, inc.n_nodes, cols)
    y = reference.maxmin(a)
    assert np.all(a @ y <= 1 + 1e-12) and np.all((y > 0) & (y <= 1))


def test_float32_control_differs():
    inc, cols = _instance(np.random.default_rng(3), n_nodes=8, width=30)
    y64 = _solve(inc, cols, "MIN")
    y32 = _solve(inc, cols, "MIN", dtype=np.float32).astype(np.float64)
    assert 1e-9 < np.max(np.abs(y64 - y32)) < 1e-5


def test_unknown_opt():
    inc, cols = _instance(np.random.default_rng(1))
    with pytest.raises(ValueError):
        _solve(inc, cols, "MAX")
