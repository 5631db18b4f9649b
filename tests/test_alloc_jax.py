"""Batched JAX allocation backend vs the numpy kernels: bit-identity.

The contract mirrors the one ``alloc_kernels`` holds against
``alloc_reference``: under x64, every per-lane result of the batched
water-filling is bit-equal to ``maxmin_yields_csr`` on that lane's CSR
alone — padding (extra rows, columns, lanes) must never leak into a real
cell, and the lockstep batched sweep must reproduce the numpy sweep's
records exactly.  The last test is the acceptance grid: 100 seeded cells
through one jitted lockstep sweep.
"""
import dataclasses

import numpy as np
import pytest

pytest.importorskip("jax", reason="the batched backend needs jax "
                    "(pip install -r requirements-dev.txt)")

from repro.core import alloc_jax
from repro.core.alloc_kernels import (CSRIncidence, avg_yields_csr, build_csr,
                                      maxmin_yields_csr)
from repro.sched.engine import Engine, SimParams
from repro.sched.sweep import grid, run_batched, run_grid
from repro.workloads.registry import WorkloadSpec, make_trace_ir

from conftest import result_dict

pytestmark = pytest.mark.skipif(not alloc_jax.has_jax(),
                                reason="jax present but not importable")


# --------------------------------------------------------------------------- #
# fixtures                                                                     #
# --------------------------------------------------------------------------- #
def random_instance(rng, max_width=30, max_nodes=12):
    """A random incidence: varied width, zero-need jobs, dead nodes,
    multiplicities > 1, possibly empty running set."""
    W = int(rng.integers(1, max_width + 1))
    N = int(rng.integers(1, max_nodes + 1))
    run = np.sort(rng.choice(W, int(rng.integers(0, W + 1)), replace=False))
    cpu = rng.choice([0.0, 0.25, 0.5, 1.0], W)
    alive = np.nonzero(rng.random(N) > 0.15)[0]
    if alive.size == 0:
        alive = np.array([0])
    mappings = [[] for _ in range(W)]
    for j in run:
        mappings[j] = list(rng.choice(alive, int(rng.integers(1, 5)),
                                      replace=True))
    inc = build_csr(cpu, mappings, N)
    active = np.zeros(W, dtype=bool)
    active[run] = True
    return inc, active


# --------------------------------------------------------------------------- #
# kernel parity                                                                #
# --------------------------------------------------------------------------- #
def test_maxmin_single_bit_equal():
    rng = np.random.default_rng(7)
    for _ in range(30):
        inc, active = random_instance(rng)
        got = alloc_jax.maxmin_yields_jax(inc, active)
        assert np.array_equal(got, maxmin_yields_csr(inc, active))


def test_maxmin_batch_padding_never_leaks():
    """Co-batched lanes, padded rows/cols and extra empty lanes must leave
    every real lane's yields bit-identical to its solo numpy solve."""
    rng = np.random.default_rng(11)
    insts = [random_instance(rng) for _ in range(12)]
    incs = [i for i, _ in insts]
    actives = [a for _, a in insts]
    N = max(i.n_nodes for i in incs)
    W = max(i.width for i in incs)
    # pad well beyond the minimal shape, plus 4 all-inactive lanes
    present, weight, active = alloc_jax.pad_batch(
        incs, actives, n_nodes=N + 5, width=W + 9, n_lanes=len(incs) + 4)
    y = alloc_jax.maxmin_yields_batch(present, weight, active)
    for b, (inc, act) in enumerate(insts):
        ref = maxmin_yields_csr(inc, act)
        assert np.array_equal(y[b, : inc.width], ref), f"lane {b} diverged"
        assert not y[b, inc.width:].any(), f"lane {b} padding got yields"
    assert not y[len(insts):].any(), "padding lanes got yields"


def test_maxmin_batch_composition_independent():
    """A lane's answer must not depend on what else is in the batch."""
    rng = np.random.default_rng(13)
    insts = [random_instance(rng) for _ in range(6)]
    incs = [i for i, _ in insts]
    actives = [a for _, a in insts]
    solo = []
    for inc, act in insts:
        p, w, a = alloc_jax.pad_batch([inc], [act])
        solo.append(alloc_jax.maxmin_yields_batch(p, w, a)[0])
    p, w, a = alloc_jax.pad_batch(incs, actives)
    together = alloc_jax.maxmin_yields_batch(p, w, a)
    for b, inc in enumerate(incs):
        assert np.array_equal(together[b, : inc.width],
                              solo[b][: inc.width])


def test_avg_backend_bit_equal():
    rng = np.random.default_rng(17)
    backend = alloc_jax.JaxAllocBackend()
    n_checked = 0
    for _ in range(20):
        inc, active = random_instance(rng)
        cols = np.nonzero(active)[0].astype(np.int64)
        if not cols.size:
            continue
        got = backend.allocate(inc, cols, "AVG")
        assert np.array_equal(got, avg_yields_csr(inc, cols))
        n_checked += 1
    assert n_checked >= 10


def test_backend_empty_running_set():
    inc = build_csr([0.5], [[]], 4)
    backend = alloc_jax.JaxAllocBackend()
    for opt in ("MIN", "AVG"):
        out = backend.allocate(inc, np.zeros(0, dtype=np.int64), opt)
        assert out.shape == (0,)
    with pytest.raises(ValueError):
        backend.allocate(inc, np.array([0]), "MAX")


def test_batched_allocator_mixed_opts():
    """One allocate_many round mixing MIN and AVG requests answers each
    bit-identically to the per-cell numpy kernels."""
    rng = np.random.default_rng(19)
    reqs, refs = [], []
    for k in range(8):
        inc, active = random_instance(rng)
        cols = np.nonzero(active)[0].astype(np.int64)
        opt = "AVG" if (k % 2 and cols.size) else "MIN"
        reqs.append((inc, cols, opt))
        if opt == "MIN":
            refs.append(maxmin_yields_csr(inc, active)[cols])
        else:
            refs.append(avg_yields_csr(inc, cols))
    outs = alloc_jax.BatchedAllocator().allocate_many(reqs)
    for got, ref in zip(outs, refs):
        assert np.array_equal(got, ref)


# --------------------------------------------------------------------------- #
# Pallas kernel                                                                #
# --------------------------------------------------------------------------- #
def test_pallas_matvec_bit_equal_csr():
    """The Pallas interpret kernel reproduces the sequential CSR matvec bit
    for bit (the adds-only formulation defeats XLA's FMA contraction)."""
    import jax

    from repro.kernels.alloc_matvec import alloc_matvec, alloc_matvec_ref

    rng = np.random.default_rng(23)
    incs_x = []
    B, N, W = 6, 10, 24
    weight = np.zeros((B, N, W))
    xs = np.zeros((B, W))
    for b in range(B):
        inc, active = random_instance(rng, max_width=W, max_nodes=N)
        _, w = alloc_jax.densify_csr(inc, n_nodes=N, width=W)
        weight[b] = w
        x = rng.random(W)
        xs[b] = x
        incs_x.append((inc, x))
    with jax.enable_x64(True):
        got_pl = np.asarray(alloc_matvec(weight, xs, interpret=True))
        got_ref = np.asarray(alloc_matvec_ref(weight, xs))
    for b, (inc, x) in enumerate(incs_x):
        ref = inc.matvec(x[: inc.width].copy())
        assert np.array_equal(got_pl[b, : inc.n_nodes], ref)
        assert np.array_equal(got_ref[b, : inc.n_nodes], ref)


def test_maxmin_pallas_matvec_bit_equal():
    rng = np.random.default_rng(29)
    for _ in range(6):
        inc, active = random_instance(rng, max_width=16, max_nodes=8)
        got = alloc_jax.maxmin_yields_jax(inc, active, matvec="interpret")
        assert np.array_equal(got, maxmin_yields_csr(inc, active))


def test_ops_dispatch_alloc_matvec():
    """kernels.ops.alloc_matvec: ref and interpreted Pallas agree bitwise."""
    import jax

    from repro.kernels import ops

    rng = np.random.default_rng(31)
    weight = rng.random((3, 6, 10))
    x = rng.random((3, 10))
    prev = ops.get_backend()
    try:
        with jax.enable_x64(True):
            ops.set_backend("ref")
            a = np.asarray(ops.alloc_matvec(weight, x))
            ops.set_backend("interpret")
            b = np.asarray(ops.alloc_matvec(weight, x))
    finally:
        ops.set_backend(prev)
    assert np.array_equal(a, b)


def test_matvec_choice_is_explicit():
    """No backend sniffing: "pallas" (compiled) refuses the lane's float64
    up front, and "auto" no longer exists."""
    with pytest.raises(ValueError, match="float64"):
        alloc_jax.BatchedAllocator(matvec="pallas")
    cells = grid([WorkloadSpec("lublin", n_jobs=10, n_nodes=4, seed=0)],
                 ["GreedyP */OPT=MIN"])
    with pytest.raises(ValueError, match="float64"):
        run_grid(cells, backend="pallas")
    with pytest.raises(ValueError, match="unknown matvec"):
        alloc_jax.BatchedAllocator(matvec="auto")


def test_has_jax_hides_only_a_missing_jax(monkeypatch):
    def missing():
        raise ImportError("no jax")

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(alloc_jax, "_jax", missing)
    assert not alloc_jax.has_jax()
    monkeypatch.setattr(alloc_jax, "_jax", broken)
    with pytest.raises(RuntimeError):
        alloc_jax.has_jax()


def test_compile_cache_placement(monkeypatch):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, else to the
    fixed path in the checkout — never a path built per process."""
    import os

    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        alloc_jax._enable_compile_cache(jax)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        alloc_jax._enable_compile_cache(jax)
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# --------------------------------------------------------------------------- #
# stretch scatter (segment_sum)                                                #
# --------------------------------------------------------------------------- #
def test_node_usage_bit_equal_add_at():
    rng = np.random.default_rng(37)
    for _ in range(10):
        n_nodes = int(rng.integers(1, 16))
        k = int(rng.integers(0, 40))
        nodes = rng.integers(0, n_nodes, k)
        vals = rng.random(k)
        ref = np.zeros(n_nodes)
        np.add.at(ref, nodes, vals)
        got = alloc_jax.node_usage(nodes, vals, n_nodes)
        assert np.array_equal(got, ref)


def test_node_usage_batch_padding():
    rng = np.random.default_rng(41)
    n_nodes, B, K = 9, 5, 20
    nodes = np.full((B, K), n_nodes, dtype=np.int64)   # sentinel = padding
    vals = np.zeros((B, K))
    refs = []
    for b in range(B):
        k = int(rng.integers(0, K))
        nodes[b, :k] = rng.integers(0, n_nodes, k)
        vals[b, :k] = rng.random(k)
        ref = np.zeros(n_nodes)
        np.add.at(ref, nodes[b, :k], vals[b, :k])
        refs.append(ref)
    got = alloc_jax.node_usage_batch(nodes, vals, n_nodes)
    for b in range(B):
        assert np.array_equal(got[b], refs[b])


# --------------------------------------------------------------------------- #
# engine + sweep integration                                                   #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("policy", ["GreedyP */OPT=MIN", "Greedy */OPT=AVG"])
def test_engine_backend_bit_identical(policy):
    tr = make_trace_ir(WorkloadSpec("lublin", n_jobs=60, n_nodes=16, seed=3))
    base = Engine(tr, policy, SimParams(n_nodes=16)).run()
    jaxed = Engine(tr, policy, SimParams(n_nodes=16),
                   alloc_backend=alloc_jax.JaxAllocBackend()).run()
    assert result_dict(base) == result_dict(jaxed)


_OUTCOME_KEYS = (
    "max_stretch", "mean_stretch", "makespan", "underutilization",
    "n_pmtn", "n_mig", "pmtn_per_job", "mig_per_job", "pmtn_per_hour",
    "mig_per_hour", "bytes_moved_gb", "bandwidth_gbps", "events",
    "hit_max_events", "final_time", "trace_fingerprint",
)


def _outcomes(res):
    return [{k: r[k] for k in _OUTCOME_KEYS} for r in res.records]


def test_run_batched_matches_run_grid():
    ws = [WorkloadSpec("lublin", n_jobs=40, n_nodes=16, seed=s)
          for s in range(4)]
    cells = grid(ws, ["GreedyP */OPT=MIN"], ["baseline", "rack_failure"])
    ref = run_grid(cells, compute_bound=True)
    got = run_batched(cells, compute_bound=True)
    assert _outcomes(got) == _outcomes(ref)
    assert all(r["backend"] == "jax" for r in got.records)
    assert all(g["bound"] == r["bound"]
               for g, r in zip(got.records, ref.records))


def test_run_grid_backend_arg():
    cells = grid([WorkloadSpec("lublin", n_jobs=30, n_nodes=16, seed=0)],
                 ["GreedyP */OPT=MIN"])
    ref = run_grid(cells)
    got = run_grid(cells, backend="jax")
    assert _outcomes(got) == _outcomes(ref)
    with pytest.raises(ValueError):
        run_grid(cells, backend="cuda")


def test_run_batched_mixed_policies_and_batch_baselines():
    """Lanes that never allocate (FCFS/EASY) and OPT=AVG lanes coexist in
    one lockstep schedule without deadlock or divergence."""
    ws = [WorkloadSpec("lublin", n_jobs=40, n_nodes=16, seed=s)
          for s in range(2)]
    policies = ["FCFS", "EASY", "GreedyP */OPT=MIN", "Greedy */OPT=AVG"]
    cells = grid(ws, policies, ["baseline"])
    ref = run_grid(cells)
    got = run_batched(cells)
    assert _outcomes(got) == _outcomes(ref)


def test_run_batched_propagates_errors():
    """A lane that raises must surface its exception on the driver thread
    (and release the other lanes) instead of deadlocking the lockstep."""
    cells = [Cell(WorkloadSpec("lublin", n_jobs=10, n_nodes=4, seed=0),
                  "GreedyP */OPT=MIN")
             for _ in range(2)]
    bad = [Cell(WorkloadSpec("lublin", n_jobs=10, n_nodes=4, seed=0),
                "NoSuchPolicy")]
    with pytest.raises(ValueError, match="NoSuchPolicy"):
        run_batched(bad + cells)
    with pytest.raises(ValueError, match="NoSuchPolicy"):
        run_grid(bad + cells, backend="jax")
    res = run_grid(bad + cells, backend="jax", retries=1)
    assert [bool(r.get("quarantined")) for r in res.records] == [
        True, False, False]


from repro.sched.sweep import Cell  # noqa: E402  (used above)


def test_acceptance_100_seed_grid_single_jitted_sweep():
    """The ISSUE acceptance criterion: a 100-cell seeded grid (one workload
    family × one policy × 100 seeds) end-to-end through the batched backend
    in one lockstep sweep, per-cell mean/max stretch matching the numpy
    sweep exactly (stronger than the required 1e-9 relative tolerance)."""
    ws = [WorkloadSpec("lublin", n_jobs=25, n_nodes=8, seed=s)
          for s in range(100)]
    cells = grid(ws, ["GreedyP */OPT=MIN"], ["baseline"])
    assert len(cells) == 100
    ref = run_grid(cells)
    got = run_batched(cells)
    for g, r in zip(got.records, ref.records):
        assert g["mean_stretch"] == r["mean_stretch"]
        assert g["max_stretch"] == r["max_stretch"]
    assert _outcomes(got) == _outcomes(ref)
