"""Core scheduler unit + property tests (greedy, MCB8, yields, policies)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import alloc_reference
from repro.core.greedy import greedy_fits, greedy_p, greedy_place, greedy_pm
from repro.core.job import JobSpec, JobState, NodePool, RUNNING
from repro.core.mcb8 import mcb8, mcb8_pack
from repro.core.policies import (TABLE1_POLICIES, all_paper_policies,
                                 parse_policy)
from repro.core.yield_alloc import allocate, maxmin_yields, min_yield

# --------------------------------------------------------------------------- #
# strategies                                                                   #
# --------------------------------------------------------------------------- #
job_st = st.builds(
    JobSpec,
    jid=st.integers(0, 10_000),
    release=st.floats(0, 1e5),
    proc_time=st.floats(1.0, 1e5),
    n_tasks=st.integers(1, 16),
    cpu_need=st.sampled_from([0.25, 0.5, 1.0]),
    mem_req=st.sampled_from([0.1, 0.2, 0.3, 0.5, 0.8, 1.0]),
)


def _states(specs, vt_seed=0):
    rng = np.random.default_rng(vt_seed)
    out = []
    for i, s in enumerate(specs):
        js = JobState(spec=JobSpec(
            jid=i, release=0.0, proc_time=s.proc_time, n_tasks=s.n_tasks,
            cpu_need=s.cpu_need, mem_req=s.mem_req))
        js.vt = float(rng.uniform(0.1, 100.0))
        out.append(js)
    return out


# --------------------------------------------------------------------------- #
# greedy placement                                                             #
# --------------------------------------------------------------------------- #
@settings(max_examples=50, deadline=None)
@given(st.lists(job_st, min_size=1, max_size=20), st.integers(2, 16))
def test_greedy_place_never_oversubscribes_memory(specs, n_nodes):
    pool = NodePool(n_nodes)
    for s in specs:
        mapping = greedy_place(pool, s)
        if mapping is not None:
            assert len(mapping) == s.n_tasks
    assert (pool.mem_free >= -1e-9).all()


def test_greedy_place_picks_lowest_load():
    pool = NodePool(3)
    pool.load[:] = [0.5, 0.1, 0.9]
    s = JobSpec(jid=0, release=0, proc_time=10, n_tasks=1,
                cpu_need=0.25, mem_req=0.1)
    assert greedy_place(pool, s) == [1]


def test_greedy_place_rolls_back_on_failure():
    pool = NodePool(2)
    pool.mem_free[:] = [0.25, 0.15]
    s = JobSpec(jid=0, release=0, proc_time=10, n_tasks=3,
                cpu_need=1.0, mem_req=0.2)
    before = pool.mem_free.copy()
    assert greedy_place(pool, s) is None
    np.testing.assert_allclose(pool.mem_free, before)


class _Probe:
    """A job's shape alone, so that a fit test may ask for no memory at all
    (``JobSpec`` wants ``mem_req`` above 0)."""

    def __init__(self, n_tasks, mem_req, cpu_need=0.25):
        self.n_tasks, self.mem_req, self.cpu_need = n_tasks, mem_req, cpu_need


def _worn_pool(seed):
    """A pool after a long history of greedy places and removals, with a
    few nodes' memory zeroed as a failure leaves it."""
    rng = np.random.default_rng(seed)
    n_nodes = int(rng.integers(1, 25))
    pool, placed = NodePool(n_nodes), []
    for step in range(400):
        if placed and rng.random() < 0.45:
            spec, mapping = placed.pop(int(rng.integers(len(placed))))
            pool.remove(spec, mapping)
            continue
        spec = JobSpec(jid=step, release=0.0, proc_time=1.0,
                       n_tasks=int(rng.integers(1, 2 * n_nodes + 2)),
                       cpu_need=float(rng.choice([0.25, 0.5, 1.0])),
                       mem_req=float(rng.choice(
                           [rng.uniform(0.01, 1.0), 0.1, 0.3, 0.7, 1.0])))
        mapping = greedy_place(pool, spec)
        if mapping is not None:
            placed.append((spec, mapping))
    for node in rng.choice(n_nodes, size=n_nodes // 5, replace=False):
        pool.mem_free[node] = 0.0
    return pool, rng


def _edge_mem_reqs(pool):
    """Memory requests whose threshold ``m - 1e-12`` lands within a few
    ulps of a node's free memory, on both sides, after its first and its
    second task."""
    out = []
    for f in np.unique(pool.mem_free)[:6]:
        for k in (1.0, 2.0):
            m = (float(f) + 1e-12) / k
            for _ in range(3):
                m = np.nextafter(m, -np.inf)
            for _ in range(7):
                if m > 0:
                    out.append(float(m))
                m = np.nextafter(m, np.inf)
    return out


def _fits_all_ways(pool, spec):
    fast = greedy_place(pool.copy(), spec) is not None
    assert fast == (alloc_reference.greedy_place(pool.copy(), spec)
                    is not None)
    return fast


@pytest.mark.parametrize("seed", range(8))
def test_greedy_fits_is_greedy_place_on_a_copy(seed):
    pool, rng = _worn_pool(seed)
    load, mem_free = pool.load.tobytes(), pool.mem_free.tobytes()
    mem_reqs = ([float(m) for m in rng.uniform(0.0, 1.0, 12)]
                + [0.1, 0.3, 0.5, 1.0, 1e-300, 1e-12, 0.0]
                + _edge_mem_reqs(pool))
    seen = set()
    for m in mem_reqs:
        for n in sorted({1, 2, pool.n, pool.n + 1, 3 * pool.n + 5,
                         int(rng.integers(1, 3 * pool.n + 6))}):
            spec = _Probe(n, m)
            got = greedy_fits(pool, spec)
            assert got == _fits_all_ways(pool, spec), (n, m)
            seen.add(got)
            # a read: the pool is left bit for bit as it was
            assert pool.load.tobytes() == load
            assert pool.mem_free.tobytes() == mem_free
    assert seen == {True, False}


@pytest.mark.parametrize("side", ["fits", "does-not-fit"])
@pytest.mark.parametrize("tasks", [1, 2, 3])
def test_greedy_fits_one_ulp_either_side_of_the_threshold(side, tasks):
    """One node with 0.3 free: the request whose ``tasks``-th task is the
    last to fit, and the next float up, whose ``tasks``-th task does not."""
    pool = NodePool(1)
    pool.mem_free[:] = 0.3
    m = (0.3 + 1e-12) / tasks
    for _ in range(64):          # walk down to the largest m that fits
        if _fits_all_ways(pool, _Probe(tasks, m)):
            break
        m = np.nextafter(m, -np.inf)
    while _fits_all_ways(pool, _Probe(tasks, np.nextafter(m, np.inf))):
        m = np.nextafter(m, np.inf)
    if side == "does-not-fit":
        m = np.nextafter(m, np.inf)
    spec = _Probe(tasks, float(m))
    assert greedy_fits(pool, spec) is (side == "fits")
    assert greedy_fits(pool, spec) == _fits_all_ways(pool, spec)


@pytest.mark.parametrize("mem_req", [0.5, 1e-300, 0.0])
def test_greedy_fits_nothing_on_an_empty_pool(mem_req):
    spec = _Probe(1, mem_req)
    assert greedy_fits(NodePool(0), spec) is False
    assert not _fits_all_ways(NodePool(0), spec)


@pytest.mark.parametrize("mem_req", [0.0, 1e-300, 1e-13])
def test_greedy_fits_takes_any_number_of_weightless_tasks(mem_req):
    """Nodes whose memory a failure zeroed still take a task that asks for
    (next to) nothing, without end; with none alive nothing fits."""
    pool = NodePool(3)
    pool.mem_free[:] = [0.0, 0.0, 0.25]
    for n in (1, 3, 50):
        assert greedy_fits(pool, _Probe(n, mem_req)) is True
        assert _fits_all_ways(pool, _Probe(n, mem_req))
    pool.mem_free[:] = -1e-9            # below every threshold
    assert greedy_fits(pool, _Probe(1, mem_req)) is False
    assert not _fits_all_ways(pool, _Probe(1, mem_req))


@pytest.mark.parametrize("seed", range(6))
def test_greedy_fits_failure_dominates(seed):
    """If (n, m) does not fit a pool, no (n' >= n, m' >= m) does: the fit
    table is monotone along both axes."""
    pool, rng = _worn_pool(100 + seed)
    ns = list(range(1, 3 * pool.n + 3))
    ms = sorted({float(m) for m in rng.uniform(0.0, 1.0, 24)}
                | set(_edge_mem_reqs(pool)) | {1e-300, 1.0})
    table = np.array([[greedy_fits(pool, _Probe(n, m)) for m in ms]
                      for n in ns])
    assert table.any() and not table.all()
    assert not (table[1:] & ~table[:-1]).any()      # rising n never fits more
    assert not (table[:, 1:] & ~table[:, :-1]).any()  # nor rising m


def test_greedy_p_pauses_lowest_priority_first():
    pool = NodePool(1)
    # two running jobs fill memory; the lower-priority one must be paused
    specs = [JobSpec(jid=i, release=0, proc_time=100, n_tasks=1,
                     cpu_need=1.0, mem_req=0.5) for i in range(2)]
    running = []
    for i, s in enumerate(specs):
        js = JobState(spec=s, status=RUNNING, mapping=[0])
        js.vt = 10.0 if i == 0 else 100.0    # jid 1: bigger vt -> lower prio
        pool.place(s, [0])
        running.append(js)
    new = JobSpec(jid=2, release=50, proc_time=10, n_tasks=1,
                  cpu_need=1.0, mem_req=0.5)
    adm = greedy_p(pool.copy(), new, running, now=50.0)
    assert adm.mapping is not None
    assert adm.paused == [1]


@settings(max_examples=30, deadline=None)
@given(st.lists(job_st, min_size=1, max_size=12), st.integers(2, 8),
       st.integers(0, 5))
def test_greedy_pm_admission_is_feasible(specs, n_nodes, seed):
    """Applying a GreedyPM admission plan transactionally never violates
    memory capacity."""
    rng = np.random.default_rng(seed)
    pool = NodePool(n_nodes)
    running = []
    for i, s in enumerate(specs[:-1]):
        spec = JobSpec(jid=i, release=0, proc_time=10, n_tasks=s.n_tasks,
                       cpu_need=s.cpu_need, mem_req=s.mem_req)
        m = greedy_place(pool, spec)
        if m is None:
            continue
        js = JobState(spec=spec, status=RUNNING, mapping=m)
        js.vt = float(rng.uniform(1, 100))
        running.append(js)
    s = specs[-1]
    new = JobSpec(jid=999, release=1, proc_time=10, n_tasks=s.n_tasks,
                  cpu_need=s.cpu_need, mem_req=s.mem_req)
    adm = greedy_pm(pool.copy(), new, running, now=1.0)
    if adm.mapping is None:
        return
    # rebuild: survivors (possibly moved) + the new job
    check = NodePool(n_nodes)
    for js in running:
        if js.spec.jid in adm.paused:
            continue
        check.place(js.spec, adm.moved.get(js.spec.jid, js.mapping))
    check.place(new, adm.mapping)      # raises if memory oversubscribed


# --------------------------------------------------------------------------- #
# MCB8                                                                         #
# --------------------------------------------------------------------------- #
@settings(max_examples=40, deadline=None)
@given(st.lists(job_st, min_size=1, max_size=20), st.integers(2, 16))
def test_mcb8_pack_respects_capacities(specs, n_nodes):
    items = [(i, s.cpu_need * 0.5, s.mem_req, s.n_tasks)
             for i, s in enumerate(specs)]
    res = mcb8_pack(n_nodes, items)
    if res is None:
        return
    cpu = np.zeros(n_nodes)
    mem = np.zeros(n_nodes)
    for (jid, c, m, n) in items:
        assert len(res[jid]) == n
        for node in res[jid]:
            cpu[node] += c
            mem[node] += m
    assert (cpu <= 1 + 1e-9).all() and (mem <= 1 + 1e-9).all()


@settings(max_examples=25, deadline=None)
@given(st.lists(job_st, min_size=1, max_size=15), st.integers(2, 8))
def test_mcb8_full_allocation_valid(specs, n_nodes):
    states = _states(specs)
    res = mcb8(states, n_nodes, now=200.0)
    cpu = np.zeros(n_nodes)
    mem = np.zeros(n_nodes)
    by = {js.spec.jid: js for js in states}
    for jid, mapping in res.mappings.items():
        s = by[jid].spec
        assert len(mapping) == s.n_tasks
        for node in mapping:
            cpu[node] += min(1.0, s.cpu_need * res.yld)
            mem[node] += s.mem_req
    assert (mem <= 1 + 1e-9).all()
    assert (cpu <= 1 + 1e-6).all()
    # every candidate is either mapped or explicitly removed
    assert set(res.mappings) | set(res.removed) == set(by)


def test_mcb8_removes_lowest_priority_when_infeasible():
    # 1 node, three jobs of mem 0.5 -> at most 2 fit; lowest prio removed
    specs = [JobSpec(jid=i, release=0, proc_time=100, n_tasks=1,
                     cpu_need=1.0, mem_req=0.5) for i in range(3)]
    states = [JobState(spec=s) for s in specs]
    states[0].vt = 100.0      # lowest priority (largest vt)
    states[1].vt = 10.0
    states[2].vt = 1.0
    res = mcb8(states, 1, now=200.0)
    assert res.removed == [0]
    assert set(res.mappings) == {1, 2}


def test_mcb8_pinned_jobs_keep_mapping():
    specs = [JobSpec(jid=i, release=0, proc_time=100, n_tasks=1,
                     cpu_need=1.0, mem_req=0.3) for i in range(3)]
    states = _states(specs)
    res = mcb8(states, 4, now=200.0, pinned={1: [3]})
    assert res.mappings[1] == [3]


def test_mcb8_deterministic_across_priority_shuffle():
    """Mapping stability (paper SS4.4 footnote): permuting the candidate
    order (priorities change over time) must not change the packing."""
    specs = [JobSpec(jid=i, release=0, proc_time=100, n_tasks=2,
                     cpu_need=1.0, mem_req=0.2) for i in range(8)]
    a = _states(specs, vt_seed=1)
    b = _states(specs, vt_seed=2)     # different priorities
    ra = mcb8(a, 8, now=200.0)
    rb = mcb8(b, 8, now=200.0)
    assert ra.mappings == rb.mappings


# --------------------------------------------------------------------------- #
# yield allocation (SS4.6)                                                     #
# --------------------------------------------------------------------------- #
@settings(max_examples=40, deadline=None)
@given(st.lists(job_st, min_size=1, max_size=10), st.integers(1, 8),
       st.integers(0, 3))
def test_maxmin_yields_feasible_and_floor(specs, n_nodes, seed):
    rng = np.random.default_rng(seed)
    pool = NodePool(n_nodes)
    placed, maps = [], []
    for i, s in enumerate(specs):
        spec = JobSpec(jid=i, release=0, proc_time=10, n_tasks=s.n_tasks,
                       cpu_need=s.cpu_need, mem_req=s.mem_req)
        m = greedy_place(pool, spec)
        if m is not None:
            placed.append(spec)
            maps.append(m)
    if not placed:
        return
    y = maxmin_yields(placed, maps, n_nodes)
    assert ((0 <= y) & (y <= 1.0 + 1e-12)).all()
    # feasibility: per-node allocated CPU <= 1
    load = np.zeros(n_nodes)
    for spec, m, yi in zip(placed, maps, y):
        for node in m:
            load[node] += yi * spec.cpu_need
    assert (load <= 1 + 1e-6).all()
    # floor: no one below the equal-share min yield
    assert (y >= min_yield(pool.load.max()) - 1e-9).all()
    # OPT=AVG dominates OPT=MIN on the sum, never below the floor
    y_avg = allocate(placed, maps, n_nodes, opt="AVG")
    assert y_avg.sum() >= y.sum() - 1e-6


def test_priority_function():
    s = JobSpec(jid=1, release=100.0, proc_time=10, n_tasks=1,
                cpu_need=1.0, mem_req=0.1)
    js = JobState(spec=s)
    assert js.priority(150.0) == np.inf          # never ran -> infinite
    js.vt = 5.0
    assert js.priority(150.0) == pytest.approx(50.0 / 25.0)


# --------------------------------------------------------------------------- #
# policy naming (SS4.5)                                                        #
# --------------------------------------------------------------------------- #
def test_parse_policy_roundtrip():
    p = parse_policy("GreedyPM */per/OPT=MIN/MINVT=600")
    assert p.on_submit == "greedyPM" and p.opportunistic
    assert p.periodic == "mcb8" and p.opt == "MIN" and p.minvt == 600.0
    assert p.on_complete == "greedy"
    p2 = parse_policy("MCB8 */OPT=AVG/MINFT=300")
    assert p2.on_submit == "mcb8" and p2.on_complete == "mcb8"
    assert p2.minft == 300.0 and p2.periodic is None
    p3 = parse_policy("/stretch-per/OPT=MAX")
    assert p3.on_submit is None and p3.periodic == "mcb8-stretch"


def test_table1_and_full_policy_space():
    for name in TABLE1_POLICIES:
        parse_policy(name)
    space = all_paper_policies()
    assert len(space) == len(set(space))
    for name in space:
        parse_policy(name)
    # the paper counts 116 combinations (SS6.1)
    assert len(space) == 116


def test_parse_policy_rejects_unknown():
    with pytest.raises(ValueError):
        parse_policy("Greedy */per/OPT=WAT")
    with pytest.raises(ValueError):
        parse_policy("Foo */per")
