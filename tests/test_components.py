"""Composable policy-component API tests.

Three layers:

* spec model: the 116-combination enumeration, canonicalization of
  equivalent spellings, render/parse round-trips;
* golden equivalence: every Table-1 policy plus the 17-cell acceptance
  grid (failure scenarios included) run once through the registry-backed
  ``ComposedPolicy`` and once through the pre-redesign monolithic classes
  (``DFRSPolicy``/``BatchPolicy``), requiring *bit-identical*
  ``SimResult``s;
* open API: a composition the grammar cannot express (the hybrid
  ``EASY+OPT=MIN``) registers via the public API, runs through
  ``run_grid``, and lands in a sweep artifact.
"""
import dataclasses

from conftest import result_dict as _result_dict
import itertools
import json

import pytest

from repro.core.policies import (PolicySpec, TABLE1_POLICIES,
                                 all_paper_policies, parse_policy,
                                 render_policy)
from repro.sched.components import (ComposedPolicy, Component, compose,
                                    compose_from_spec, get_component,
                                    list_components, register_component,
                                    register_policy, registered_policies,
                                    resolve_policy)
from repro.sched.engine import Engine, SimParams, make_seed_policy
from repro.sched.scenarios import apply_scenario
from repro.sched.sweep import grid, run_grid
from repro.workloads.registry import WorkloadSpec, make_trace


def mini_trace(n=30, nodes=16, seed=0):
    return make_trace(WorkloadSpec("lublin", n_jobs=n, n_nodes=nodes,
                                   seed=seed))


# --------------------------------------------------------------------------- #
# spec model: enumeration + canonicalization + round-trip                      #
# --------------------------------------------------------------------------- #
def test_paper_space_is_116_unique_parseable():
    names = all_paper_policies()
    assert len(names) == 116
    canon = [parse_policy(n).name for n in names]     # all parseable
    assert len(set(canon)) == 116                     # no duplicates


@pytest.mark.parametrize("a,b", [
    ("Greedy *", "greedy */OPT=MIN"),
    ("GreedyP */per/OPT=MIN/MINVT=600", "greedyp */MINVT=600/per/opt=min"),
    ("  GreedyPM  */per", "GREEDYPM*/PER/OPT=MIN"),
    ("/per", "/per/OPT=MIN"),
    ("/stretch-per/OPT=MAX", "/OPT=MAX/stretch-per"),
    ("MCB8 *", "mcb8*/OPT=MIN"),
    ("fcfs", "FCFS"),
])
def test_equivalent_spellings_parse_to_equal_specs(a, b):
    sa, sb = parse_policy(a), parse_policy(b)
    assert sa == sb
    assert sa.name == sb.name                         # one canonical name


def test_all_spellings_round_trip():
    """parse(render(spec)) == spec across the full combination space."""
    for name in all_paper_policies() + TABLE1_POLICIES + ["FCFS", "EASY"]:
        spec = parse_policy(name)
        assert render_policy(spec) == spec.name
        assert parse_policy(render_policy(spec)) == spec


def test_make_round_trips_over_component_product():
    limits = [(None, None), (300.0, None), (None, 600.0)]
    for on_submit, opp, periodic, (minvt, minft) in itertools.product(
            [None, "greedy", "greedyP", "greedyPM", "mcb8"],
            [False, True],
            [None, "mcb8", "mcb8-stretch"],
            limits):
        opts = ("MIN", "AVG", "MAX") if periodic == "mcb8-stretch" \
            else ("MIN", "AVG")
        for opt in opts:
            spec = PolicySpec.make(on_submit, opp, periodic, opt, minvt, minft)
            assert parse_policy(render_policy(spec)) == spec


def test_opt_max_requires_stretch_per():
    with pytest.raises(ValueError):
        parse_policy("GreedyP */OPT=MAX")


# --------------------------------------------------------------------------- #
# registry                                                                     #
# --------------------------------------------------------------------------- #
def test_component_registry_contents():
    comps = list_components()
    assert set(comps) == {"submit", "complete", "periodic", "opt"}
    assert {"greedy", "greedyP", "greedyPM", "mcb8",
            "fcfs-queue"} <= set(comps["submit"])
    assert {"greedy", "mcb8", "reclaim", "fcfs-start",
            "easy-backfill"} <= set(comps["complete"])
    assert {"mcb8", "mcb8-stretch", "backfill"} <= set(comps["periodic"])
    assert set(comps["opt"]) == {"MIN", "AVG", "MAX"}


def test_registry_rejects_duplicates_and_unknowns():
    with pytest.raises(ValueError):
        register_component("submit", "greedy")(type("Dup", (Component,), {}))
    with pytest.raises(ValueError):
        register_component("not-a-kind", "x")
    with pytest.raises(KeyError, match="unknown submit"):
        get_component("submit", "nope")


def test_compose_from_spec_shapes():
    p = compose_from_spec(parse_policy("GreedyPM */per/OPT=MIN/MINVT=600"))
    assert isinstance(p, ComposedPolicy)
    kinds = [(c.kind, c.component_name) for c in p.components]
    assert kinds == [("submit", "greedyPM"), ("complete", "greedy"),
                     ("periodic", "mcb8"), ("opt", "MIN")]
    assert p.periodic_kind == "mcb8" and p.handles_cluster_events

    b = compose_from_spec(parse_policy("EASY"))
    kinds = [(c.kind, c.component_name) for c in b.components]
    assert kinds == [("submit", "fcfs-queue"), ("complete", "reclaim"),
                     ("complete", "easy-backfill")]
    assert b.periodic_kind is None and not b.handles_cluster_events


def test_composition_rejects_two_periodic_components():
    with pytest.raises(ValueError, match="periodic"):
        compose("broken",
                get_component("periodic", "mcb8")(),
                get_component("periodic", "mcb8-stretch")())


def test_register_policy_rejects_grammar_spellings_and_duplicates():
    with pytest.raises(ValueError, match="grammar"):
        register_policy("GreedyP */OPT=MIN", lambda: None)
    with pytest.raises(ValueError, match="already registered"):
        register_policy("EASY+OPT=MIN", lambda: None)
    assert "EASY+OPT=MIN" in registered_policies()
    assert resolve_policy("no-such-policy") is None
    # factories build fresh (stateful) instances per resolution
    assert resolve_policy("EASY+OPT=MIN") is not resolve_policy("EASY+OPT=MIN")


# --------------------------------------------------------------------------- #
# golden equivalence: composed == seed classes, bit for bit                    #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("policy", TABLE1_POLICIES + ["FCFS", "EASY"])
def test_every_table1_policy_composed_equals_seed(policy):
    specs = mini_trace()
    spec = parse_policy(policy)
    params = SimParams(n_nodes=16)
    composed = Engine(specs, policy, params).run()
    seed = Engine(specs, make_seed_policy(spec), params).run()
    assert _result_dict(composed) == _result_dict(seed)


# the 17-cell acceptance harness of tests/test_alloc_kernels.py
GOLDEN_POLICIES = ["FCFS", "EASY", "GreedyP */OPT=MIN",
                   "GreedyPM */per/OPT=MIN/MINVT=600"]
GOLDEN_WORKLOADS = [WorkloadSpec("lublin", n_jobs=40, n_nodes=16, seed=0),
                    WorkloadSpec("hpc2n", n_jobs=40, n_nodes=128, seed=1)]
GOLDEN_CASES = [(w, p, sc)
                for w in GOLDEN_WORKLOADS
                for p in GOLDEN_POLICIES
                for sc in ("baseline", "rack_failure")]
GOLDEN_CASES.append((GOLDEN_WORKLOADS[0], "/stretch-per/OPT=MAX", "baseline"))
# the contended end, where the composed resume scan skips the jobs that
# cannot fit and the seed's tries every one; trace 0 leaves a short backlog,
# trace 1 a long one at most completions
LOADED_WORKLOADS = [WorkloadSpec("lublin", n_jobs=120, n_nodes=16, seed=s,
                                 load=0.9) for s in (0, 1)]
GOLDEN_CASES += [(w, p, sc)
                 for w in LOADED_WORKLOADS
                 for p in ("GreedyP */OPT=MIN",
                           "GreedyPM */per/OPT=MIN/MINVT=600")
                 for sc in ("baseline", "rack_failure")]


@pytest.mark.parametrize(
    "workload,policy,scenario", GOLDEN_CASES,
    ids=[f"{w.name}-{p}-{sc}" for w, p, sc in GOLDEN_CASES])
def test_golden_composed_vs_seed_simresult(workload, policy, scenario):
    specs = make_trace(workload)
    specs, events = apply_scenario(scenario, specs, workload.n_nodes,
                                   seed=workload.seed)
    params = SimParams(n_nodes=workload.n_nodes)
    engine = Engine(specs, policy, params, cluster_events=events)
    composed = engine.run()
    seed = Engine(specs, make_seed_policy(parse_policy(policy)), params,
                  cluster_events=events).run()
    assert _result_dict(composed) == _result_dict(seed)
    m = engine.meter
    assert m.started <= m.placed <= m.scanned
    if workload is LOADED_WORKLOADS[1]:
        # the scan pruned: most waiting jobs never reached greedy_place
        assert 0 < m.started <= m.placed < m.scanned / 2


def test_default_engine_policy_is_composed():
    eng = Engine(mini_trace(n=5), "GreedyP */OPT=MIN", SimParams(n_nodes=16))
    assert isinstance(eng.policy, ComposedPolicy)


# --------------------------------------------------------------------------- #
# the open API: compositions beyond the grammar                                #
# --------------------------------------------------------------------------- #
def test_hybrid_runs_end_to_end_and_fractionally_backfills(monkeypatch):
    from repro.sched import components as C

    frac_starts = []
    orig = C.BatchStartPass._start_frac

    def counting(self, st, js):
        ok = orig(self, st, js)
        if ok:
            frac_starts.append(js.spec.jid)
        return ok

    monkeypatch.setattr(C.BatchStartPass, "_start_frac", counting)
    specs = make_trace(WorkloadSpec("lublin", n_jobs=60, n_nodes=16, seed=0,
                                    load=0.9))
    r = Engine(specs, "EASY+OPT=MIN", SimParams(n_nodes=16)).run()
    assert set(r.completions) == {s.jid for s in specs}
    assert r.policy == "EASY+OPT=MIN"
    assert frac_starts, "fractional backfill never fired on this trace"
    # fractional sharing is arbitrated by OPT=MIN: co-located jobs finish,
    # and the hybrid is still a batch policy from the engine's perspective
    assert not Engine(specs, "EASY+OPT=MIN",
                      SimParams(n_nodes=16)).policy.handles_cluster_events


def test_hybrid_improves_mean_stretch_on_contended_trace():
    specs = mini_trace(n=80, seed=1)
    hybrid = Engine(specs, "EASY+OPT=MIN", SimParams(n_nodes=16)).run()
    easy = Engine(specs, "EASY", SimParams(n_nodes=16)).run()
    assert hybrid.mean_stretch <= easy.mean_stretch + 1e-9


def test_hybrid_through_run_grid_lands_in_artifact(tmp_path):
    w = WorkloadSpec("lublin", n_jobs=30, n_nodes=16, seed=3)
    path = str(tmp_path / "hybrid_sweep.json")
    res = run_grid(grid([w], ["EASY", "EASY+OPT=MIN"]), n_workers=1,
                   json_path=path)
    assert res.n_cells == 2
    art = json.loads(open(path).read())
    assert {r["policy"] for r in art["records"]} == {"EASY", "EASY+OPT=MIN"}
    for rec in art["records"]:
        assert not rec["hit_max_events"] and rec["makespan"] > 0


def test_hybrid_blocks_backfill_when_reservation_uncomputable():
    """When withheld frac-occupied nodes make the head's shadow time
    uncomputable (free + exclusive-running < head need), no job may
    backfill — a vacuous `t <= inf` check would disable EASY's reservation
    protection entirely."""
    from repro.core.job import JobSpec
    from repro.core.state import S_PENDING
    from repro.sched.components import BatchStartPass, _batch_state

    specs = [JobSpec(jid=0, release=0.0, proc_time=100.0, n_tasks=2,
                     cpu_need=1.0, mem_req=0.5),      # head: needs both nodes
            JobSpec(jid=1, release=0.0, proc_time=10.0, n_tasks=1,
                    cpu_need=1.0, mem_req=0.2)]       # would fit node 1
    e = Engine(specs, "EASY+OPT=MIN", SimParams(n_nodes=2))
    pol = e.policy
    st = _batch_state(pol)
    st.free = [1]                 # node 0 withheld: frac occupant remains
    st.frac_count[0] = 1
    e.state.status[:] = S_PENDING
    st.queue.append(e.state.views[0])
    st.queue.append(e.state.views[1])
    start = next(c for c in pol.components if isinstance(c, BatchStartPass))
    start._try_start(st)
    # head cannot start (1 free < 2) and the candidate must NOT jump it
    assert e.state.views[0].status == "pending"
    assert e.state.views[1].status == "pending"


def test_custom_composition_registers_and_sweeps():
    """A user-defined composition (periodic-only batch backfill — the queue
    drains on the tick, not on events) goes through the whole public path."""
    name = "test-periodic-backfill"
    if name not in registered_policies():
        register_policy(name, lambda: compose(
            name,
            get_component("submit", "fcfs-queue")(),
            get_component("complete", "reclaim")(),
            get_component("periodic", "backfill")(),
        ), description="batch queue drained only on the periodic tick")
    pol = resolve_policy(name)
    assert pol.periodic_kind == "backfill"
    w = WorkloadSpec("lublin", n_jobs=20, n_nodes=16, seed=0)
    res = run_grid(grid([w], [name]), n_workers=1)
    assert res.records[0]["policy"] == name
    assert not res.records[0]["hit_max_events"]
    # delaying every start to the tick can only push completions later
    direct = run_grid(grid([w], ["EASY"]), n_workers=1)
    assert res.records[0]["makespan"] >= direct.records[0]["makespan"] - 1e-9
