"""The plain DFRS simulator and the benchmark's traces agree with the
program, and the simulator's records react to what they should."""
import math

import pytest

from chipbench import dfrs_reference, verify, workload
from repro import api
from repro.tune import Variant, race

#: a trace small enough for the CPU and dense enough that jobs wait, get
#: paused and move (the paper's arrival rate at 128 nodes rarely queues)
LOADED = {"kind": "lublin", "n_nodes": 32, "n_jobs": 200,
          "mean_interarrival_s": 40.0}
POLICIES = ["GreedyP */OPT=MIN", "GreedyPM */OPT=MIN",
            "GreedyP */per/OPT=MIN/MINVT=600",
            "GreedyPM */per/OPT=MIN/MINVT=600",
            "Greedy */OPT=AVG", "GreedyP */OPT=AVG"]


def _program_record(seed, policy, conf=LOADED):
    cell = api.grid([workload.spec(api, conf, seed)], [policy], ["baseline"])
    return api.run_grid(cell, n_workers=1).records[0]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_traces_are_the_programs_lublin_traces(seed):
    conf = dict(LOADED, n_jobs=1000, n_nodes=128, mean_interarrival_s=450.0)
    mine = api.make_trace_ir(workload.spec(api, conf, seed))
    theirs = api.make_trace_ir(api.WorkloadSpec("lublin", n_jobs=1000,
                                                n_nodes=128, seed=seed))
    assert mine.fingerprint == theirs.fingerprint


@pytest.mark.parametrize("policy", POLICIES)
def test_sweep_records_match_program(policy):
    for seed in (0, 1):
        got = _program_record(seed, policy)
        ref = dfrs_reference.simulate(workload.columns(LOADED, seed), policy,
                                      LOADED["n_nodes"])
        assert verify.outcome_gap([got], [ref]) < 1e-12, (seed, policy)


def test_loaded_trace_pauses_and_moves():
    ref = dfrs_reference.simulate(workload.columns(LOADED, 0),
                                  "GreedyPM */per/OPT=MIN/MINVT=600",
                                  LOADED["n_nodes"])
    assert ref["n_pmtn"] > 10 and ref["n_mig"] > 5


def test_race_decisions_match_program():
    inc = "GreedyP */OPT=MIN"
    variants = POLICIES[1:4]
    ses = api.open_session(LOADED["n_nodes"], inc)
    ses.submit(workload.spec(api, LOADED, 0))
    snaps, at = [], 1500.0
    while True:
        ses.step_until(at)
        if ses.exhausted:
            break
        snaps.append(ses.snapshot())
        at += 1500.0
    forks = dfrs_reference.forks(workload.columns(LOADED, 0), inc,
                                 LOADED["n_nodes"], 1500.0)
    assert len(forks) == len(snaps) >= 2
    got = [race(s, [Variant(v) for v in variants], Variant(inc),
                objective="max_stretch", base_horizon=600.0, rungs=2)
           for s in snaps]
    ref = [dfrs_reference.race(f, inc, variants, 600.0, 2) for f in forks]
    assert verify.race_ref_gap(got, ref) < 1e-12


def test_outcome_gap_reads_what_differs():
    ref = {"max_stretch": 4.0, "n_pmtn": 3, "partial": False}
    assert verify.outcome_gap([dict(ref, policy="x")], [ref]) == 0.0
    assert verify.outcome_gap([dict(ref, n_pmtn=4)], [ref]) == pytest.approx(1 / 3)
    assert verify.outcome_gap([dict(ref, partial=True)], [ref]) > 1e100
    missing = dict(ref)
    del missing["n_pmtn"]
    assert verify.outcome_gap([missing], [ref]) == math.inf
    assert verify.outcome_gap([], [ref]) == math.inf


def test_unknown_policy_is_refused():
    with pytest.raises(ValueError):
        dfrs_reference.Policy("MCB8 */OPT=MIN")
    with pytest.raises(ValueError):
        dfrs_reference.Policy("GreedyP */stretch-per/OPT=MAX")
    with pytest.raises(ValueError):
        workload.columns(dict(LOADED, kind="hpc2n"), 0)
