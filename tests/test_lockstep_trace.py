"""The lockstep path's round log and its ``dfrs.*`` spans.

One batched sweep pass and one branch race, small enough for the CPU, are
run with the allocator's calls counted from outside; the round log must
account for every call and request, its timed parts must nest inside the
round, and no lane's answer may change.  A CPU profiler trace of a tiny
pass must carry the spans, nested, for the trace reduction to label gaps.
"""
import math
import sys
import threading
import time

import numpy as np
import pytest

from repro import api
from repro.core import alloc_jax, roundlog
from repro.core.alloc_kernels import build_csr
from repro.sched.sweep import grid, run_batched, run_grid
from repro.tune import Variant, race
from repro.workloads.registry import WorkloadSpec

pytestmark = pytest.mark.skipif(not alloc_jax.has_jax(),
                                reason="jax present but not importable")

POLICIES = ["FCFS", "GreedyP */OPT=MIN", "Greedy */OPT=AVG"]
PARTS = ("pad_s", "dispatch_s", "fetch_s", "lam_s", "lp_s")
#: the spans as the trace reduction takes them, innermost first
SPANS = ("dfrs.fetch", "dfrs.dispatch", "dfrs.lam", "dfrs.lp", "dfrs.pad",
         "dfrs.allocate", "dfrs.barrier_wait")


class _Counted:
    """Counts ``BatchedAllocator.allocate_many`` calls and their requests
    from outside the program, as the benchmark's harness wraps it."""

    def __init__(self):
        self.calls, self.opts = [], []
        self._inner = alloc_jax.BatchedAllocator.allocate_many

    def __enter__(self):
        inner, seen = self._inner, self

        def allocate_many(alloc, requests):
            seen.calls.append(len(requests))
            seen.opts.extend(opt for _, _, opt in requests)
            return inner(alloc, requests)

        alloc_jax.BatchedAllocator.allocate_many = allocate_many
        return self

    def __exit__(self, *exc):
        alloc_jax.BatchedAllocator.allocate_many = self._inner
        return False


def _cells(n_jobs=30, seeds=2):
    ws = [WorkloadSpec("lublin", n_jobs=n_jobs, n_nodes=16, seed=s)
          for s in range(seeds)]
    return grid(ws, POLICIES, ["baseline"])


def _outcomes(res):
    return [{k: r[k] for k in ("max_stretch", "mean_stretch", "makespan",
                               "n_pmtn", "n_mig", "events",
                               "trace_fingerprint")}
            for r in res.records]


@pytest.fixture(scope="module")
def sweep_pass():
    cells = _cells()
    before = alloc_jax.lockstep_totals()
    with _Counted() as seen:
        t0 = time.perf_counter()
        got = run_batched(cells)
        t1 = time.perf_counter()
    return {"cells": cells, "got": got, "seen": seen, "t0": t0, "t1": t1,
            "rows": alloc_jax.lockstep_rounds(t0, t1), "before": before,
            "after": alloc_jax.lockstep_totals()}


def test_rows_count_the_rounds_and_requests(sweep_pass):
    rows, seen = sweep_pass["rows"], sweep_pass["seen"]
    served = [r for r in rows if r.requests]
    assert len(served) == len(seen.calls) > 0
    assert [r.requests for r in served] == seen.calls
    assert sum(r.min_requests for r in rows) == seen.opts.count("MIN")
    assert sum(r.lps for r in rows) == seen.opts.count("AVG")
    # the pass's last wait ends with every lane finished: a row of its own
    last = [r for r in rows if not r.requests]
    assert len(last) == 1 and last[0] is rows[-1]
    assert last[0].alloc_t0 == last[0].alloc_t1


def test_timed_parts_lie_inside_the_allocator_call(sweep_pass):
    for r in sweep_pass["rows"]:
        assert sum(getattr(r, k) for k in PARTS) <= r.alloc_s
        assert all(getattr(r, k) >= 0 for k in PARTS)
        if r.min_requests:
            assert r.dispatch_s > 0 and r.fetch_s > 0
        if r.lps:
            assert r.lam_s > 0 and r.lp_s > 0
        if r.requests:
            assert r.pad_s > 0 and r.cells >= r.nnz > 0


def test_wait_and_allocate_fit_the_pass(sweep_pass):
    rows = sweep_pass["rows"]
    wall = sweep_pass["t1"] - sweep_pass["t0"]
    assert sum(r.wait_s + r.alloc_s for r in rows) <= wall
    for r, nxt in zip(rows, rows[1:]):
        assert r.wait_t0 <= r.alloc_t0 <= r.alloc_t1 <= nxt.wait_t0


def test_lane_cpu_and_totals(sweep_pass):
    rows = sweep_pass["rows"]
    assert all(r.lane_cpu_s >= 0 for r in rows)
    assert sum(r.lane_cpu_s for r in rows) > 0
    before, after = sweep_pass["before"], sweep_pass["after"]
    assert after["rounds"] - before["rounds"] == len(rows)
    assert after["requests"] - before["requests"] == sum(
        r.requests for r in rows)
    assert after["lane_cpu_s"] - before["lane_cpu_s"] == pytest.approx(
        sum(r.lane_cpu_s for r in rows))


def test_lp_nonoptimal_in_rows_and_totals(sweep_pass):
    rows, seen = sweep_pass["rows"], sweep_pass["seen"]
    assert sum(r.lps for r in rows) == seen.opts.count("AVG") > 0
    assert all(r.lp_nonoptimal == 0 for r in rows)
    before, after = sweep_pass["before"], sweep_pass["after"]
    assert "lp_nonoptimal" in after
    assert after["lp_nonoptimal"] - before["lp_nonoptimal"] == 0
    assert after["lps"] - before["lps"] == seen.opts.count("AVG")


def test_answers_unchanged(sweep_pass):
    ref = run_grid(sweep_pass["cells"])
    assert _outcomes(sweep_pass["got"]) == _outcomes(ref)


def test_calls_outside_a_round_add_no_row():
    t0 = time.perf_counter()
    inc = build_csr(np.full(4, 0.25), [[0]] * 4, 2)
    alloc_jax.BatchedAllocator().allocate_many([(inc, np.arange(4), "MIN")])
    assert alloc_jax.lockstep_rounds(t0, time.perf_counter()) == []


def test_ring_stays_bounded(monkeypatch):
    monkeypatch.setattr(roundlog, "LOG", roundlog.RoundLog(capacity=5))
    run_batched(_cells(n_jobs=15, seeds=1))
    assert len(alloc_jax.lockstep_rounds(-math.inf, math.inf)) == 5
    assert alloc_jax.lockstep_totals()["rounds"] > 5


def test_round_log_totals_under_contention():
    log = roundlog.RoundLog(capacity=64)
    row = roundlog.Round(0.0, 1.0, 2.0, 3, 1, 8, 4, *[0.0] * 5, 1, 0.5)
    threads = [threading.Thread(
        target=lambda: [log.append(row) for _ in range(500)])
        for _ in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    totals = log.totals()
    assert totals["rounds"] == 8000 and totals["requests"] == 24000
    assert totals["lps"] == 8000 and totals["wait_s"] == 8000.0
    assert len(log.rounds(-math.inf, math.inf)) == 64


def test_race_rounds_and_answers():
    ses = api.open_session(16, "GreedyP */OPT=MIN")
    ses.submit(api.parse_workload("lublin", n_jobs=40, n_nodes=16, seed=3))
    ses.step_until(3000.0)
    snap = ses.snapshot()
    args = ([Variant("GreedyPM */per/OPT=MIN/MINVT=600")],
            Variant("GreedyP */OPT=MIN"))
    with _Counted() as seen:
        t0 = time.perf_counter()
        got = race(snap, *args, base_horizon=1500.0, rungs=2, backend="jax")
        t1 = time.perf_counter()
    rows = alloc_jax.lockstep_rounds(t0, t1)
    assert [r.requests for r in rows if r.requests] == seen.calls
    assert len(seen.calls) > 0
    # one barrier per rung, each ending with a wait that serves nothing
    assert sum(not r.requests for r in rows) == 2
    ref = race(snap, *args, base_horizon=1500.0, rungs=2)
    assert got.winner.key() == ref.winner.key()
    assert [g["scores"] for g in got.rungs] == [
        r["scores"] for r in ref.rungs]


def test_profiler_trace_carries_the_nested_spans(tmp_path):
    import jax

    from chipbench import tracing

    cells = _cells(n_jobs=12, seeds=1)
    run_batched(cells)                  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_batched(cells)
    finally:
        jax.profiler.stop_trace()
    events = tracing.read_events(tracing.find_xplane(str(tmp_path)))
    named = {name: [e for e in events if e.name == name] for name in SPANS}
    assert all(named[name] for name in SPANS), {
        k: len(v) for k, v in named.items()}

    def inside(ev, outer):
        return any(o.line == ev.line and o.start_ns <= ev.start_ns
                   and ev.end_ns <= o.end_ns for o in named[outer])

    for inner in ("dfrs.fetch", "dfrs.dispatch", "dfrs.lam", "dfrs.lp",
                  "dfrs.pad"):
        assert all(inside(ev, "dfrs.allocate") for ev in named[inner])
    assert not any(inside(ev, "dfrs.allocate")
                   for ev in named["dfrs.barrier_wait"])
    # the reduction labels each gap by the innermost span that covers it;
    # on the CPU these small programs run on the calling thread, so each
    # execution stands for the device's busy time
    s = tracing.summarize(
        events, SPANS,
        is_op=lambda ev: ev.name == "PjRtCpuExecutable::Execute",
        is_program=lambda ev: False)
    assert s.busy_s > 0
    assert s.gap_totals.get("dfrs.barrier_wait", 0) > 0
    assert s.gap_totals.get("dfrs.lp", 0) > 0
