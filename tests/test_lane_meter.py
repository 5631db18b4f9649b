"""The lanes' meters: resume scans, MCB8 packs, pauses and migrations.

On the lockstep path each lane's engine counts into the lane's meter and
hands the counts to the barrier with its requests; the round log's sums
must be the records' own counts.  On the numpy path the engine keeps the
same counts on a meter of its own and times nothing.
"""
import time
from collections import namedtuple

import pytest

from chipbench import bench
from repro import api
from repro.core import alloc_jax, roundlog
from repro.core.job import PAUSED, PENDING
from repro.sched import components
from repro.sched.engine import Engine, SimParams
from repro.workloads.registry import WorkloadSpec, make_trace_ir

POLICIES = ["GreedyP */per/OPT=MIN/MINVT=600",
            "GreedyPM */per/OPT=MIN/MINVT=600"]
READERS = ("scan_cpu_pct.sweep", "pack_cpu_pct.sweep",
           "scanned_per_request.sweep", "scan_placed_pct.sweep")


def _loaded(seed):
    """32 nodes x 100 jobs at offered load 0.9: a backlog at every
    completion."""
    return WorkloadSpec("lublin", n_jobs=100, n_nodes=32, seed=seed, load=0.9)


@pytest.mark.skipif(not alloc_jax.has_jax(),
                    reason="jax present but not importable")
def test_lockstep_sums_are_the_records_counts():
    cells = api.grid([_loaded(s) for s in (0, 1)], POLICIES, ["baseline"])
    before = alloc_jax.lockstep_totals()
    t0 = time.perf_counter()
    records = api.run_grid(cells, backend="jax").records
    rows = alloc_jax.lockstep_rounds(t0, time.perf_counter())
    after = alloc_jax.lockstep_totals()
    got = {k: after[k] - before[k] for k in roundlog.LANE_FIELDS}
    assert got["pauses"] == sum(r["n_pmtn"] for r in records) > 0
    assert got["migrations"] == sum(r["n_mig"] for r in records) > 0
    assert got["packs"] > 0
    assert got["scanned"] > got["placed"] >= got["started"] > 0
    assert 0 < got["scan_cpu_s"] + got["pack_cpu_s"] <= after["lane_cpu_s"]
    # the rows of the pass carry the same sums
    for field in roundlog.LANE_FIELDS:
        assert sum(getattr(r, field) for r in rows) == pytest.approx(
            got[field])
    # and the records are the numpy path's, count for count
    witness = api.run_grid(cells, n_workers=1).records
    for g, w in zip(records, witness):
        assert (g["n_pmtn"], g["n_mig"], g["max_stretch"]) == (
            w["n_pmtn"], w["n_mig"], w["max_stretch"])


@pytest.mark.skipif(not alloc_jax.has_jax(),
                    reason="jax present but not importable")
def test_branches_count_from_their_fork():
    """A restored branch counts into its lane's meter from the fork on:
    the log's pauses and migrations are what the branches added to the
    snapshot's own."""
    from repro.sched.session import SimSession
    from repro.sched.sweep import run_branches

    ses = SimSession.from_engine(Engine(make_trace_ir(_loaded(1)),
                                        POLICIES[0], SimParams(n_nodes=32)))
    ses.step(150)
    snap = ses.snapshot()
    before = alloc_jax.lockstep_totals()
    records = run_branches(snap, POLICIES, backend="jax").records
    after = alloc_jax.lockstep_totals()
    assert snap.payload["n_pmtn"] > 0
    for field, key in (("pauses", "n_pmtn"), ("migrations", "n_mig")):
        added = sum(r[key] - snap.payload[key] for r in records)
        assert after[field] - before[field] == added > 0


@pytest.mark.parametrize("policy", POLICIES)
def test_numpy_path_counts_what_the_resume_scan_tried(policy, monkeypatch):
    """``scanned`` counts the waiting jobs each scan walked, ``placed``
    those it put through ``greedy_place``: the fit test and failure
    dominance keep the rest from it."""
    walked, tried, started = [0], [0], [0]
    inner_place = components.greedy_place
    inner_scan = components.CompleteGreedy.on_complete
    inside = [False]

    def place(pool, spec):
        mapping = inner_place(pool, spec)
        if inside[0]:
            tried[0] += 1
            started[0] += mapping is not None
        return mapping

    def scan(self):
        walked[0] += sum(j.status in (PENDING, PAUSED)
                         for j in self.p.e.state.uncompleted())
        inside[0] = True
        try:
            inner_scan(self)
        finally:
            inside[0] = False

    monkeypatch.setattr(components, "greedy_place", place)
    monkeypatch.setattr(components.CompleteGreedy, "on_complete", scan)
    e = Engine(make_trace_ir(_loaded(1)), policy, SimParams(n_nodes=32))
    r = e.run()
    assert type(e.meter) is roundlog.Meter
    assert e.meter.scanned == walked[0] > 0
    assert e.meter.placed == tried[0] > 0
    assert e.meter.started == started[0] > 0
    assert e.meter.placed <= e.meter.scanned
    assert e.meter.pauses == r.n_pmtn and e.meter.migrations == r.n_mig
    assert e.meter.packs > 0
    # nothing timed off a lane
    assert e.meter.scan_cpu_s == 0 and e.meter.pack_cpu_s == 0


def test_meter_take_starts_again():
    m = roundlog.LaneMeter()
    m.scanned, m.packs = 3, 1
    with m.clock("dfrs.pack", "pack_cpu_s"):
        sum(range(1000))
    taken = m.take()
    assert dict(zip(roundlog.LANE_FIELDS, taken))["scanned"] == 3
    assert taken[roundlog.LANE_FIELDS.index("pack_cpu_s")] >= 0
    assert m.take() == (0,) * len(roundlog.LANE_FIELDS)
    assert roundlog.meter_for(None) is not roundlog.meter_for(None)


def test_rows_without_the_new_fields_still_read():
    row = roundlog.Round(0.0, 1.0, 2.0, 3, 3, 10, 5, 0.1, 0.2, 0.3, 0.0,
                         0.0, 0, 0.5)
    assert (row.scan_cpu_s, row.scanned, row.placed, row.packs, row.pauses,
            row.migrations) == (0.0, 0, 0, 0, 0, 0)
    log = roundlog.RoundLog()
    log.append(row)
    totals = log.totals()
    assert totals["requests"] == 3 and totals["scanned"] == 0
    assert all(totals[k] == 0 for k in roundlog.LANE_FIELDS)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_an_empty_window(name):
    assert bench.load_metric(name)(bench.Context([], 0.0, None, {})) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_rows_without_the_fields(name, monkeypatch):
    """A program whose round log predates the lane counters reads None."""
    Old = namedtuple("Old", "wait_t0 alloc_t0 alloc_t1 requests lane_cpu_s")
    monkeypatch.setattr(alloc_jax, "lockstep_rounds",
                        lambda t0, t1: [Old(0.0, 0.5, 1.0, 4, 0.2)])
    span = namedtuple("Span", "t0 t1")(0.0, 1.0)
    assert bench.load_metric(name)(bench.Context([span], 1.0, None, {})) \
        is None


def test_placed_share_is_absent_where_rows_only_count_the_walk(monkeypatch):
    """A program whose lanes count `scanned` but not `placed` (the scan
    before the fit test) reads no share, not 0 % or 100 %."""
    Mid = namedtuple("Mid", "wait_t0 alloc_t0 alloc_t1 requests lane_cpu_s "
                            "scanned")
    monkeypatch.setattr(alloc_jax, "lockstep_rounds",
                        lambda t0, t1: [Mid(0.0, 0.5, 1.0, 4, 0.2, 12)])
    span = namedtuple("Span", "t0 t1")(0.0, 1.0)
    read = bench.load_metric("scan_placed_pct.sweep")
    assert read(bench.Context([span], 1.0, None, {})) is None


def test_traced_scaled_sweep_reads_the_placed_share():
    """A traced `lublin.scaled-sweep` run, driven on the CPU on a smaller
    cluster at the cell's offered load of 0.9, reports the share."""
    import jax
    from chipbench import control

    cell = bench.Cell("lublin.scaled-sweep")
    cell.config = dict(cell.config, n_nodes=32, n_jobs=100)
    cell.traffic = dict(cell.traffic, trace={"after_s": 0.2, "length_s": 0.5})
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    out = bench.run_cell(cell, 2**31 + 54321, 0.5, True, jax, device,
                         time.perf_counter(), solver=control.solver("program"),
                         log=lambda msg: None)
    assert out["correct"] is True, out["checks"]
    assert 0 <= out["metrics"]["scan_placed_pct.sweep"]["value"] < 100
