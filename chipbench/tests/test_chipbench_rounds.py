"""The readers of the program's round log, on a CPU window with the
harness's ``Probe`` installed, at the size ``test_chipbench_correct.py``
runs (32 nodes x 100 jobs, arrivals every 40 s)."""
import time

import pytest

from chipbench import bench, rounds, stats

LOADED = {"n_nodes": 32, "n_jobs": 100, "mean_interarrival_s": 40.0}
SMALL = {"lublin.min-sweep": {}, "lublin.avg-sweep": {},
         "lublin.tune-race": {"every": 1500.0}}
#: the metrics that read the round log
READERS = ("barrier_wait_pct", "lane_cpu_ms", "lane_parallelism",
           "dispatch_ms", "fetch_ms", "lp_ms")


def _window(name, seed=2**31 + 77):
    """One window of the cell with the harness's spans, and the cell."""
    import jax

    from repro import api
    from repro.core import alloc_jax

    cell = bench.Cell(name)
    cell.config = dict(cell.config, **LOADED)
    cell.traffic = dict(cell.traffic, **SMALL[name])
    entry = bench.load_entry(cell.traffic["entry"])(
        api, cell.config, cell.traffic, seed, lambda msg: None)
    entry.setup()
    probe = bench.Probe(alloc_jax, jax, seed,
                        cell.traffic["verify"]["stride"]).install()
    try:
        t0 = time.perf_counter()
        entry.window(0.5)
        t1 = time.perf_counter()
    finally:
        probe.uninstall()
    return bench.Context(probe.spans, t1 - t0, None, {}), cell


@pytest.fixture(scope="module", params=sorted(SMALL))
def window(request):
    return _window(request.param)


def test_rounds_match_the_harness_spans(window):
    ctx, _ = window
    rows = rounds.window_rounds(ctx)
    served = [r for r in rows if r.requests]
    assert len(served) == len(ctx.spans) > 0
    assert [r.requests for r in served] == [s.n_requests for s in ctx.spans]
    program = sum(r.alloc_s for r in served)
    harness = sum(s.seconds for s in ctx.spans)
    assert harness <= program <= 1.05 * harness


def test_barrier_wait_and_allocator_share_fit_the_window(window):
    ctx, _ = window
    wait = bench.load_metric("barrier_wait_pct.sweep")(ctx)
    alloc = stats.alloc_share_pct(ctx.spans, ctx.window_s)
    assert wait > 0 and alloc > 0
    assert wait + alloc <= 101


def test_every_reader_of_the_cell_reads(window):
    ctx, cell = window
    mine = [m["name"] for m in cell.per_layer
            if m["name"].split(".")[0] in READERS]
    assert mine
    for name in mine:
        v = bench.load_metric(name)(ctx)
        assert v is not None and v > 0, name


def test_a_program_without_the_round_log_reads_nothing(window, monkeypatch):
    from repro.core import alloc_jax

    ctx, _ = window
    monkeypatch.delattr(alloc_jax, "lockstep_rounds")
    for base in READERS:
        assert bench.load_metric(f"{base}.sweep")(ctx) is None
