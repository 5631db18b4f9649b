"""``lublin.scaled-sweep``: ``correct`` holds for the program and fails for
the control and for each fault planted under the lockstep barrier.

As in ``test_chipbench_correct.py``, the runs skip the harness's look for
a chip and drive the rest of a run on the CPU, on a smaller cluster; the
traces keep the cell's offered load of 0.9, so the lanes carry a backlog
and answers are fractional.
"""
import time

import pytest

from chipbench import bench, control

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SMALL = {"n_nodes": 32, "n_jobs": 100}
BROKEN = ("control", "altered", "half", "stale", "swapped")


def _run(mode, seed=2**31 + 54321, trace=False):
    import jax

    cell = bench.Cell("lublin.scaled-sweep")
    cell.config = dict(cell.config, **SMALL)
    cell.traffic = dict(cell.traffic, trace={"after_s": 0.2, "length_s": 0.5})
    return bench.run_cell(cell, seed, 0.5, trace, jax, FAKE_DEVICE,
                          time.perf_counter(), solver=control.solver(mode),
                          log=lambda msg: None)


def test_program_is_correct():
    out = _run("program")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"min_yield_gap", "record_ref_gap",
                                  "record_rel_gap"}


@pytest.mark.parametrize("mode", BROKEN)
def test_broken_answers_are_not_correct(mode):
    out = _run(mode)
    assert out["correct"] is False, (mode, out["checks"])


def test_traced_run_reads_the_lane_counters():
    out = _run("program", trace=True)
    assert out["correct"] is True
    metrics = out["metrics"]
    for name in ("scan_cpu_pct.sweep", "pack_cpu_pct.sweep",
                 "scanned_per_request.sweep", "lane_cpu_ms.scaled",
                 "barrier_wait_pct.scaled", "lanes_per_round.scaled"):
        assert name in metrics, sorted(metrics)
    assert metrics["scanned_per_request.sweep"]["value"] > 0
    assert 0 < metrics["pack_cpu_pct.sweep"]["value"] < 100
    assert 0 <= metrics["scan_cpu_pct.sweep"]["value"] < 100
    # the CPU has no TPU plane: the device metric is left out, not zero
    assert "device_idle_pct.scaled" not in metrics
