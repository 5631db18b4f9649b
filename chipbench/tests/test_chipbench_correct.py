"""``correct`` holds for the program and fails for the control and for each
fault planted under the lockstep barrier.

These runs skip the harness's look for a chip and drive the rest of a run
on the CPU, at a size a test run can hold; on the chip the same control
runs at the cells' own size (``chipbench/control.py``).
"""
import os
import subprocess
import sys
import time

import pytest

from chipbench import bench, control

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
#: (config, traffic) overrides: small enough for the CPU, and arrivals dense
#: enough that answers are fractional and differ from lane to lane, so that
#: a fault changes what a lane receives (a light trace at this size answers
#: 1.0 nearly everywhere, where a stale or swapped answer is the right one)
LOADED = {"n_nodes": 32, "n_jobs": 100, "mean_interarrival_s": 40.0}
SMALL = {"lublin.min-sweep": (LOADED, {}),
         "lublin.avg-sweep": (LOADED, {}),
         "lublin.tune-race": (LOADED, {"every": 1500.0})}
BROKEN = ("control", "altered", "half", "stale", "swapped")


def _run(name, mode, seed=2**31 + 12345, trace=False):
    import jax

    cell = bench.Cell(name)
    conf, traffic = SMALL[name]
    cell.config = dict(cell.config, **conf)
    cell.traffic = dict(cell.traffic, trace={"after_s": 0.2, "length_s": 0.5},
                        **traffic)
    return bench.run_cell(cell, seed, 0.5, trace, jax, FAKE_DEVICE,
                          time.perf_counter(), solver=control.solver(mode),
                          log=lambda msg: None)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_is_correct(name):
    out = _run(name, "program")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("mode", BROKEN)
@pytest.mark.parametrize("name", sorted(SMALL))
def test_broken_answers_are_not_correct(name, mode):
    out = _run(name, mode)
    assert out["correct"] is False, (mode, out["checks"])


def test_traced_run_reports_per_layer_metrics():
    out = _run("lublin.min-sweep", "program", trace=True)
    assert out["correct"] is True
    names = set(out["metrics"])
    assert {"lanes_per_round.sweep", "alloc_share_pct.sweep",
            "alloc_round_ms.sweep", "pad_fill_pct.sweep"} <= names
    # the CPU has no TPU plane: device metrics are left out, not zero
    assert not any(n.startswith(("device_idle", "solve_")) for n in names)


def test_off_chip_run_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(bench.HERE, "run.py"),
                        "--workload", "lublin.min-sweep", "--seed",
                        str(2**32 + 7), "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_unknown_mode():
    with pytest.raises(ValueError):
        control.solver("nothing")
