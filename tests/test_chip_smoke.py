"""``chip_smoke.py`` on the CPU: it refuses to run, and its checks hold.

The script itself drives the chip; here its phases run at a small size on
the CPU, and its parity verdicts are checked against hand-made records.
"""
import importlib.util
import json
import os

import pytest

pytest.importorskip("jax")

import jax  # noqa: E402

from repro import api  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_exits_nonzero_without_a_result(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main() == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


def _record(cell, **kw):
    rec = {"cell": cell, "policy": "GreedyP */OPT=MIN", "n_events": 10,
           "max_stretch": 3.0, "mean_stretch": 1.5, "wall_s": 0.1,
           "sim_wall_s": 0.1, "backend": "numpy"}
    rec.update(kw)
    return rec


@pytest.mark.parametrize("device_kw, ok", [
    ({}, True),                                       # bit-equal
    ({"wall_s": 9.0, "backend": "jax"}, True),        # not outcomes
    ({"max_stretch": 3.0 * (1 + 1e-12)}, True),       # continuous, in RTOL
    ({"max_stretch": 3.0 * (1 + 1e-6)}, False),       # continuous, beyond
    ({"n_events": 11}, False),                        # a count: exact
    ({"policy": "GreedyPM */OPT=MIN"}, False),        # a label: exact
])
def test_report_parity(smoke, device_kw, ok):
    ref = [_record(0), _record(1)]
    got = [_record(0), _record(1, **device_kw)]
    assert smoke.report_parity("t", got, ref) is ok


def test_report_parity_missing_record(smoke):
    assert not smoke.report_parity("t", [_record(0)],
                                   [_record(0), _record(1)])


def test_phases_at_small_size_on_cpu(smoke, capsys):
    """Every phase of the smoke run, at a few jobs per trace: the device
    lane on the CPU backend matches the numpy sweep exactly."""
    cells = smoke.sweep_cells(api, lublin_seeds=range(1),
                              hpc2n_seeds=range(1), n_jobs=60)
    snap = smoke.branch_snapshot(api, n_jobs=60, after_jobs=20)
    assert smoke.run(jax, api, cells, snap, "cpu")
    out = capsys.readouterr().out
    assert "16/16 lanes bit-equal" in out
    assert "0 quarantined" in out and "DIVERGED" not in out
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())
