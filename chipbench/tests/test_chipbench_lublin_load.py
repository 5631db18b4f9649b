"""The ``lublin_load`` kind: Lublin traces rescaled to a target offered
load, the same traces the program's own ``lublin`` kind gives with
``load=``, bit for bit."""
import numpy as np
import pytest

from chipbench import workload
from chipbench.workloads import lublin, lublin_load
from repro.workloads.registry import WorkloadSpec, make_trace_ir

SIZES = [(32, 100), (128, 1000)]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n_nodes,n_jobs", SIZES)
def test_traces_equal_the_program_lublin_kind(n_nodes, n_jobs, seed):
    cols = lublin_load.generate(n_jobs, n_nodes, seed, 450.0, 0.9)
    trace = make_trace_ir(WorkloadSpec("lublin", n_jobs=n_jobs,
                                       n_nodes=n_nodes, seed=seed, load=0.9))
    assert set(cols) == set(lublin.COLUMNS)
    for name in lublin.COLUMNS:
        ours, theirs = cols[name], getattr(trace, name)
        assert ours.dtype == theirs.dtype, name
        assert np.array_equal(ours, theirs), name


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n_nodes,n_jobs", SIZES)
def test_offered_load_is_the_target(n_nodes, n_jobs, seed):
    cols = lublin_load.generate(n_jobs, n_nodes, seed, 450.0, 0.9)
    assert abs(workload.offered_load(cols, n_nodes) - 0.9) <= 1e-12
    base = lublin.generate(n_jobs, n_nodes, seed, 450.0)
    # only the releases move, and they keep their order and first value
    for name in ("jid", "proc_time", "n_tasks", "cpu_need", "mem_req"):
        assert np.array_equal(cols[name], base[name])
    assert cols["release"][0] == base["release"][0]
    assert np.all(np.diff(cols["release"]) > 0)


def test_configuration_runs_the_kind():
    from chipbench import bench

    cell = bench.Cell("lublin.scaled-sweep")
    assert cell.config["kind"] == "lublin_load"
    assert workload.generator("lublin_load").PARAMS == (
        "mean_interarrival_s", "target_load")
    cols = workload.columns(cell.config, 0)
    assert len(cols["jid"]) == cell.config["n_jobs"] == 1000
    assert abs(workload.offered_load(cols, cell.config["n_nodes"])
               - cell.config["target_load"]) <= 1e-12
