"""Lublin traces rescaled to a target offered load (paper §5.3.2).

The paper stretches or squeezes every Lublin trace's inter-arrival times
by one constant so that the trace offers loads 0.1 to 0.9.  This kind
takes the traces of ``chipbench/workloads/lublin.py`` and rescales each
one's releases so its offered load (``chipbench/workload.py``: CPU work
over the cluster's capacity through the span of releases) is
``target_load``:

    release' = t0 + (release - t0) * load / target_load

with ``t0`` the first release.  The trace's load is summed job by job in
release order, as the program's own ``lublin`` kind with ``load=`` sums it,
so the two give the same trace bit for bit.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import lublin

#: the configuration keys this generator reads besides jobs and nodes
PARAMS = ("mean_interarrival_s", "target_load")


def trace_load(cols: Dict[str, np.ndarray], n_nodes: int) -> float:
    """The offered load, the work summed one job at a time in row order."""
    work = sum(n * p * c for n, p, c in zip(cols["n_tasks"].tolist(),
                                            cols["proc_time"].tolist(),
                                            cols["cpu_need"].tolist()))
    release = cols["release"]
    span = max(float(release.max()) - float(release.min()), 1.0)
    return work / (n_nodes * span)


def generate(n_jobs: int, n_nodes: int, seed: int,
             mean_interarrival_s: float = 450.0,
             target_load: float = 0.9) -> Dict[str, np.ndarray]:
    """One trace as columns, in release order, at ``target_load``."""
    cols = lublin.generate(n_jobs, n_nodes, seed, mean_interarrival_s)
    factor = trace_load(cols, n_nodes) / float(target_load)
    t0 = float(cols["release"].min())
    cols["release"] = t0 + (cols["release"] - t0) * factor
    return cols
