"""The traces the cells run, generated here from a configuration and a seed.

A configuration's ``kind`` names a generator module,
``chipbench/workloads/<kind>.py``, with ``generate(n_jobs, n_nodes, seed,
**params)`` returning the trace as columns and ``PARAMS`` naming the
configuration keys it reads.  The program runs the trace through a
workload kind of its own, ``chipbench-<kind>``, registered on first use,
so the traffic belongs to the benchmark and not to the program's own
generators.
"""
from __future__ import annotations

import importlib
from typing import Dict

import numpy as np


def generator(kind: str):
    """The generator module of a workload kind."""
    try:
        return importlib.import_module(f"chipbench.workloads.{kind}")
    except ModuleNotFoundError:
        raise ValueError(f"no generator for workload kind {kind!r}") from None


def offered_load(cols: Dict[str, np.ndarray], n_nodes: int) -> float:
    """The trace's CPU work over the cluster's capacity through its span
    of releases."""
    work = float((cols["n_tasks"] * cols["proc_time"] * cols["cpu_need"]).sum())
    span = max(float(cols["release"].max() - cols["release"].min()), 1.0)
    return work / (n_nodes * span)


def columns(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """The trace ``seed`` of a configuration."""
    gen = generator(config["kind"])
    return gen.generate(config["n_jobs"], config["n_nodes"], seed,
                        **{k: config[k] for k in gen.PARAMS})


def spec(api, config: dict, seed: int):
    """The program's ``WorkloadSpec`` for trace ``seed`` of a configuration,
    generated by its kind's ``generate``."""
    gen = generator(config["kind"])
    kind = f"chipbench-{config['kind']}"
    if kind not in api.list_workloads():
        def make(ws):
            return api.Trace(**gen.generate(
                ws.n_jobs, ws.n_nodes, ws.seed,
                **{k: ws.param(k) for k in gen.PARAMS}))

        api.register_workload(kind, params=gen.PARAMS,
                              doc=f"chipbench's {config['kind']} traces")(make)
    return api.WorkloadSpec(kind, n_jobs=config["n_jobs"],
                            n_nodes=config["n_nodes"], seed=seed,
                            params={k: config[k] for k in gen.PARAMS})
