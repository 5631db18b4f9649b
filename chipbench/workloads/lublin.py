"""Lublin–Feitelson traces (JPDC 2003) with the paper's §5.3.2 augmentation.

* job sizes: serial with probability 0.244, else two-stage log-uniform
  (0.8, 4.5, log2 N; 0.86) rounded to a power of two with probability 0.78;
* runtimes: hyper-gamma on log2 of the runtime, Gamma(4.2, 0.94) or
  Gamma(312, 0.03), the first with probability -0.0054 * size + 0.78,
  clipped to 1 s .. 6 days;
* arrivals: exponential gaps of ``mean_interarrival_s`` seconds, divided by
  a daily cycle ``1.6 + 0.6 sin(2 pi day_fraction - pi / 2)`` (the rate
  peaks at midday); Lublin's own gamma arrival model is not used;
* quad-core nodes: a one-task job needs 0.25 of a node's CPU, every task of
  a wider job needs 1.0;
* memory (Setia et al.): 10 % of a node for 55 % of the jobs, else 10 x %
  with x uniform over 2..10.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: the configuration keys this generator reads besides jobs and nodes
PARAMS = ("mean_interarrival_s",)
COLUMNS = ("jid", "release", "proc_time", "n_tasks", "cpu_need", "mem_req")


def _size(rng, n_nodes: int) -> int:
    if rng.random() < 0.244:
        return 1
    uhi = np.log2(n_nodes)
    umed = min(4.5, max(0.8, uhi - 2.5))
    if rng.random() <= 0.86:
        u = rng.uniform(0.8, umed)
    else:
        u = rng.uniform(umed, uhi)
    if rng.random() <= 0.78:
        size = 2 ** int(round(u))
    else:
        size = int(round(2 ** u))
    return int(np.clip(size, 1, n_nodes))


def _runtime(rng, size: int) -> float:
    p = float(np.clip(-0.0054 * size + 0.78, 0.0, 1.0))
    if rng.random() <= p:
        lg = rng.gamma(4.2, 0.94)
    else:
        lg = rng.gamma(312.0, 0.03)
    return float(np.clip(2.0 ** lg, 1.0, 6 * 86400.0))


def generate(n_jobs: int, n_nodes: int, seed: int,
             mean_interarrival_s: float = 450.0) -> Dict[str, np.ndarray]:
    """One trace as columns, in release order."""
    rng = np.random.default_rng(seed)
    rows = []
    t = 0.0
    for jid in range(n_jobs):
        gap = rng.exponential(mean_interarrival_s)
        phase = 2 * np.pi * ((t / 86400.0) % 1.0)
        gap *= 1.0 / (1.0 + 0.6 * np.sin(phase - np.pi / 2) + 0.6)
        t += float(gap)
        size = _size(rng, n_nodes)
        proc = _runtime(rng, size)
        cpu = 0.25 if size == 1 else 1.0
        mem = 0.10 if rng.random() < 0.55 else 0.10 * int(rng.integers(2, 11))
        rows.append((jid, t, proc, size, cpu, float(mem)))
    cols = list(zip(*rows))
    dtypes = (np.int64, np.float64, np.float64, np.int64, np.float64,
              np.float64)
    return {name: np.array(col, dtype=dt)
            for name, col, dt in zip(COLUMNS, cols, dtypes)}
