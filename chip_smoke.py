"""Chip smoke run: the batched allocation lane at the paper's cluster size.

    python chip_smoke.py          # from the repository root, on a TPU host

Drives the system's device path once, end to end, through the entry points
a user calls, in one process (a child could not take the chip this process
holds):

1. lane parity — random padded instances through the jitted lockstep
   water-filling, compared bit for bit with the numpy kernel;
2. the sweep — ``api.run_grid(cells, backend="jax")`` cold, then warm, and
   ``api.run_grid(cells, n_workers=1)`` as the numpy reference, at the
   paper's FULL scale (128 nodes, 1000 jobs per trace,
   ``benchmarks/common.py``): lublin seeds x {GreedyP, GreedyPM/per,
   Greedy OPT=AVG} x baseline, and hpc2n seeds x GreedyP x rack_failure;
3. a what-if branch race — ``api.run_branches(snap, ..., backend="jax")``
   against the same race on numpy (the autotuner's device path).

Every record of the device paths must equal its numpy record on every
simulation outcome field, except that the continuous metrics in
``CONTINUOUS`` may differ by a relative ``RTOL`` where the chip's float64
is not bit-equal to numpy's (the divergence is printed first).  The device
paths run unsupervised and with ``quarantine=False``, so a failed lane
raises and nothing can be quarantined silently.  Earlier lines report
cells, wall seconds (labelled with the device), compiles and parity.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``, printed
only when every check held.  With no TPU the script exits 1 before any
work, and it prints no result.
"""
from __future__ import annotations

import json
import os
import sys
import time

N_NODES, N_JOBS = 128, 1000
LUBLIN_SEEDS = range(3)     # trimmed from 8 and 4 seeds to keep the run to
HPC2N_SEEDS = range(2)      # a few minutes; nodes and jobs are never cut
LUBLIN_POLICIES = ("GreedyP */OPT=MIN", "GreedyPM */per/OPT=MIN/MINVT=600",
                   "Greedy */OPT=AVG")
HPC2N_POLICY = "GreedyP */OPT=MIN"
BRANCH_POLICIES = ("GreedyP */OPT=MIN", "GreedyPM */OPT=MIN",
                   "GreedyPM */per/OPT=MIN/MINVT=600", "Greedy */OPT=AVG")
BRANCH_AFTER_JOBS = 300        # fork once this many jobs have completed
BRANCH_HORIZON_S = 200_000.0   # race horizon past the fork (~2.3 days)
#: record keys that are wall-clock measurements or labels, not outcomes
NOT_OUTCOMES = ("sim_wall_s", "wall_s", "backend")
#: where the chip's float64 is not bit-equal to numpy, these continuous
#: metrics are compared within RTOL; every other outcome field (counts,
#: labels, fingerprints, flags) must still be exactly equal.  Why 1e-9:
#: ARCHITECTURE.md, "Exactness under jit"
CONTINUOUS = ("max_stretch", "mean_stretch", "makespan", "final_time",
              "underutilization", "pmtn_per_hour", "mig_per_hour",
              "bandwidth_gbps")
RTOL = 1e-9


class CompileLog:
    """Counts XLA compiles and persistent-cache hits through jax.monitoring."""

    def __init__(self, jax):
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.compile_s, self.cache_hits

    def since(self, mark):
        c, s, h = mark
        return (f"{self.compiles - c} compiles, "
                f"{self.compile_s - s:.3f} s compiling, "
                f"{self.cache_hits - h} persistent-cache hits")


def sweep_cells(api, lublin_seeds=LUBLIN_SEEDS, hpc2n_seeds=HPC2N_SEEDS,
                n_nodes=N_NODES, n_jobs=N_JOBS):
    lublin = [api.WorkloadSpec("lublin", n_jobs=n_jobs, n_nodes=n_nodes,
                               seed=s) for s in lublin_seeds]
    hpc2n = [api.WorkloadSpec("hpc2n", n_jobs=n_jobs, n_nodes=n_nodes,
                              seed=s) for s in hpc2n_seeds]
    return (api.grid(lublin, LUBLIN_POLICIES, ["baseline"])
            + api.grid(hpc2n, [HPC2N_POLICY], ["rack_failure"]))


def branch_snapshot(api, n_nodes=N_NODES, n_jobs=N_JOBS,
                    after_jobs=BRANCH_AFTER_JOBS):
    """A live mid-run session: lublin seed 0 under GreedyP, forked once
    ``after_jobs`` jobs have completed."""
    ses = api.open_session(n_nodes, "GreedyP */OPT=MIN")
    ses.submit(api.WorkloadSpec("lublin", n_jobs=n_jobs, n_nodes=n_nodes,
                                seed=0))
    while not ses.exhausted and ses.observe()["n_completed"] < after_jobs:
        ses.step(25)
    return ses.snapshot()


def outcome(rec):
    return {k: v for k, v in rec.items() if k not in NOT_OUTCOMES}


def divergences(got, ref):
    """``(cell, key, device value, numpy value)`` for every outcome field
    where a device record differs from its numpy record."""
    out = []
    for g, r in zip(got, ref):
        go, ro = outcome(g), outcome(r)
        for k in sorted(set(go) | set(ro)):
            if go.get(k) != ro.get(k):
                out.append((r["cell"], k, go.get(k), ro.get(k)))
    if len(got) != len(ref):
        out.append((None, "n_records", len(got), len(ref)))
    return out


def rel_diff(a, b):
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return abs(a - b) / max(abs(b), 1e-300)
    return float("inf")


def report_parity(name, got, ref):
    """Print the parity outcome of one device path.  True when every
    outcome field is equal, or when only CONTINUOUS fields differ and
    those by at most RTOL."""
    div = divergences(got, ref)
    if not div:
        print(f"parity {name}: {len(got)}/{len(ref)} records equal the "
              f"numpy records on every outcome field")
        return True
    cells = sorted({d[0] for d in div}, key=str)
    worst = {}
    for _, k, a, b in div:
        worst[k] = max(worst.get(k, 0.0), rel_diff(a, b))
    print(f"parity {name}: DIVERGED in {len(cells)}/{len(ref)} records, "
          f"{len(div)} fields")
    print(f"  first divergent cell {cells[0]}: " + "; ".join(
        f"{k} device={a!r} numpy={b!r}" for c, k, a, b in div
        if c == cells[0]))
    print("  largest relative difference per field: " + ", ".join(
        f"{k}={v!r}" for k, v in sorted(worst.items())))
    beyond = [d for d in div
              if d[1] not in CONTINUOUS or not rel_diff(d[2], d[3]) <= RTOL]
    print(f"  within tolerance (rtol {RTOL:g} on {', '.join(CONTINUOUS)}; "
          f"exact elsewhere): "
          + ("yes" if not beyond else f"NO, {len(beyond)} fields beyond"))
    return not beyond


def lane_parity(seed=0, n_lanes=16, max_width=64):
    """Random incidences at the lane's node count through the jitted
    water-filling, each lane compared bit for bit with ``maxmin_yields_csr``.
    Returns ``(bit-equal lanes, lanes, max |dy|)``."""
    import numpy as np

    from repro.core import alloc_jax
    from repro.core.alloc_kernels import build_csr, maxmin_yields_csr

    rng = np.random.default_rng(seed)
    incs, actives = [], []
    for _ in range(n_lanes):
        width = int(rng.integers(1, max_width + 1))
        running = rng.random(width) < 0.8
        cpu = rng.choice([0.1, 0.25, 0.3, 0.5, 0.7, 1.0], width)
        mappings = [list(rng.integers(0, N_NODES, int(rng.integers(1, 9))))
                    if running[j] else [] for j in range(width)]
        incs.append(build_csr(cpu, mappings, N_NODES))
        actives.append(running)
    present, weight, active = alloc_jax.pad_batch(
        incs, actives, n_nodes=N_NODES, width=max_width)
    y = alloc_jax.maxmin_yields_batch(present, weight, active)
    equal, worst = 0, 0.0
    for b, (inc, act) in enumerate(zip(incs, actives)):
        ref = maxmin_yields_csr(inc, act)
        got = y[b, : inc.width]
        equal += bool(np.array_equal(got, ref))
        worst = max(worst, float(np.max(np.abs(got - ref), initial=0.0)))
    return equal, n_lanes, worst


def run(jax, api, cells, snap, label):
    """Every phase on the current default device; True when all held."""
    log = CompileLog(jax)
    ok = True

    mark = log.mark()
    equal, n, worst = lane_parity()
    print(f"lane parity: {equal}/{n} lanes bit-equal to maxmin_yields_csr, "
          f"max |dy| = {worst!r} ({log.since(mark)})")

    print(f"sweep: {len(cells)} cells at {cells[0].workload.n_nodes} nodes "
          f"x {cells[0].workload.n_jobs} jobs per path")
    t0 = time.perf_counter()
    ref = api.run_grid(cells, n_workers=1)
    print(f"numpy run_grid: {time.perf_counter() - t0:.3f} s host wall")
    passes = {}
    for name in ("cold", "warm"):
        mark = log.mark()
        t0 = time.perf_counter()
        passes[name] = api.run_grid(cells, backend="jax")
        print(f"jax run_grid {name}: {time.perf_counter() - t0:.3f} s wall "
              f"on {label} ({log.since(mark)})")
    for name, res in passes.items():
        quarantined = sum(bool(r.get("quarantined")) for r in res.records)
        print(f"jax {name}: {len(res.records)}/{len(cells)} records, "
              f"{quarantined} quarantined")
        ok &= quarantined == 0 and len(res.records) == len(cells)
        ok &= report_parity(f"jax {name}", res.records, ref.records)

    print(f"branch race: {len(BRANCH_POLICIES)} policies forked at "
          f"t={snap.time:.1f} s, horizon {BRANCH_HORIZON_S:.0f} s")
    t0 = time.perf_counter()
    bref = api.run_branches(snap, BRANCH_POLICIES, horizon_s=BRANCH_HORIZON_S)
    print(f"numpy run_branches: {time.perf_counter() - t0:.3f} s host wall")
    mark = log.mark()
    t0 = time.perf_counter()
    bjax = api.run_branches(snap, BRANCH_POLICIES, horizon_s=BRANCH_HORIZON_S,
                            backend="jax", quarantine=False)
    print(f"jax run_branches: {time.perf_counter() - t0:.3f} s wall on "
          f"{label} ({log.since(mark)})")
    quarantined = sum(bool(r.get("quarantined")) for r in bjax.records)
    print(f"jax branches: {len(bjax.records)}/{len(BRANCH_POLICIES)} "
          f"records, {quarantined} quarantined")
    ok &= quarantined == 0 and len(bjax.records) == len(BRANCH_POLICIES)
    ok &= report_parity("jax branches", bjax.records, bref.records)
    print(f"compiles in all: {log.compiles}, {log.compile_s:.3f} s; "
          f"persistent-cache hits {log.cache_hits}; cache dir "
          f"{jax.config.jax_compilation_cache_dir}")
    return ok


def main() -> int:
    import jax

    print(f"jax {jax.__version__}; devices {jax.devices()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the default device is {dev.platform!r}; this smoke "
              f"run drives the chip and has no CPU path", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro import api

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cells = sweep_cells(api)
    snap = branch_snapshot(api)
    if not run(jax, api, cells, snap, dev.device_kind):
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
