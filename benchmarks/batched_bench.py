"""Batched-sweep benchmark → machine-readable BENCH_batched.json.

Runs an N-seed grid (one workload family × one allocating policy × N
seeds) through serial numpy ``run_grid`` and the lockstep JAX backend
``run_batched`` — the latter twice, splitting *cold* (jit trace + XLA
compile) from *warm* (cached executable) cells/s — and records the
throughputs plus a per-cell parity check: every cell's mean/max stretch
must be *exactly* equal across the two paths (the backend's contract is
bit-identity under x64, stronger than the 1e-9 relative tolerance the
acceptance criterion asks for).  The lane keeps JAX's persistent
compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says, or else in the
checkout's ``.jax_cache``, so re-invocations skip XLA compilation.

CLI (used by the CI jax-smoke job)::

    PYTHONPATH=src python -m benchmarks.batched_bench --cells 8 \
        --jobs 40 --nodes 16 --matvec interpret

Exits non-zero on a parity mismatch only — throughput is recorded, never
gated (the batched path is compile-dominated at smoke scale; its win is
amortizing one jitted program over many lanes on an accelerator).
"""
from __future__ import annotations

import argparse
import json
import platform
import sys

from repro.core.alloc_jax import MATVECS
from repro.sched.sweep import grid, run_batched, run_grid
from repro.workloads.registry import WorkloadSpec

from .common import Bench

BENCH_JSON = "BENCH_batched.json"
POLICY = "GreedyP */OPT=MIN"


def run(bench: Bench, verbose: bool = True, n_cells: int = 100,
        n_jobs: int = 25, n_nodes: int = 8, matvec: str = "jnp") -> dict:
    """One seeded grid through both sweep paths; parity + throughput.

    The batched pass runs *twice*: the first (cold) pays jit tracing +
    XLA compilation — or a persistent-cache read when the compilation cache
    is warm from an earlier process — while the second (warm) hits the
    in-process executable cache and measures pure lockstep throughput.
    Both are recorded; compile amortization is the whole point of the
    batched backend, so conflating the two in one number hides it.
    """
    import jax  # noqa: PLC0415 — main() reports a missing jax first

    workloads = [WorkloadSpec("lublin", n_jobs=n_jobs, n_nodes=n_nodes,
                              seed=s) for s in range(n_cells)]
    cells = grid(workloads, [POLICY], ["baseline"])

    res_np = run_grid(cells, compute_bound=False, n_workers=1)
    res_jax = run_batched(cells, compute_bound=False, matvec=matvec)
    res_warm = run_batched(cells, compute_bound=False, matvec=matvec)

    mismatches = [
        {"workload": g["workload"], "seed": g["seed"],
         "jax": [g["mean_stretch"], g["max_stretch"]],
         "numpy": [r["mean_stretch"], r["max_stretch"]]}
        for g, r in zip(res_jax.records, res_np.records)
        if g["mean_stretch"] != r["mean_stretch"]
        or g["max_stretch"] != r["max_stretch"]
    ]
    payload = {
        "bench": "batched",
        "config": {"n_cells": n_cells, "n_jobs": n_jobs, "n_nodes": n_nodes,
                   "policy": POLICY, "matvec": matvec,
                   "compilation_cache_dir":
                       jax.config.jax_compilation_cache_dir},
        "batched_cells_per_sec": round(res_jax.cells_per_sec, 4),
        "batched_wall_s": round(res_jax.wall_s, 3),
        "batched_warm_cells_per_sec": round(res_warm.cells_per_sec, 4),
        "batched_warm_wall_s": round(res_warm.wall_s, 3),
        "numpy_cells_per_sec": round(res_np.cells_per_sec, 4),
        "numpy_wall_s": round(res_np.wall_s, 3),
        "stretch_parity": not mismatches,
        "n_mismatches": len(mismatches),
        "mismatches": mismatches[:10],
        "platform": platform.platform(),
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=1)

    if verbose:
        print(f"== Batched sweep ({n_cells} cells, {POLICY}, "
              f"matvec={matvec}) ==")
        print(f"  numpy 1-worker: {res_np.wall_s:.2f}s = "
              f"{res_np.cells_per_sec:.2f} cells/s")
        print(f"  jax cold:       {res_jax.wall_s:.2f}s = "
              f"{res_jax.cells_per_sec:.2f} cells/s (incl. jit compile)")
        print(f"  jax warm:       {res_warm.wall_s:.2f}s = "
              f"{res_warm.cells_per_sec:.2f} cells/s (executable cached)")
        print(f"  stretch parity: {payload['stretch_parity']} "
              f"({len(mismatches)} mismatches) -> {BENCH_JSON}")
    return payload


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cells", type=int, default=100,
                    help="number of seeds in the grid (default 100)")
    ap.add_argument("--jobs", type=int, default=25)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--matvec", default="jnp", choices=MATVECS)
    ap.add_argument("--no-check-parity", dest="check_parity",
                    action="store_false", default=True,
                    help="record parity but never fail on it")
    args = ap.parse_args()

    from repro.core.alloc_jax import has_jax
    if not has_jax():
        print("jax not installed — the batched bench cannot run",
              file=sys.stderr)
        return 1

    from .common import QUICK

    payload = run(Bench(QUICK), n_cells=args.cells, n_jobs=args.jobs,
                  n_nodes=args.nodes, matvec=args.matvec)
    if args.check_parity and not payload["stretch_parity"]:
        print(f"PARITY MISMATCH: {payload['n_mismatches']} cells diverge "
              f"from the numpy sweep (first: {payload['mismatches'][:1]})",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
