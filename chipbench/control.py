"""The control and the planted faults that ``correct`` must catch.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        --mode program,control,altered,half,stale,swapped

For each mode in turn, each seed runs the cell's set-up, a short window and
the check in this one process, and prints one JSON line with the compared
numbers.  ``program``
is the program as it stands (the lower readings); ``control`` puts the plain
reference, computed in float32, in the allocator's place (the precision one
step below the float64 the configurations state); the others break the
allocator's answers underneath the lockstep barrier:

* ``altered`` — one yield of each round changed by one part in a million;
* ``half``    — half of each round's requests left unsolved (yield 1);
* ``stale``   — a request answered with the previous answer given at its
  place in the round, its state left unchanged;
* ``swapped`` — answers of equal length handed to the wrong lanes.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import bench, reference  # noqa: E402


def _control(alloc, requests, inner):
    return [reference.solve(inc.indptr, inc.indices, inc.data, inc.n_nodes,
                            cols, opt, dtype=np.float32).astype(np.float64)
            for inc, cols, opt in requests]


def _altered(alloc, requests, inner):
    out = inner(alloc, requests)
    for y in out:
        if y.shape[0]:
            y[0] = y[0] * (1.0 - 1e-6)
            break
    return out


def _half(alloc, requests, inner):
    keep = len(requests) - len(requests) // 2
    out = inner(alloc, requests[:keep]) if keep else []
    return out + [np.ones(c.shape[0]) for _, c, _ in requests[keep:]]


class _Stale:
    def __init__(self):
        self.last = {}

    def __call__(self, alloc, requests, inner):
        out = inner(alloc, requests)
        for i, y in enumerate(out):
            old = self.last.get(i)
            self.last[i] = y
            if old is not None and old.shape == y.shape:
                out[i] = old
        return out


def _swapped(alloc, requests, inner):
    out = inner(alloc, requests)
    by_len = {}
    for i, y in enumerate(out):
        by_len.setdefault(y.shape[0], []).append(i)
    for idx in by_len.values():
        if len(idx) > 1:
            ys = [out[i] for i in idx]
            for i, y in zip(idx, ys[1:] + ys[:1]):
                out[i] = y
    return out


def solver(mode: str):
    """The allocator replacement for ``mode``; None runs the program."""
    table = {"program": lambda: None, "control": lambda: _control,
             "altered": lambda: _altered, "half": lambda: _half,
             "stale": _Stale, "swapped": lambda: _swapped}
    if mode not in table:
        raise ValueError(f"unknown mode {mode!r}: {', '.join(table)}")
    return table[mode]()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--mode", default="control",
                    help="one mode or several, comma-separated")
    args = ap.parse_args(argv)
    cell = bench.Cell(args.workload)
    import jax

    try:
        device = bench.accelerator(jax, cell.chips)
    except bench.NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    modes = args.mode.split(",")
    for mode in modes:
        solver(mode)                # an unknown mode fails before any run
    for mode in modes:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = bench.run_cell(cell, seed, args.seconds, False, jax, device,
                                 time.perf_counter(), solver=solver(mode),
                                 log=lambda m: print(m, file=sys.stderr))
            print(json.dumps({"workload": args.workload, "mode": mode,
                              "seed": seed, "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
