"""A policy study: ``api.run_grid(cells, backend="jax")`` pass after pass.

Every pass runs the traffic's whole grid (its trace seeds x policies x
scenarios), its cells in an order drawn from (``--seed``, pass).  The
window starts no new pass once its seconds are gone, and
``sweep_cells_per_s`` is every cell completed over all the time of the
passes, the last one to its end.  The traces are fixed by the traffic
file, so every run does the same work.

The check compares a sample of the window's records, drawn from the seed,
and the cell with the most events with the plain DFRS simulator and with
the program's serial numpy path.
"""
from __future__ import annotations

import time
import traceback
from typing import Dict, List

import numpy as np

from chipbench import dfrs_reference, verify, workload

from .common import warm_shapes


class Entry:
    def __init__(self, api, config: dict, traffic: dict, seed: int, log):
        self.api, self.config, self.traffic = api, config, traffic
        self.seed, self.log = int(seed), log
        self.grid: List = []
        self.plan: List[tuple] = []     # (trace seed, policy, scenario)
        self.passes: List[list] = []
        self.records: List[list] = []

    def setup(self) -> None:
        api, c, t = self.api, self.config, self.traffic
        specs = [workload.spec(api, c, s) for s in t["trace_seeds"]]
        for w in specs:
            api.make_trace_ir(w)
        self.grid = api.grid(specs, t["policies"], t["scenarios"])
        self.plan = [(s, p, sc) for s in t["trace_seeds"]
                     for p in t["policies"] for sc in t["scenarios"]]
        warm_shapes(c["n_nodes"], t["warm"])

    def _pass_cells(self, index: int) -> list:
        rng = np.random.default_rng([self.seed, index])
        return [int(i) for i in rng.permutation(len(self.grid))]

    def window(self, seconds: float) -> Dict[str, float]:
        attempted = failed = 0
        t0 = time.perf_counter()
        while True:
            order = self._pass_cells(len(self.passes))
            self.passes.append(order)
            cells = [self.grid[i] for i in order]
            ts = time.perf_counter()
            attempted += len(cells)
            try:
                records = self.api.run_grid(cells, backend="jax").records
            except Exception:  # noqa: BLE001 — a failed pass is counted
                self.log(traceback.format_exc())
                records = []
            self.records.append(records)
            failed += sum(bool(r.get("quarantined")) for r in records)
            failed += len(cells) - len(records)
            self.log(f"pass {len(self.passes) - 1}: {len(cells)} cells in "
                     f"{time.perf_counter() - ts:.3f} s")
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        return {"elapsed_s": elapsed, "attempted": attempted,
                "failed": failed}

    def end_to_end(self, names, win) -> Dict[str, float]:
        out = {}
        for name in names:
            if name != "sweep_cells_per_s":
                raise KeyError(f"the sweep entry does not measure {name!r}")
            out[name] = win["attempted"] / win["elapsed_s"]
        return out

    def verify(self, captures, rng) -> Dict[str, float]:
        v = self.traffic["verify"]
        numbers = verify.yield_gaps(
            verify.sample_requests(captures, v["requests"], rng))
        pairs = [(p, i) for p, recs in enumerate(self.records)
                 for i in range(len(recs))]
        if not pairs:
            return numbers
        pick = set(int(k) for k in rng.choice(
            len(pairs), size=min(v["cells"], len(pairs)), replace=False))
        pick.add(max(range(len(pairs)), key=lambda k: self.records[
            pairs[k][0]][pairs[k][1]].get("n_events", 0)))
        chosen = [pairs[k] for k in sorted(pick)]
        got = [self.records[p][i] for p, i in chosen]
        which = [self.passes[p][i] for p, i in chosen]
        ref = []
        for k in which:
            seed, policy, scenario = self.plan[k]
            if scenario != "baseline":
                raise ValueError(f"the reference simulates no {scenario!r}")
            ref.append(dfrs_reference.simulate(
                workload.columns(self.config, seed), policy,
                self.config["n_nodes"]))
        numbers["record_ref_gap"] = verify.outcome_gap(got, ref)
        witness = self.api.run_grid([self.grid[k] for k in which],
                                    n_workers=1).records
        # a record's "cell" is its index in its own grid: pair by order
        for g, r in zip(got, witness):
            r["cell"] = g["cell"]
        numbers["record_rel_gap"] = verify.record_gap(got, witness)
        return numbers
