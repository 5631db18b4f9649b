"""The autotuner's decision: ``tune.race(..., backend="jax")`` per fork.

Set-up runs one trace (its seed is part of the traffic) under the
incumbent policy and forks a snapshot every ``every`` simulated seconds.
The window races the forks in an order drawn from ``--seed`` and ends at
the end of the first whole cycle through them that closes after its
seconds, so every run decides over the same forks: ``race_mean_s`` is the
window's time over its decisions, ``race_pNN_s`` the NN-th percentile of
all of them, where NN is the highest percentile with ten distinct forks
beyond it (a fork raced again in a later cycle counts once).

The check compares a sample of the decisions, drawn from the seed, and the
slowest with the plain DFRS simulator's race from its own run of the
trace to the same fork, and with the program's race on its numpy path.
"""
from __future__ import annotations

import time
import traceback
from typing import Dict, List

import numpy as np

from chipbench import dfrs_reference, stats, verify, workload

from .common import warm_shapes


class Entry:
    def __init__(self, api, config: dict, traffic: dict, seed: int, log):
        self.api, self.config, self.traffic = api, config, traffic
        self.seed, self.log = int(seed), log
        self.snaps: List = []
        self.order: List[int] = []
        self.decisions: List = []      # (fork index, seconds, RaceResult)

    def _race(self, snap, backend):
        from repro.tune import Variant, race

        t = self.traffic
        return race(snap, [Variant(p) for p in t["variants"]],
                    Variant(t["incumbent"]), objective=t["objective"],
                    base_horizon=t["base_horizon"], rungs=t["rungs"],
                    backend=backend)

    def setup(self) -> None:
        c, t = self.config, self.traffic
        ses = self.api.open_session(c["n_nodes"], t["incumbent"])
        ses.submit(workload.spec(self.api, c, t["trace_seed"]))
        at = float(t["every"])
        while True:
            ses.step_until(at)
            if ses.exhausted:
                break
            self.snaps.append(ses.snapshot())
            at += float(t["every"])
        rng = np.random.default_rng([self.seed, 1])
        self.order = [int(i) for i in rng.permutation(len(self.snaps))]
        self.log(f"set-up: {len(self.snaps)} forks every {at / (len(self.snaps) + 1):.0f} s")
        warm_shapes(c["n_nodes"], t["warm"])

    def window(self, seconds: float) -> Dict[str, float]:
        failed = 0
        t0 = time.perf_counter()
        while True:
            for k in self.order:
                ts = time.perf_counter()
                try:
                    res = self._race(self.snaps[k], "jax")
                except Exception:  # noqa: BLE001 — a failed decision counts
                    self.log(traceback.format_exc())
                    res = None
                dt = time.perf_counter() - ts
                self.decisions.append((k, dt, res))
                failed += res is None or any(
                    bool(r.get("quarantined")) for r in res.records)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.log(f"{len(self.decisions)} decisions over {len(self.snaps)} "
                 f"forks: the highest percentile with ten distinct forks "
                 f"beyond it is p{stats.tail_percentile(len(self.snaps))}")
        return {"elapsed_s": elapsed, "attempted": len(self.decisions),
                "failed": failed}

    def end_to_end(self, names, win) -> Dict[str, float]:
        lat = [d[1] for d in self.decisions]
        out = {}
        for name in names:
            if name == "race_mean_s":
                out[name] = win["elapsed_s"] / len(lat)
            elif name.startswith("race_p"):
                out[name] = stats.percentile(lat,
                                             stats.percentile_of_name(name))
            else:
                raise KeyError(f"the race entry does not measure {name!r}")
        return out

    def verify(self, captures, rng) -> Dict[str, float]:
        c, t, v = self.config, self.traffic, self.traffic["verify"]
        if t["objective"] != "max_stretch":
            raise ValueError("the reference races on max_stretch only")
        numbers = verify.yield_gaps(
            verify.sample_requests(captures, v["requests"], rng))
        done = [i for i, d in enumerate(self.decisions) if d[2] is not None]
        if not done:
            return numbers
        pick = set(int(i) for i in rng.choice(
            done, size=min(v["decisions"], len(done)), replace=False))
        pick.add(max(done, key=lambda i: self.decisions[i][1]))
        got = [self.decisions[i][2] for i in sorted(pick)]
        forks = [self.decisions[i][0] for i in sorted(pick)]
        ref_forks = dfrs_reference.forks(
            workload.columns(c, t["trace_seed"]), t["incumbent"],
            c["n_nodes"], float(t["every"]))
        if len(ref_forks) != len(self.snaps):
            numbers["record_ref_gap"] = float("inf")
        else:
            ref = [dfrs_reference.race(ref_forks[k], t["incumbent"],
                                       t["variants"], t["base_horizon"],
                                       t["rungs"]) for k in forks]
            numbers["record_ref_gap"] = verify.race_ref_gap(got, ref)
        witness = [self._race(self.snaps[k], None) for k in forks]
        numbers["record_rel_gap"] = verify.race_gap(got, witness)
        return numbers
