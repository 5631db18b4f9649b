"""A plain DFRS simulator: the reference for a sweep cell's outcome record.

Independent of the program: it imports nothing of it and takes only the
trace (release, processing time, tasks, CPU need, memory need per job) and
a policy name, and simulates the paper's fluid model (§5.1) from first
principles:

* a running job progresses at its yield (virtual time grows by ``y dt``)
  and completes when its virtual time reaches its processing time; a job
  resumed or moved makes no progress for the rescheduling penalty;
* on submission ``Greedy`` puts each task on the least-loaded node with
  room in memory; ``GreedyP`` pauses running jobs, lowest priority first,
  until the new job fits, then resumes those it can; ``GreedyPM`` moves
  the paused ones by ``Greedy`` where it can (§4.2);
* ``*``: on completions, waiting jobs are placed by ``Greedy`` in order of
  priority (flow time over virtual time squared, §4.1);
* ``/per``: every period MCB8 repacks every job in the system: the
  largest uniform yield, to 0.01, at which the two-list vector packing
  places them, dropping the lowest-priority jobs while none fits; a job
  under ``MINVT`` seconds of virtual time keeps its nodes if it runs
  (§4.3);
* after every event the yields of the running jobs are recomputed, max-min
  fair (OPT=MIN) or sum-optimal above the floor (OPT=AVG), by
  :mod:`chipbench.reference` (§4.6).

One task is placed at a time, every load and free-memory figure is the
running sum of what was placed and removed, and ties go to the lowest node
and the earliest job.  :func:`simulate` returns the outcome fields of a
sweep record; :func:`forks` and :func:`race` make the autotuner's decision
from a fork of the simulator's own run, as a race record holds it.
"""
from __future__ import annotations

import copy
import math
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from . import reference

EPS = 1e-9          # time and remaining-work tolerance of the event loop
MEM_EPS = 1e-12     # a node has room for a task within this much memory
PACK_EPS = 1e-9     # MCB8's capacity tolerance
Y_STEP = 0.01       # MCB8's smallest yield and the accuracy of its search

PENDING, RUNNING, PAUSED, DONE = "pending", "running", "paused", "done"


class Policy:
    """The parts of a policy name ``Submit[ *][/per]/OPT=X[/MINVT=s]``."""

    def __init__(self, name: str):
        parts = [p.strip() for p in name.split("/") if p.strip()]
        head = parts[0]
        self.opportunistic = head.endswith("*")
        self.submit = head.rstrip("*").strip().lower()
        if self.submit not in ("greedy", "greedyp", "greedypm"):
            raise ValueError(f"the reference does not simulate {name!r}")
        self.periodic = False
        self.opt = "MIN"
        self.minvt: Optional[float] = None
        for p in parts[1:]:
            low = p.lower()
            if low == "per":
                self.periodic = True
            elif low.startswith("opt="):
                self.opt = p.split("=", 1)[1].strip().upper()
            elif low.startswith("minvt="):
                self.minvt = float(p.split("=", 1)[1])
            else:
                raise ValueError(f"the reference does not simulate {p!r}")
        if self.opt not in ("MIN", "AVG"):
            raise ValueError(f"the reference does not simulate OPT={self.opt}")


class Cluster:
    """Per-node CPU load (sum of resident needs) and free memory."""

    def __init__(self, n_nodes: int):
        self.load = [0.0] * n_nodes
        self.mem = [1.0] * n_nodes

    def copy(self) -> "Cluster":
        c = Cluster(0)
        c.load, c.mem = list(self.load), list(self.mem)
        return c

    def put(self, c: float, m: float, nodes) -> None:
        for n in nodes:
            self.load[n] += c
            self.mem[n] -= m
        if min(self.mem) < -EPS:
            raise RuntimeError("node memory oversubscribed")

    def take(self, c: float, m: float, nodes) -> None:
        for n in nodes:
            self.load[n] -= c
            self.mem[n] += m

    def greedy(self, c: float, m: float, tasks: int) -> Optional[List[int]]:
        """Each task on the least-loaded node with room; None (and nothing
        placed) when a task finds no room."""
        nodes: List[int] = []
        for _ in range(tasks):
            best, best_load = -1, math.inf
            for n, (ld, mf) in enumerate(zip(self.load, self.mem)):
                if mf >= m - MEM_EPS and ld < best_load:
                    best, best_load = n, ld
            if best < 0:
                self.take(c, m, nodes)
                return None
            nodes.append(best)
            self.load[best] += c
            self.mem[best] -= m
        return nodes

    def fits(self, c: float, m: float, tasks: int) -> bool:
        nodes = self.greedy(c, m, tasks)
        if nodes is None:
            return False
        self.take(c, m, nodes)
        return True


def _pack(n_nodes: int, items, pinned) -> Optional[Dict[int, List[int]]]:
    """MCB8's two-list packing.  ``items`` are ``(job, cpu, mem, tasks)``;
    ``pinned`` are ``(job, cpu, mem, nodes)`` that keep their nodes.  Node
    by node, a task is drawn from the list that goes against the node's
    imbalance (memory-intensive when more memory than CPU is free), the
    first in the list that fits, and from the other list when none does."""
    cpu = [1.0] * n_nodes
    mem = [1.0] * n_nodes
    out: Dict[int, List[int]] = {}
    for j, c, m, nodes in pinned:
        for n in nodes:
            cpu[n] -= c
            mem[n] -= m
        out[j] = list(nodes)
    if min(cpu) < -PACK_EPS or min(mem) < -PACK_EPS:
        return None
    lists = ([it for it in items if it[1] > it[2]],     # CPU-intensive
             [it for it in items if it[1] <= it[2]])    # memory-intensive
    for lst in lists:
        lst.sort(key=lambda it: (-max(it[1], it[2]), it[0]))
    left = [[it[3] for it in lst] for lst in lists]
    for j, _, _, _ in items:
        out[j] = []
    remaining = sum(it[3] for it in items)
    for n in range(n_nodes):
        while remaining:
            first = 1 if mem[n] > cpu[n] else 0
            placed = False
            for li in (first, 1 - first):
                for k, (j, c, m, _) in enumerate(lists[li]):
                    if (left[li][k] and c <= cpu[n] + PACK_EPS
                            and m <= mem[n] + PACK_EPS):
                        left[li][k] -= 1
                        cpu[n] -= c
                        mem[n] -= m
                        out[j].append(n)
                        remaining -= 1
                        placed = True
                        break
                if placed:
                    break
            if not placed:
                break
        if not remaining:
            break
    return None if remaining else out


class Sim:
    """One trace under one policy on ``n_nodes`` nodes."""

    def __init__(self, jobs: Dict[str, np.ndarray], policy: str,
                 n_nodes: int, penalty: float = 300.0, period: float = 600.0,
                 node_mem_gb: float = 8.0, tau: float = 10.0):
        order = np.lexsort((jobs["jid"], jobs["release"]))
        self.jid = [int(x) for x in np.asarray(jobs["jid"])[order]]
        self.rel = [float(x) for x in np.asarray(jobs["release"])[order]]
        self.p = [float(x) for x in np.asarray(jobs["proc_time"])[order]]
        self.k = [int(x) for x in np.asarray(jobs["n_tasks"])[order]]
        self.c = [float(x) for x in np.asarray(jobs["cpu_need"])[order]]
        self.m = [float(x) for x in np.asarray(jobs["mem_req"])[order]]
        n = len(self.jid)
        self.pol = Policy(policy)
        self.n_nodes, self.penalty, self.period = n_nodes, penalty, period
        self.gb, self.tau = node_mem_gb, tau
        self.status = [None] * n
        self.vt = [0.0] * n
        self.y = [0.0] * n
        self.pen = [-math.inf] * n
        self.nodes: List[Optional[List[int]]] = [None] * n
        self.done_at = [math.nan] * n
        self.cluster = Cluster(n_nodes)
        self.now = 0.0
        self.n_pmtn = self.n_mig = 0
        self.moved_gb = 0.0
        self.util = self.demand = 0.0
        self.events = 0
        self.next_arrival = 0
        self.tick = (self.rel[0] + period if (self.pol.periodic and n)
                     else math.inf)
        self.exhausted = False

    # ---- who is where ----------------------------------------------------
    def _with(self, *states) -> List[int]:
        return [i for i, s in enumerate(self.status) if s in states]

    def _priority(self, i: int):
        vt = self.vt[i]
        prio = math.inf if vt <= 0.0 else (self.now - self.rel[i]) / (vt * vt)
        return (prio, -self.jid[i])

    # ---- transitions -----------------------------------------------------
    def _start(self, i: int, nodes: List[int]) -> None:
        self.cluster.put(self.c[i], self.m[i], nodes)
        if self.status[i] == PAUSED:
            self.pen[i] = self.now + self.penalty
            self.moved_gb += self.k[i] * self.m[i] * self.gb
        self.status[i] = RUNNING
        self.nodes[i] = list(nodes)

    def _pause(self, i: int) -> None:
        self.cluster.take(self.c[i], self.m[i], self.nodes[i])
        self.status[i], self.nodes[i], self.y[i] = PAUSED, None, 0.0
        self.n_pmtn += 1
        self.moved_gb += self.k[i] * self.m[i] * self.gb

    def _move(self, pairs) -> None:
        """Move running jobs together: every old placement is freed before
        any new one is taken."""
        for i, _ in pairs:
            self.cluster.take(self.c[i], self.m[i], self.nodes[i])
        for i, nodes in pairs:
            old, new = Counter(self.nodes[i]), Counter(nodes)
            tasks = self.k[i] - sum(min(v, new[n]) for n, v in old.items())
            self.cluster.put(self.c[i], self.m[i], nodes)
            self.nodes[i] = list(nodes)
            if tasks:
                self.n_mig += 1
                self.pen[i] = self.now + self.penalty
                self.moved_gb += 2.0 * tasks * self.m[i] * self.gb

    def _complete(self, i: int) -> None:
        self.cluster.take(self.c[i], self.m[i], self.nodes[i])
        self.status[i], self.nodes[i], self.y[i] = DONE, None, 0.0
        self.done_at[i] = self.now

    # ---- policy ----------------------------------------------------------
    def _submit(self, i: int) -> None:
        kind = self.pol.submit
        c, m, k = self.c[i], self.m[i], self.k[i]
        if kind == "greedy":
            nodes = self.cluster.copy().greedy(c, m, k)
            if nodes is not None:
                self._start(i, nodes)
            return
        trial = self.cluster.copy()
        nodes = trial.greedy(c, m, k)
        paused: List[int] = []
        moved = []
        if nodes is None:
            running = sorted(self._with(RUNNING), key=self._priority)
            marked = []
            for j in running:
                trial.take(self.c[j], self.m[j], self.nodes[j])
                marked.append(j)
                if trial.fits(c, m, k):
                    break
            else:
                return          # pausing every running job would not do
            for j in sorted(marked, key=self._priority, reverse=True):
                trial.put(self.c[j], self.m[j], self.nodes[j])
                if not trial.fits(c, m, k):
                    trial.take(self.c[j], self.m[j], self.nodes[j])
                    paused.append(j)
            paused.sort(key=self._priority)
            nodes = trial.greedy(c, m, k)
            if kind == "greedypm":
                stay = []
                for j in sorted(paused, key=self._priority, reverse=True):
                    new = trial.greedy(self.c[j], self.m[j], self.k[j])
                    if new is None:
                        stay.append(j)
                    else:
                        moved.append((j, new))
                paused = stay
        for j in paused:
            self._pause(j)
        self._move(moved)
        self._start(i, nodes)

    def _on_completions(self) -> None:
        if not self.pol.opportunistic:
            return
        waiting = sorted(self._with(PENDING, PAUSED), key=self._priority,
                         reverse=True)
        for i in waiting:
            nodes = self.cluster.copy().greedy(self.c[i], self.m[i], self.k[i])
            if nodes is not None:
                self._start(i, nodes)

    def _mcb8(self) -> None:
        jobs = sorted(self._with(PENDING, RUNNING, PAUSED), key=self._priority)
        if not jobs:
            return
        minvt = self.pol.minvt
        pins = {i for i in jobs if self.status[i] == RUNNING
                and minvt is not None and self.vt[i] < minvt}

        def pack(y: float, drop: int):
            keep = jobs[drop:]
            cpu = [min(1.0, self.c[i] * y) for i in keep]
            items = [(i, cu, self.m[i], self.k[i])
                     for i, cu in zip(keep, cpu) if i not in pins]
            pinned = [(i, cu, self.m[i], self.nodes[i])
                      for i, cu in zip(keep, cpu) if i in pins]
            return _pack(self.n_nodes, items, pinned)

        drop = 0
        best = pack(Y_STEP, 0)
        while best is None:           # drop the lowest priority, one by one
            drop += 1
            best = pack(Y_STEP, drop)
        if drop < len(jobs):
            full = pack(1.0, drop)
            if full is not None:
                best = full
            else:
                lo, hi = Y_STEP, 1.0
                while hi - lo > Y_STEP:
                    mid = 0.5 * (lo + hi)
                    got = pack(mid, drop)
                    if got is None:
                        hi = mid
                    else:
                        best, lo = got, mid
        moves, starts = [], []
        for i in sorted(jobs):
            new = best.get(i)
            if self.status[i] == RUNNING:
                if new is None:
                    self._pause(i)
                elif Counter(new) != Counter(self.nodes[i]):
                    moves.append((i, new))
            elif new is not None:
                starts.append((i, new))
        self._move(moves)
        for i, nodes in starts:
            self._start(i, nodes)

    def _yields(self) -> None:
        run = self._with(RUNNING)
        if not run:
            return
        a = np.zeros((self.n_nodes, len(run)))
        for col, i in enumerate(run):
            for n in self.nodes[i]:
                a[n, col] += self.c[i]
        y = reference.maxmin(a) if self.pol.opt == "MIN" else reference.avg(a)
        for col, i in enumerate(run):
            self.y[i] = float(y[col])

    # ---- time ------------------------------------------------------------
    def _next_completion(self) -> float:
        best = math.inf
        for i in self._with(RUNNING):
            if self.y[i] > EPS:
                t = (max(self.now, self.pen[i])
                     + (self.p[i] - self.vt[i]) / self.y[i])
                best = min(best, t)
        return best

    def _finished(self) -> List[int]:
        out = []
        for i in self._with(RUNNING):
            if self.y[i] <= EPS:
                continue
            rem = self.p[i] - self.vt[i]
            if (rem <= EPS or max(self.now, self.pen[i]) + rem / self.y[i]
                    <= self.now):
                out.append(i)
        return out

    def _advance(self, t: float) -> None:
        """Progress and the utilization integrals from now to ``t``; the
        used capacity changes where a penalty ends inside the step."""
        if t <= self.now:
            return
        run = self._with(RUNNING)
        demand = sum(self.k[i] * self.c[i]
                     for i in self._with(PENDING, RUNNING, PAUSED))
        cuts = sorted({self.now, t} | {self.pen[i] for i in run
                                      if self.now < self.pen[i] < t})
        for a, b in zip(cuts[:-1], cuts[1:]):
            used = sum(self.y[i] * self.k[i] * self.c[i] for i in run
                       if self.pen[i] <= a + EPS)
            self.util += used * (b - a)
            self.demand += min(self.n_nodes, demand) * (b - a)
        for i in run:
            dt = max(0.0, t - max(self.now, self.pen[i]))
            self.vt[i] = min(self.p[i], self.vt[i] + self.y[i] * dt)
        self.now = t

    def step_until(self, until: float = math.inf) -> "Sim":
        """Handle every event at or before ``until``; the clock stays at
        the last event handled."""
        n = len(self.jid)
        while not self.exhausted:
            live = any(s in (PENDING, RUNNING, PAUSED) for s in self.status)
            t_arr = self.rel[self.next_arrival] if self.next_arrival < n else math.inf
            t_tick = (self.tick if (live or self.next_arrival < n)
                      else math.inf)
            t = min(t_arr, self._next_completion(), t_tick)
            if t > until and not math.isinf(t):
                break
            self.events += 1            # the last look, at no event, counts
            if math.isinf(t):
                self.exhausted = True
                break
            self._advance(t)
            acted = False
            while True:
                fin = self._finished()
                if not fin:
                    break
                for i in fin:
                    self._complete(i)
                self._on_completions()
                acted = True
            while self.next_arrival < n and self.rel[self.next_arrival] <= self.now + EPS:
                self.status[self.next_arrival] = PENDING
                self._submit(self.next_arrival)
                self.next_arrival += 1
                acted = True
            if self.now + EPS >= self.tick:
                self._mcb8()
                self.tick += self.period
                acted = True
            if acted:
                self._yields()
        return self

    def switch(self, policy: str) -> "Sim":
        """A copy of this run that goes on under ``policy``; a periodic
        pass it brings starts one period after the current clock."""
        other = copy.deepcopy(self)
        pol = Policy(policy)
        if not pol.periodic:
            other.tick = math.inf
        elif math.isinf(other.tick):
            other.tick = other.now + other.period
        other.pol = pol
        other.exhausted = False
        return other

    def record(self) -> Dict[str, float]:
        """The outcome fields so far: stretches of the completed jobs, the
        counters and integrals of the whole run."""
        n = len(self.jid)
        done = [i for i in range(n) if self.status[i] == DONE]
        if self.exhausted and len(done) < n:
            raise RuntimeError("the reference left jobs unfinished")
        stretch = [max(self.done_at[i] - self.rel[i], self.tau) / self.p[i]
                   for i in done]
        last = max((self.done_at[i] for i in done), default=0.0)
        makespan = max(0.0, last - min(self.rel))
        hours = max(makespan / 3600.0, 1e-9)
        work = sum(self.k[i] * self.p[i] * self.c[i] for i in range(n))
        return {
            "max_stretch": max(stretch, default=0.0),
            "mean_stretch": float(np.mean(stretch)) if stretch else 0.0,
            "makespan": makespan,
            "underutilization": (self.demand - self.util) / work,
            "n_pmtn": self.n_pmtn,
            "n_mig": self.n_mig,
            "pmtn_per_job": self.n_pmtn / n,
            "mig_per_job": self.n_mig / n,
            "pmtn_per_hour": self.n_pmtn / hours,
            "mig_per_hour": self.n_mig / hours,
            "bytes_moved_gb": self.moved_gb,
            "bandwidth_gbps": self.moved_gb / max(makespan, 1e-9),
            "events": self.events,
            "final_time": self.now,
        }


def simulate(jobs: Dict[str, np.ndarray], policy: str, n_nodes: int,
             **params) -> Dict[str, float]:
    """The outcome fields of one trace run to its end under ``policy``."""
    return Sim(jobs, policy, n_nodes, **params).step_until().record()


def forks(jobs: Dict[str, np.ndarray], policy: str, n_nodes: int,
          every: float) -> List[Sim]:
    """The run of ``policy`` stopped after every ``every`` seconds of the
    trace (at the last event before each multiple), while work is left."""
    sim = Sim(jobs, policy, n_nodes)
    out: List[Sim] = []
    at = every
    while True:
        sim.step_until(at)
        if sim.exhausted:
            return out
        out.append(copy.deepcopy(sim))
        at += every


def race(fork: Sim, incumbent: str, variants, base_horizon: float,
         rungs: int) -> Dict[str, object]:
    """The autotuner's decision from ``fork``: every variant, the incumbent
    first, runs ``base_horizon * 2**r`` seconds from the fork's clock in
    rung ``r``; the score is the largest stretch of the jobs completed by
    then; after each rung but the last the better half of the challengers
    (at least one) goes on with the incumbent; the best score of the last
    rung wins, the incumbent on a tie."""
    alive = [incumbent] + [v for v in dict.fromkeys(variants)
                           if v != incumbent]
    out: Dict[str, object] = {"rungs": []}
    for r in range(rungs):
        until = fork.now + base_horizon * 2 ** r
        records = []
        for v in alive:
            branch = copy.deepcopy(fork) if v == incumbent else fork.switch(v)
            rec = branch.step_until(until).record()
            del rec["pmtn_per_hour"], rec["mig_per_hour"]
            rec["partial"] = not branch.exhausted
            records.append(rec)
        scores = [rec["max_stretch"] if math.isfinite(rec["max_stretch"])
                  else math.inf for rec in records]
        out["rungs"].append({"variants": list(alive), "scores": scores})
        if r < rungs - 1:
            rank = sorted(range(1, len(alive)), key=lambda i: (scores[i], i))
            keep = sorted(rank[:max(1, math.ceil(len(rank) / 2))])
            alive = [alive[0]] + [alive[i] for i in keep]
    best = min(range(len(alive)), key=lambda i: (scores[i], i != 0, i))
    out["winner"] = alive[best]
    out["records"] = records
    return out
