"""The harness: run one cell of ``BENCHMARK.json`` once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a configuration, a traffic mix, an entry or a
per-layer metric is a file of its own that this module finds by name
(``chipbench/README.md``).  A run is: the device check (no TPU, no result),
set-up (``setup_s``: import, device init, the traffic's inputs from the
seed, a warm-up of the cell's own device shapes), the measured window, the
reading of device memory, the check against the reference, and one JSON
line.  With ``--trace 1`` a few seconds of the window are traced with the
JAX profiler and the line carries the per-layer metrics instead.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import stats, tracing, verify

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the traced stretch of a ``--trace 1`` window: it starts ``after_s`` into
#: the window and lasts ``length_s``; a traffic file's ``trace`` key may
#: set either for its cells
TRACE = {"after_s": 3.0, "length_s": 4.0}


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and
    metric definitions resolved from ``BENCHMARK.json``."""

    def __init__(self, name: str, bench_path: Optional[str] = None):
        spec = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"cells: {', '.join(sorted(cells))}")
        w = cells[name]
        self.name = name
        self.chips = int(w["chips"])
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        self.config = load_json(os.path.join(ROOT, conf["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", f"{w['traffic']}.json"))

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]


def accelerator(jax, chips: int) -> Dict[str, object]:
    """The device as JAX reports it; raises :class:`NoChip` off the TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"the default device is {devs[0].platform!r}, not a "
                     f"TPU: this benchmark measures the chip and has no "
                     f"CPU path")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_of(kind: str) -> Dict[str, float]:
    """The published peaks of one chip kind; a kind not in the table is an
    error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["chips"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


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

    def since(self, mark) -> str:
        c, s, h = mark
        return (f"{self.compiles - c} compiles, "
                f"{self.compile_s - s:.3f} s compiling, "
                f"{self.cache_hits - h} persistent-cache hits")


class Tracer:
    """Starts the JAX profiler ``after_s`` into the window and stops it
    ``length_s`` later, from the allocator calls of the thread that serves
    the lockstep barrier."""

    def __init__(self, jax, after_s: float, length_s: float):
        self.jax = jax
        self.after_s, self.length_s = float(after_s), float(length_s)
        self.logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.start_at = math.inf
        self.t0 = self.t1 = None

    @property
    def on(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def arm(self, window_start: float) -> None:
        self.start_at = window_start + self.after_s

    def tick(self, now: float) -> None:
        if self.t0 is None and now >= self.start_at:
            # host events at level 1 keep the harness's annotations; the
            # Python tracer would slow the lanes' loops several times over
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.t0 = time.perf_counter()
        elif self.on and now >= self.t0 + self.length_s:
            self.stop()

    def stop(self) -> None:
        if self.on:
            self.t1 = time.perf_counter()
            self.jax.profiler.stop_trace()

    def close(self) -> None:
        shutil.rmtree(self.logdir, ignore_errors=True)


class _CapturingLane:
    """A lockstep lane as the engine sees it, keeping what it asked and
    what it was answered."""

    __slots__ = ("_lane", "_capture")

    def __init__(self, lane, capture: verify.Capture):
        self._lane, self._capture = lane, capture

    def allocate(self, inc, cols, opt="MIN"):
        y = self._lane.allocate(inc, cols, opt)
        if cols.shape[0]:
            self._capture.add(inc, cols, opt, y)
        return y


class Probe:
    """The harness's hooks on the program's lockstep path.

    * a span around each ``BatchedAllocator.allocate_many`` call from the
      lockstep barrier (requests, padded shapes, nonzeros), with a
      ``jax.profiler.TraceAnnotation`` of the same name while tracing;
    * the padded (B, N, W) of each ``maxmin_yields_batch`` device solve;
    * each ``LockstepDispatcher`` lane wrapped to keep a sample of its
      requests and answers for the check.

    ``solver`` replaces the allocator's answers (the control and the
    planted faults); by default the program answers.
    """

    def __init__(self, alloc_jax, jax, seed: int, stride: int,
                 tracer: Optional[Tracer] = None, solver=None):
        self.alloc_jax, self.jax = alloc_jax, jax
        self.tracer = tracer
        self.solver = solver
        self.spans: List[stats.Span] = []
        self.captures: List[verify.Capture] = []
        self._rng = np.random.default_rng([seed, 0x5A3])
        self._stride = int(stride)
        self._padded: List = []
        self._saved = []

    def _annotate(self, name: str):
        if self.tracer is not None and self.tracer.on:
            return self.jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def install(self) -> "Probe":
        aj = self.alloc_jax
        inner_many = aj.BatchedAllocator.allocate_many
        inner_solve = aj.maxmin_yields_batch
        inner_lane = aj.LockstepDispatcher.lane
        probe = self

        def allocate_many(alloc, requests):
            t0 = time.perf_counter()
            if probe.tracer is not None:
                probe.tracer.tick(t0)
            probe._padded = []
            with probe._annotate("allocate_many"):
                if probe.solver is None:
                    out = inner_many(alloc, requests)
                else:
                    out = probe.solver(alloc, requests, inner_many)
            t1 = time.perf_counter()
            nnz = cols = 0
            for inc, c, opt in requests:
                if opt == "MIN" and c.shape[0]:
                    nnz += inc.indices.shape[0]
                    cols += c.shape[0]
            probe.spans.append(stats.Span(t0, t1, len(requests),
                                          tuple(probe._padded), nnz, cols))
            return out

        def maxmin_yields_batch(present, weight, active, *a, **k):
            probe._padded.append(tuple(int(d) for d in weight.shape))
            with probe._annotate("maxmin_yields_batch"):
                return inner_solve(present, weight, active, *a, **k)

        def lane(dispatcher, i):
            cap = verify.Capture(probe._stride,
                                 int(probe._rng.integers(probe._stride)))
            probe.captures.append(cap)
            return _CapturingLane(inner_lane(dispatcher, i), cap)

        self._saved = [(aj.BatchedAllocator, "allocate_many", inner_many),
                       (aj, "maxmin_yields_batch", inner_solve),
                       (aj.LockstepDispatcher, "lane", inner_lane)]
        aj.BatchedAllocator.allocate_many = allocate_many
        aj.maxmin_yields_batch = maxmin_yields_batch
        aj.LockstepDispatcher.lane = lane
        return self

    def uninstall(self) -> None:
        for owner, name, fn in self._saved:
            setattr(owner, name, fn)
        self._saved = []


class Context:
    """What a per-layer metric's reader gets: the spans of the traced
    window, its length, the device trace's summary and the chip's peaks."""

    def __init__(self, spans, window_s, device, peaks):
        self.spans, self.window_s = spans, window_s
        self.device, self.peaks = device, peaks


def load_metric(name: str):
    """The ``read(ctx)`` of ``chipbench/metrics/<name>.py``; a metric
    ``<base>.<suffix>`` without a file of its own reads ``<base>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_entry(name: str):
    """The entry class of ``chipbench/entries/<name>.py``."""
    return importlib.import_module(f"chipbench.entries.{name}").Entry


def _finite(x):
    """JSON has no infinity: a non-finite number is written as a string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def _memory_peak(jax) -> Optional[int]:
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _per_layer(cell: Cell, tracer: Tracer, spans, kind: str, log):
    """Reduce the traced window to ``(metrics, device, breakdown)``: the
    cell's per-layer metrics, the device's ``busy_s`` and ``window_s``, and
    the trace's top ops and idle gaps (the last two empty without a
    trace)."""
    try:
        summary = None
        if tracer.t0 is not None and tracer.t1 is not None:
            summary = tracing.summarize(
                tracing.read_events(tracing.find_xplane(tracer.logdir)),
                spans=("maxmin_yields_batch", "allocate_many"))
    finally:
        tracer.close()
    window_s = (tracer.t1 - tracer.t0) if summary is not None else 0.0
    ctx = Context(stats.in_window(spans, tracer.t0 or 0.0, tracer.t1 or 0.0),
                  window_s, summary, peaks_of(kind))
    metrics = {}
    for m in cell.per_layer:
        v = load_metric(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if summary is None:
        return metrics, {}, {}
    log("idle by label: " + ", ".join(
        f"{k} {v:.4f} s" for k, v in sorted(
            summary.gap_totals.items(), key=lambda kv: -kv[1])))
    return (metrics, {"busy_s": summary.busy_s, "window_s": window_s},
            {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps})


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             jax, device: Dict[str, object], t_start: float,
             solver=None, log=print) -> Dict[str, object]:
    """Set up, measure, check: the result object of one run."""
    from repro import api
    from repro.core import alloc_jax

    alloc_jax.has_jax()             # places the compile cache first
    log(f"set-up: program imported at {time.perf_counter() - t_start:.3f} s")
    compiles = CompileLog(jax)
    traffic = cell.traffic
    tracer = None
    if trace:
        window = dict(TRACE, **traffic.get("trace", {}))
        tracer = Tracer(jax, window["after_s"], window["length_s"])
    entry = load_entry(traffic["entry"])(api, cell.config, traffic, seed, log)
    mark = compiles.mark()
    entry.setup()
    log(f"set-up: inputs and warm-up done at "
        f"{time.perf_counter() - t_start:.3f} s; {compiles.since(mark)}")
    probe = Probe(alloc_jax, jax, seed, traffic["verify"]["stride"],
                  tracer=tracer, solver=solver).install()
    try:
        mark = compiles.mark()
        setup_s = time.perf_counter() - t_start
        if tracer is not None:
            tracer.arm(time.perf_counter())
        win = entry.window(seconds)
        if tracer is not None:
            tracer.stop()
        log(f"window: {win['elapsed_s']:.3f} s, {compiles.since(mark)} "
            f"inside it")
    finally:
        probe.uninstall()
    memory = _memory_peak(jax)
    numbers = entry.verify(probe.captures, np.random.default_rng([seed, 7]))
    ok, checks = verify.judge(numbers, traffic["limits"])
    failed = int(win["failed"])
    correct = bool(ok and failed == 0 and win["attempted"] > 0)
    dev = dict(device)
    dev["memory_peak_bytes"] = memory
    out: Dict[str, object] = {"correct": correct,
                              "attempted": int(win["attempted"]),
                              "failed": failed}
    if tracer is None:
        values = entry.end_to_end([m["name"] for m in cell.end_to_end
                                   if m["name"] != "setup_s"], win)
        values["setup_s"] = setup_s
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        out["metrics"], extra, breakdown = _per_layer(
            cell, tracer, probe.spans, str(dev["kind"]), log)
        dev.update(extra)
        if breakdown:
            out["breakdown"] = breakdown
    out["device"] = dev
    out["checks"] = {k: {"value": _finite(v["value"]),
                         "limit": _finite(v["limit"])}
                     for k, v in checks.items()}
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    cell = Cell(args.workload)
    import jax

    try:
        device = accelerator(jax, cell.chips)
    except NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 1
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    log(f"set-up: device found at {time.perf_counter() - t_start:.3f} s")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), jax,
                   device, t_start, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0
