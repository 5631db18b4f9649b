"""Batched JAX backend for the §4.6 allocation kernels.

``alloc_kernels`` made the per-event allocation a handful of sparse numpy
matvecs; this module makes *many cells at once* a single device dispatch.
The CSR incidence is padded to a dense ``(batch, n_nodes, width)`` SoA
layout (boolean ``present`` mask + float64 ``weight = cpu_need ×
multiplicity``), and the OPT=MIN water-filling runs as **one jitted
``lax.while_loop`` stepping every lane in lockstep** — two batched
sequential matvecs per freeze round (frozen use, unfrozen need), a vmapped
bottleneck scan, masked freeze updates.  Finished lanes are masked out and
idle until the slowest lane converges, so one compiled program serves the
whole batch.

Bit-identity contract on the CPU backend (the same one ``alloc_kernels``
holds against ``alloc_reference``): with ``jax_enable_x64``, every
per-lane result is **bit-equal** to ``maxmin_yields_csr`` /
``avg_yields_csr`` on that lane's CSR alone.  Three properties make this work:

* padding is exact — a padded column/row/lane contributes an exact
  ``+0.0`` to every accumulation, which never changes a finite partial sum,
  and padded lanes start all-frozen so the lockstep loop never writes them;
* the inner matvec materializes all products with one vectorized multiply
  and then accumulates with an adds-only ``fori_loop`` (ascending column
  order).  XLA CPU would contract a mul feeding an add in the same loop
  body into a single-rounding FMA — 1 ulp off numpy's two-rounding sequence
  — so the multiply must live outside the accumulation loop (see
  ``kernels/alloc_matvec.py``);
* x64 is enabled through the *scoped* ``jax.enable_x64(True)`` config
  context, not the global flag, so the repo's float32 model/kernel stack is
  untouched in the same process.

A TPU has no native float64: XLA emulates it with float32 pairs, which
round even a host-to-device copy, so there the lanes agree with numpy to
about 1e-14 relative, not bit for bit (ARCHITECTURE.md, "Exactness under
jit", has the measurement and the tolerance ``chip_smoke.py`` holds).

OPT=AVG is a HiGHS LP — a host simplex solver, not jittable — so the
batched path computes the LP's yield floor (``1/max(1, Λ)``, Λ = max
sequential node load) on device for all lanes at once and solves the small
per-lane LPs on host from bit-identical inputs through the same
``lp2_yields`` call; the results equal ``avg_yields_csr`` exactly.

The matvec is chosen by name, never by the backend the process found:
``"jnp"`` (the pure-jnp formulation, the default on every backend) or
``"interpret"`` (the Pallas kernel in the Pallas interpreter — a CPU
validation path).  ``"pallas"`` asks for the compiled kernel and raises:
Mosaic has no float64 and XLA's TPU x64 rewrite does not reach a Pallas
custom call, so the kernel cannot run in the lane's dtype on the chip.

On top sits the lockstep machinery ``sweep.run_batched`` drives: a
:class:`BatchedAllocator` turning N allocation requests into one padded
dispatch (shapes bucketed to powers of two to bound recompiles), and a
:class:`LockstepDispatcher` that parks engine threads at their allocation
points until every live lane has a request in the batch.  Each round it
serves is a row of the process's round log, timed by ``dfrs.*`` spans that
also land in any profiler trace (:mod:`repro.core.roundlog`;
:func:`lockstep_rounds`, :func:`lockstep_totals`).

Everything imports lazily: environments without jax can import this module,
and ``has_jax()`` gates the callers (``pytest.importorskip`` in tests).  The
first import also places JAX's persistent compilation cache (see
:func:`_enable_compile_cache`).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .alloc_kernels import CSRIncidence, lp2_yields
from .roundlog import Round, lockstep_rounds, lockstep_totals, span
from . import roundlog

__all__ = [
    "has_jax",
    "densify_csr",
    "pad_batch",
    "maxmin_yields_batch",
    "maxmin_yields_jax",
    "node_usage",
    "node_usage_batch",
    "JaxAllocBackend",
    "BatchedAllocator",
    "LockstepDispatcher",
    "Round",
    "lockstep_rounds",
    "lockstep_totals",
]

_EPS = 1e-12
MATVECS = ("jnp", "interpret", "pallas")
#: the compile cache's place when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path in the checkout, so that the next process finds it again
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, os.pardir, ".jax_cache")


# --------------------------------------------------------------------------- #
# lazy jax                                                                     #
# --------------------------------------------------------------------------- #
_STATE: Dict[str, object] = {}


def has_jax() -> bool:
    """True when a working jax import is available (the backend is usable)."""
    try:
        _jax()
        return True
    except ImportError:
        return False


def _jax():
    jax = _STATE.get("jax")
    if jax is None:
        import jax  # noqa: PLC0415 — lazy: tier-1 must pass without jax

        _enable_compile_cache(jax)
        _STATE["jax"] = jax
    return _STATE["jax"]


def _enable_compile_cache(jax) -> None:
    """Keep compiled lockstep programs across processes.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives at :data:`CACHE_DIR`.  Every
    program is cached however small or quick to compile: the lane is many
    small bucketed programs, and those are exactly what a rerun repeats.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.normpath(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _x64():
    """The scoped x64 context (thread-local — never the global flag)."""
    return _jax().enable_x64(True)


def _bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) — bounds distinct jit shapes."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


# --------------------------------------------------------------------------- #
# padding: CSR -> dense (batch, n_nodes, width) SoA                            #
# --------------------------------------------------------------------------- #
def densify_csr(
    inc: CSRIncidence,
    n_nodes: Optional[int] = None,
    cols: Optional[np.ndarray] = None,
    width: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Dense ``(present, weight)`` of one incidence snapshot.

    ``cols`` compacts the job axis to those (sorted) columns — ascending
    column order is preserved, so sequential accumulation over the compact
    axis performs the identical operation sequence (every entry must lie in
    ``cols``, which holds for engine snapshots: the incidence contains only
    running tasks).  ``n_nodes``/``width`` pad with exact zeros.
    """
    N = inc.n_nodes if n_nodes is None else n_nodes
    if cols is None:
        W = inc.width if width is None else width
        col_idx = inc.indices
    else:
        W = cols.shape[0] if width is None else width
        col_idx = np.searchsorted(cols, inc.indices)
    present = np.zeros((N, W), dtype=bool)
    weight = np.zeros((N, W), dtype=np.float64)
    rows = np.repeat(np.arange(inc.n_nodes), np.diff(inc.indptr))
    present[rows, col_idx] = True
    weight[rows, col_idx] = inc.data
    return present, weight


def pad_batch(
    incs: Sequence[CSRIncidence],
    actives: Sequence[np.ndarray],
    n_nodes: Optional[int] = None,
    width: Optional[int] = None,
    n_lanes: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a list of (incidence, active-mask) cells into one dense batch.

    Returns ``(present, weight, active)`` with shapes ``(B, N, W)``,
    ``(B, N, W)``, ``(B, W)``.  Extra lanes (``n_lanes > len(incs)``) are
    all-inactive: the lockstep loop treats them as already converged.
    """
    B = len(incs) if n_lanes is None else n_lanes
    N = n_nodes if n_nodes is not None else max(
        (i.n_nodes for i in incs), default=1)
    W = width if width is not None else max(
        (i.width for i in incs), default=1)
    present = np.zeros((B, N, W), dtype=bool)
    weight = np.zeros((B, N, W), dtype=np.float64)
    active = np.zeros((B, W), dtype=bool)
    for b, (inc, act) in enumerate(zip(incs, actives)):
        p, w = densify_csr(inc, n_nodes=N, width=W)
        present[b], weight[b] = p, w
        active[b, : act.shape[0]] = act
    return present, weight, active


# --------------------------------------------------------------------------- #
# the lockstep water-filling program                                           #
# --------------------------------------------------------------------------- #
def _matvec_fn(matvec: str):
    """Resolve a matvec kind to a traced ``(weight, x) -> use`` callable."""
    if matvec == "jnp":
        from ..kernels.alloc_matvec import alloc_matvec_ref

        return alloc_matvec_ref
    if matvec == "interpret":
        from ..kernels.alloc_matvec import alloc_matvec

        return lambda w, x: alloc_matvec(w, x, interpret=True)
    if matvec == "pallas":
        raise ValueError(
            "matvec='pallas': the compiled Pallas alloc_matvec cannot run in "
            "the lane's float64 (Mosaic has no 64-bit float, and XLA's TPU "
            "x64 rewrite does not reach a Pallas custom call); use "
            "matvec='jnp', or 'interpret' to validate the kernel on CPU")
    raise ValueError(f"unknown matvec backend {matvec!r}")


def _build_maxmin(matvec: str):
    """The jitted lockstep program for one matvec kind (shape-polymorphic:
    jax caches one executable per padded shape)."""
    jax = _jax()
    import jax.numpy as jnp
    from jax import lax

    mv = _matvec_fn(matvec)

    def maxmin_batch(present, weight, active):
        B, N, W = weight.shape
        n_active = jnp.sum(active, axis=1)                       # (B,)
        arange_n = jnp.arange(N)

        def lane_done(i, frozen):
            # mirrors the numpy loop: stop on full freeze or after the
            # n_active+1 safety cap (i counts completed rounds)
            return jnp.all(frozen, axis=1) | (i >= n_active + 1)

        def cond(carry):
            i, _, frozen = carry
            return ~jnp.all(lane_done(i, frozen))

        def scan_single(levels, valid):
            # the reference's tolerance-updated running minimum — order-
            # dependent when two levels sit within 1e-15, so it must scan
            # nodes in ascending order exactly like the numpy loop
            def scan_body(n, best_binding):
                best, binding = best_binding
                lvl, v = levels[n], valid[n]
                lower = v & (lvl < best - 1e-15)
                tie = v & ~lower & (jnp.abs(lvl - best) <= 1e-15)
                onehot = arange_n == n
                binding = jnp.where(
                    lower, onehot,
                    jnp.where(tie, binding | onehot, binding))
                best = jnp.where(lower, lvl, best)
                return best, binding

            return lax.fori_loop(
                0, N, scan_body,
                (jnp.asarray(1.0, levels.dtype), jnp.zeros(N, bool)))

        def body(carry):
            i, y, frozen = carry
            live = ~lane_done(i, frozen)                         # (B,)
            f_use = mv(weight, jnp.where(frozen, y, 0.0))        # (B, N)
            u_need = mv(weight, (~frozen).astype(weight.dtype))  # (B, N)
            valid = u_need > _EPS
            levels = jnp.maximum(0.0, 1.0 - f_use) / jnp.where(
                valid, u_need, 1.0)
            best, binding = jax.vmap(scan_single)(levels, valid)
            cap = best >= 1.0 - 1e-12
            best = jnp.where(cap, 1.0, best)
            on_binding = jnp.any(present & binding[:, :, None], axis=1)
            newly = jnp.where(cap[:, None], ~frozen, on_binding & ~frozen)
            # numerical safety (reference semantics): a round that froze
            # nothing freezes everything still open
            newly = jnp.where(
                jnp.any(newly, axis=1)[:, None], newly, ~frozen)
            upd = live[:, None] & ~frozen
            y = jnp.where(upd, best[:, None], y)
            frozen = frozen | (newly & live[:, None])
            return i + 1, y, frozen

        _, y, _ = lax.while_loop(
            cond, body,
            (jnp.asarray(0, jnp.int64),
             jnp.zeros((B, W), weight.dtype), ~active))
        return jnp.clip(y, 0.0, 1.0)

    return jax.jit(maxmin_batch)


def _maxmin_jit(matvec: str):
    key = ("maxmin", matvec)
    fn = _STATE.get(key)
    if fn is None:
        fn = _build_maxmin(matvec)
        _STATE[key] = fn
    return fn


def maxmin_yields_batch(
    present: np.ndarray,
    weight: np.ndarray,
    active: np.ndarray,
    matvec: str = "jnp",
) -> np.ndarray:
    """OPT=MIN water-filling over a padded dense batch — one jitted lockstep
    dispatch.  Per lane bit-equal to ``maxmin_yields_csr`` under x64."""
    with _x64():
        with span("dfrs.dispatch"):
            y = _maxmin_jit(matvec)(present, weight, active)
        with span("dfrs.fetch"):
            return np.asarray(y)


def maxmin_yields_jax(
    inc: CSRIncidence, active: np.ndarray, matvec: str = "jnp",
) -> np.ndarray:
    """Single-cell convenience (a 1-lane batch): full-width yield vector,
    bit-equal to ``maxmin_yields_csr(inc, active)``."""
    present, weight = densify_csr(inc)
    y = maxmin_yields_batch(present[None], weight[None], active[None],
                            matvec=matvec)
    return y[0]


# --------------------------------------------------------------------------- #
# batched stretch scatter (§4.7 node-usage pass)                               #
# --------------------------------------------------------------------------- #
def _usage_jit(n_nodes: int, batched: bool):
    key = ("usage", n_nodes, batched)
    fn = _STATE.get(key)
    if fn is None:
        jax = _jax()

        def usage(nodes, vals):
            # one extra segment swallows the padding (sentinel id n_nodes)
            out = jax.ops.segment_sum(vals, nodes,
                                      num_segments=n_nodes + 1)
            return out[..., :n_nodes]

        fn = jax.jit(jax.vmap(usage) if batched else usage)
        _STATE[key] = fn
    return fn


def node_usage(nodes: np.ndarray, vals: np.ndarray, n_nodes: int) -> np.ndarray:
    """Per-node usage scatter — bit-equal to the in-order ``np.add.at``
    accumulation of the §4.7 stretch passes.  ``nodes`` entries equal to
    ``n_nodes`` are padding and are dropped."""
    with _x64():
        return np.asarray(_usage_jit(int(n_nodes), False)(nodes, vals))


def node_usage_batch(
    nodes: np.ndarray, vals: np.ndarray, n_nodes: int,
) -> np.ndarray:
    """Batched :func:`node_usage` over ``(B, K)`` scatter lists (padded with
    the ``n_nodes`` sentinel), one fused device dispatch."""
    with _x64():
        return np.asarray(_usage_jit(int(n_nodes), True)(nodes, vals))


# --------------------------------------------------------------------------- #
# OPT=AVG: device floor + host HiGHS                                           #
# --------------------------------------------------------------------------- #
def _lam_jit(matvec: str):
    key = ("lam", matvec)
    fn = _STATE.get(key)
    if fn is None:
        jax = _jax()
        import jax.numpy as jnp

        mv = _matvec_fn(matvec)

        def lam(weight):
            # Λ per lane: max over nodes of the sequential row load sums
            B, N, W = weight.shape
            load = mv(weight, jnp.ones((B, W), weight.dtype))
            return jnp.max(load, axis=1)

        fn = jax.jit(lam)
        _STATE[key] = fn
    return fn


# --------------------------------------------------------------------------- #
# engine-pluggable backends                                                    #
# --------------------------------------------------------------------------- #
class BatchedAllocator:
    """Serve many cells' allocation requests as single padded dispatches.

    ``allocate_many([(inc, cols, opt), ...])`` answers every request with
    the bit-exact yields for its cell: OPT=MIN requests are compacted to
    their running columns, padded into one ``(B, N, W)`` batch (shapes
    bucketed to powers of two so a sweep compiles a handful of programs,
    not one per event) and solved in one lockstep dispatch; OPT=AVG
    requests get their floors from one device reduction and their LPs from
    the host solver.
    """

    def __init__(self, matvec: str = "jnp"):
        _matvec_fn(matvec)              # unknown or unrunnable: raise now
        self.matvec = matvec

    # -- single request (the Engine alloc_backend protocol) ---------------- #
    def allocate(self, inc: CSRIncidence, cols: np.ndarray,
                 opt: str = "MIN") -> np.ndarray:
        return self.allocate_many([(inc, cols, opt)])[0]

    # -- batched ----------------------------------------------------------- #
    def allocate_many(
        self, requests: Sequence[Tuple[CSRIncidence, np.ndarray, str]],
    ) -> List[np.ndarray]:
        out: List[Optional[np.ndarray]] = [None] * len(requests)
        min_idx = [i for i, (_, c, opt) in enumerate(requests)
                   if opt == "MIN" and c.shape[0]]
        avg_idx = [i for i, (_, c, opt) in enumerate(requests)
                   if opt == "AVG" and c.shape[0]]
        for i, (_, c, opt) in enumerate(requests):
            if opt not in ("MIN", "AVG"):
                raise ValueError(f"unknown OPT {opt!r}")
            if not c.shape[0]:
                out[i] = np.zeros(0)
        if min_idx:
            self._serve_min(requests, min_idx, out)
        if avg_idx:
            self._serve_avg(requests, avg_idx, out)
        return out  # fully populated

    def _pad_compact(self, requests, idx):
        """Compact each request to its running columns and pad the set into
        one bucketed batch (per-lane exactness makes the co-batching safe:
        no lane's answer depends on what else is in the batch)."""
        with span("dfrs.pad"):
            N = _bucket(max(requests[i][0].n_nodes for i in idx))
            W = _bucket(max(requests[i][1].shape[0] for i in idx), 8)
            B = _bucket(len(idx))
            present = np.zeros((B, N, W), dtype=bool)
            weight = np.zeros((B, N, W), dtype=np.float64)
            active = np.zeros((B, W), dtype=bool)
            for b, i in enumerate(idx):
                inc, cols, _ = requests[i]
                p, w = densify_csr(inc, n_nodes=N, cols=cols, width=W)
                present[b], weight[b] = p, w
                active[b, : cols.shape[0]] = True
        roundlog.count(cells=B * N * W, nnz=sum(
            requests[i][0].indices.shape[0] for i in idx))
        return present, weight, active

    def _serve_min(self, requests, idx, out):
        present, weight, active = self._pad_compact(requests, idx)
        y = maxmin_yields_batch(present, weight, active, matvec=self.matvec)
        for b, i in enumerate(idx):
            m = requests[i][1].shape[0]
            out[i] = y[b, :m].copy()

    def _serve_avg(self, requests, idx, out):
        _, weight, _ = self._pad_compact(requests, idx)
        with _x64(), span("dfrs.lam"):
            lams = np.asarray(_lam_jit(self.matvec)(weight))
        for b, i in enumerate(idx):
            inc, cols, _ = requests[i]
            lam = float(lams[b]) if inc.n_nodes else 0.0
            with span("dfrs.lp"):
                out[i] = lp2_yields(inc, cols, 1.0 / max(1.0, lam))


class JaxAllocBackend(BatchedAllocator):
    """One-cell engine backend: ``Engine(..., alloc_backend=JaxAllocBackend())``
    answers every §4.6 reallocation from the device, bit-identically to the
    numpy hot path (``allocate_incidence``)."""


# --------------------------------------------------------------------------- #
# lockstep dispatch: many engine threads, one device                           #
# --------------------------------------------------------------------------- #
class LockstepDispatcher:
    """Coordinate N engine threads so their allocation requests land on the
    device as one batch per scheduling round.

    Each engine runs in its own thread with a :meth:`lane` backend plugged
    in; a lane's ``allocate`` parks the thread until the driver thread
    (:meth:`serve`) has collected a request from *every* lane that is still
    running — engines that never allocate (batch baselines) simply run to
    completion and drop out of the barrier via :meth:`finish_lane`.  The
    driver answers each round with one ``BatchedAllocator.allocate_many``
    and wakes the lanes.  Per-lane results are bit-independent of batch
    composition, so the lockstep schedule cannot change any cell's outcome.

    Every round is a row of the round log (:mod:`repro.core.roundlog`): the
    barrier wait (``dfrs.barrier_wait``), the allocator call
    (``dfrs.allocate``), the requests, and the lane CPU each request cost
    since its lane's last answer, with what the lane's engine counted in
    that time (its :class:`~repro.core.roundlog.LaneMeter`); what a lane
    counted after its last request goes with the next row, so the log sums
    to the lanes' whole counts.  The last wait of a pass is a row with no
    request.
    """

    def __init__(self, n_lanes: int, allocator: BatchedAllocator):
        self.n_lanes = int(n_lanes)
        self.allocator = allocator
        self._cond = threading.Condition()
        self._pending: Dict[int, Tuple[CSRIncidence, np.ndarray, str]] = {}
        self._lane_cpu: Dict[int, float] = {}
        self._lane_counts: Dict[int, tuple] = {}
        self._meters: Dict[int, roundlog.LaneMeter] = {}
        self._leftover: List[tuple] = []
        self._results: Dict[int, object] = {}
        self._finished: set = set()
        self._broken: Optional[BaseException] = None

    def lane(self, i: int) -> "_Lane":
        return _Lane(self, i)

    def finish_lane(self, i: int) -> None:
        """A lane's engine is done (or died) — it leaves the barrier, with
        what its meter counted since its last request."""
        with self._cond:
            meter = self._meters.pop(i, None)
            if meter is not None:
                self._leftover.append(meter.take())
                roundlog.unbind_lane_meter(meter)
            self._finished.add(i)
            self._cond.notify_all()

    def _request(self, i, inc, cols, opt, cpu_s: float,
                 counts: tuple) -> np.ndarray:
        with self._cond:
            if self._broken is not None:
                raise self._broken
            self._pending[i] = (inc, cols, opt)
            self._lane_cpu[i] = cpu_s
            self._lane_counts[i] = counts
            self._cond.notify_all()
            self._cond.wait_for(
                lambda: i in self._results or self._broken is not None)
            res = self._results.pop(i, self._broken)
        if isinstance(res, BaseException):
            raise res
        return res

    def serve(self) -> None:
        """Drive rounds until every lane finished.  Call from the thread
        that owns the device (the sweep driver)."""
        while True:
            with span("dfrs.barrier_wait") as wait, self._cond:
                self._cond.wait_for(
                    lambda: len(self._pending) + len(self._finished)
                    >= self.n_lanes)
                batch = sorted(self._pending.items())
                lane_cpu = sum(self._lane_cpu.values())
                counts = list(self._lane_counts.values()) + self._leftover
                self._pending.clear()
                self._lane_cpu.clear()
                self._lane_counts.clear()
                self._leftover = []
            if not batch:               # every lane finished
                roundlog.record(wait.t0, wait.t1, wait.t1, 0, 0, 0.0,
                                lanes=counts)
                return
            lanes = [i for i, _ in batch]
            acc = roundlog.open_round()
            try:
                with span("dfrs.allocate") as call:
                    answers = self.allocator.allocate_many(
                        [r for _, r in batch])
            except BaseException as exc:
                with self._cond:        # poison: wake every parked/future lane
                    self._broken = exc
                    self._cond.notify_all()
                raise
            finally:
                roundlog.close_round()
            roundlog.record(wait.t0, call.t0, call.t1, len(batch),
                            sum(opt == "MIN" for _, (_, _, opt) in batch),
                            lane_cpu, acc, lanes=counts)
            with self._cond:
                for i, y in zip(lanes, answers):
                    self._results[i] = y
                self._cond.notify_all()


class _Lane:
    """The per-engine view of a :class:`LockstepDispatcher` (the object an
    ``Engine`` receives as ``alloc_backend``).  Made and used in the lane's
    own thread: each request carries the thread's CPU seconds since its
    last answer (the first, since the lane was made), so its wake-ups at
    the barrier are left out.  That takes two reads of the thread's CPU
    clock a request, each a system call (about 6 µs on a virtualized
    host).  The lane makes the thread's
    :class:`~repro.core.roundlog.LaneMeter`, which an engine made on the
    thread counts into, and hands its counts with each request."""

    __slots__ = ("_dispatcher", "index", "_cpu_mark", "meter")

    def __init__(self, dispatcher: LockstepDispatcher, index: int):
        self._dispatcher = dispatcher
        self.index = index
        self.meter = roundlog.LaneMeter()
        roundlog.bind_lane_meter(self.meter)
        with dispatcher._cond:
            dispatcher._meters[index] = self.meter
        self._cpu_mark = time.thread_time()

    def allocate(self, inc: CSRIncidence, cols: np.ndarray,
                 opt: str = "MIN") -> np.ndarray:
        if not cols.shape[0]:
            return np.zeros(0)          # nothing running: no round trip
        cpu_s = time.thread_time() - self._cpu_mark
        y = self._dispatcher._request(self.index, inc, cols, opt, cpu_s,
                                      self.meter.take())
        self._cpu_mark = time.thread_time()
        return y
