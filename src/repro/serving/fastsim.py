"""Vectorized fast-path simulation engine: batched Lindley-recursion sweeps.

Compass's offline stage (Planner profiling, switching-policy validation,
paper §V) and the entire benchmark suite evaluate *thousands* of
(configuration, load, pool-size) scenarios against the serving model.  The
event-heap :class:`repro.serving.simulator.ServingSimulator` is exact but
pure-Python-per-event: every simulated request pays a heap push/pop, a
scheduler poll, and a dataclass allocation, which caps it around ~5e4
simulated requests/s and makes large sweeps minutes of wall clock.

This module is the fast path.  For the *static* sub-family of scenarios —
fixed configuration or fixed per-server assignment, one shared FIFO queue,
no batching, no stealing, no admission control, no controller — an M/G/c
FIFO system is fully described by the Lindley (c = 1) / Kiefer–Wolfowitz
(c > 1) recursion over the arrival and service sequences:

    c = 1:   C_i = max(A_i, C_{i-1}) + S_i            (Lindley)
    c > 1:   start_i = max(A_i, min_s F[s]);  F[s*] = start_i + S_i
             where s* is the lowest-numbered server with F[s*] <= start_i
             (the Kiefer–Wolfowitz workload-vector recursion, with the
             event-heap's deterministic lowest-free-server tie-break)

so per-request waits can be computed directly from pre-drawn arrival /
service arrays with no event heap at all.  Two entry points:

- :func:`simulate` — drop-in scenario runner mirroring
  :class:`ServingSimulator`'s constructor + ``run`` signature.  Eligible
  cases (:func:`fast_path_eligible`) take the fast path and reproduce the
  event-heap simulator **bit-for-bit** at c = 1 (same ``random.Random``
  draw order, same float operations — the golden test in
  ``tests/test_fastsim.py``); everything else (controllers, batching,
  stealing, admission bounds, per-worker queues) transparently falls back
  to the event-heap simulator, which is kept as the exact oracle.
- :func:`simulate_batch` — the batched sweep API: R replications x
  K configurations x L load patterns evaluated as one set of numpy array
  operations over a padded ``(R*K*L, N_max)`` request grid, returning a
  result grid of mean wait / p95 latency / SLO compliance / throughput.
  Every cell is an independent, deterministic function of ``(seed, cell
  coordinates, cell inputs)``: arrival streams are keyed by (replication,
  load) and service streams by (config, arrival-trace fingerprint), so a
  cell's result never depends on which other cells share the batch — the
  permutation/slicing-invariance property tests rely on this.

Throughput: the batched sweep runs ~1e6-1e8 simulated requests/s
(scenario-count dependent; ``benchmarks/fastsim_bench.py`` tracks the
measured number in ``experiments/fastsim_bench.json``), vs ~5e4 for the
event heap — the >= 20x fast-path acceptance criterion of the PR that
introduced this module.  The event heap remains authoritative: fast-path
agreement is enforced by golden (c = 1) and statistical (c > 1) tests
against it, plus the Allen-Cunneen M/G/c prediction
(:func:`repro.core.aqm.allen_cunneen_mean_wait`).

Backends.  ``simulate_batch`` evaluates the recursion on one of two
backends (``backend="numpy" | "jax" | "auto"``):

- **numpy** — the authoritative reference: the original per-request-index
  Python loop over vectorized array ops.  Its results are bit-for-bit
  stable across this PR and remain the values every parity test pins.
- **jax** — the same pre-drawn (arrival, service) grids pushed through a
  jitted scan: the c = 1 Lindley recursion as a max-plus
  ``jax.lax.associative_scan`` over 2x2 operator pairs
  (:func:`repro.kernels.lindley_scan.maxplus_combine`), or an equivalent
  sequential ``lax.scan`` that reproduces the numpy loop *bit-exactly*
  (``scan_impl="auto"`` picks the sequential form on CPU, where XLA's
  O(N log N) associative materialization loses to the O(N) scan, and the
  associative form on accelerators, where its log-depth parallelism
  wins); the c > 1 Kiefer-Wolfowitz recursion as a ``lax.scan`` whose
  carry is the sorted length-c workload vector, maintained by an unrolled
  insertion (comparator) network.  ``scan_impl="pallas"`` names the
  blocked Pallas kernel
  (:func:`repro.kernels.lindley_scan.lindley_scan`), which lowers for
  float32 only: the float64 grids here refuse it with a ValueError.
  Arrival and service draws always come from the *same* content-keyed
  numpy streams as the numpy backend, so the jax grids are held to tight
  allclose parity (bit-exact schedules for the sequential impl on the
  CPU) — only the recursion and reductions move to the accelerator.  The
  scan math runs in float64 via the scoped ``jax.enable_x64(True)``
  context, which does not leak x64 into the rest of the process.  A TPU
  has no native float64 and XLA emulates it there, so on the chip the
  grids are close to numpy, not bit-equal: on one TPU v5e the largest
  relative differences were 6e-13 on p95s and compliance equal or within
  2e-15 (``Planner.validate``, ``dag.sweep_pipeline``), and up to 1e-7
  on a streamed stage's mean wait (``traces.replay_dag``);
  ``chip_smoke.py`` holds them to 1e-6.
- **auto** — jax when it is importable, the pool fits the jax path
  (c <= ``_JAX_MAX_SERVERS``), and the padded grid is big enough to
  amortize dispatch (>= ``_JAX_AUTO_MIN_SLOTS`` request slots); numpy
  otherwise.  Falling back is always silent and safe: both backends
  compute the same grids.
"""

from __future__ import annotations

import hashlib
import math
import random
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..core.pareto import BatchProfile
from .simulator import (
    CompletedRequest,
    ServiceSampler,
    ServingSimulator,
    SimulationResult,
)

try:  # jax is optional at runtime: the numpy backend is always available
    import jax as _jax
    import jax.numpy as _jnp
    _JAX_IMPORT_ERROR: Optional[str] = None
except Exception as _e:  # pragma: no cover - exercised on jax-less installs
    _jax = None
    _jnp = None
    _JAX_IMPORT_ERROR = f"{type(_e).__name__}: {_e}"

__all__ = [
    "fast_path_eligible",
    "simulate",
    "simulate_batch",
    "FastSimulationResult",
    "SweepResult",
    "lognormal_params",
    "chained_lindley",
    "jax_available",
    "jax_unavailable_reason",
    "resolve_backend",
]

_Z95 = 1.6448536269514722


def lognormal_params(mean_s: float, p95_s: float) -> Tuple[float, float]:
    """(mu, sigma) of the lognormal matched to (mean, p95) — the same solve
    :func:`repro.serving.simulator.lognormal_sampler_from_profile` uses, so
    batched sweeps and the event-heap oracle share one service model."""
    if not (p95_s > 0 and mean_s > 0):
        raise ValueError("profile stats must be positive")
    ratio = max(p95_s / mean_s, 1.001)
    c = math.log(ratio)
    disc = _Z95 * _Z95 - 2.0 * c
    sigma = _Z95 - math.sqrt(disc) if disc > 0 else _Z95
    mu = math.log(mean_s) - sigma * sigma / 2.0
    return mu, sigma


# --------------------------------------------------------------------------
# eligibility
# --------------------------------------------------------------------------


def fast_path_eligible(
    *,
    controller: Any = None,
    num_servers: int = 1,
    assignment: Optional[Sequence[int]] = None,
    max_batch_size: int = 1,
    batch_timeout_s: float = 0.0,
    batch_profiles: Optional[Sequence[BatchProfile]] = None,
    max_queue_depth: Optional[int] = None,
    admission_reroute: bool = False,
    queue_discipline: str = "shared",
    steal: bool = False,
    steal_threshold: Optional[int] = None,
    faults: Any = None,
    request_timeout_s: Optional[float] = None,
) -> bool:
    """Can this scenario take the vectorized fast path?

    The Lindley / Kiefer-Wolfowitz recursion describes exactly the static
    shared-FIFO M/G/c system: a fixed configuration (or fixed per-server
    assignment), every arrival admitted, one request per dispatch.  Any
    dynamic-policy feature — an Elastico controller, in-worker batching
    (B > 1; a linger window at B = 1 never forms, so ``batch_timeout_s``
    alone does not disqualify), admission control, per-worker backlogs,
    work stealing, fault injection (a non-empty
    :class:`repro.serving.faults.FaultSchedule`), request deadlines —
    changes which request runs where/when in ways the closed-form
    recursion does not capture, so those scenarios go to the event-heap
    oracle."""
    return (
        controller is None
        and max_batch_size == 1
        and queue_discipline == "shared"
        and not steal
        and max_queue_depth is None
        and not admission_reroute
        and num_servers >= 1
        and (faults is None or faults.is_empty())
        and request_timeout_s is None
    )


# --------------------------------------------------------------------------
# fast-path result (SimulationResult-compatible, array-backed)
# --------------------------------------------------------------------------


@dataclass
class FastSimulationResult:
    """Array-backed drop-in for :class:`SimulationResult`.

    Exposes the same metric surface (``mean_wait`` / ``slo_compliance`` /
    ``goodput`` / ``p95_latency`` / ``mean_accuracy`` / ``latencies`` /
    ``per_server_utilization`` / ``mean_batch_size`` and the bookkeeping
    attributes) computed from numpy arrays, and materializes the
    per-request :class:`CompletedRequest` list lazily on first access to
    ``.completed`` — consumers that only read aggregate metrics never pay
    for N dataclass allocations."""

    arrival_s: np.ndarray
    start_s: np.ndarray
    completion_s: np.ndarray
    config_index: np.ndarray          # per-request config (int array)
    server_id: np.ndarray             # per-request serving worker
    duration_s: float
    num_servers: int = 1
    per_server_busy_s: List[float] = field(default_factory=lambda: [0.0])
    config_timeline: List[Tuple[float, int]] = field(default_factory=list)
    queue_depth_samples: List[Tuple[float, int]] = field(default_factory=list)
    assignment_timeline: List[Tuple[float, Tuple[int, ...]]] = field(
        default_factory=list)
    switch_events: List = field(default_factory=list)
    offered: int = 0
    dropped: int = 0
    rerouted: int = 0
    stolen_batches: int = 0
    _completed: Optional[List[CompletedRequest]] = field(
        default=None, repr=False)

    @property
    def num_batches(self) -> int:
        return int(self.arrival_s.size)   # unbatched: one dispatch per request

    @property
    def completed(self) -> List[CompletedRequest]:
        """Per-request records, materialized on first access (the fast path
        keeps everything in arrays until a consumer actually wants them)."""
        if self._completed is None:
            self._completed = [
                CompletedRequest(
                    request_id=i,
                    arrival_s=float(self.arrival_s[i]),
                    start_s=float(self.start_s[i]),
                    completion_s=float(self.completion_s[i]),
                    config_index=int(self.config_index[i]),
                    server_id=int(self.server_id[i]),
                    batch_size=1,
                )
                for i in range(self.arrival_s.size)
            ]
        return self._completed

    def __len__(self) -> int:  # len(result.completed) without materializing
        return int(self.arrival_s.size)

    @property
    def num_completed(self) -> int:
        return int(self.arrival_s.size)

    # -- vectorized metric surface (mirrors SimulationResult) ---------------

    def waits(self) -> np.ndarray:
        return self.start_s - self.arrival_s

    def latencies_array(self) -> np.ndarray:
        return self.completion_s - self.arrival_s

    def latencies(self) -> List[float]:
        return self.latencies_array().tolist()

    def mean_wait(self) -> float:
        if self.arrival_s.size == 0:
            return 0.0
        return float(self.waits().mean())

    def slo_compliance(self, slo_s: float) -> float:
        if self.arrival_s.size == 0:
            return 1.0
        lat = self.latencies_array()
        return float(np.count_nonzero(lat <= slo_s)) / lat.size

    def goodput(self, slo_s: float) -> float:
        if self.offered == 0:
            return 1.0
        lat = self.latencies_array()
        return float(np.count_nonzero(lat <= slo_s)) / self.offered

    def mean_accuracy(self, accuracies: Sequence[float]) -> float:
        if self.arrival_s.size == 0:
            return 0.0
        acc = np.asarray(accuracies, dtype=float)
        return float(acc[self.config_index].mean())

    def config_counts(self) -> dict:
        """{config_index: served count} — the per-rung usage histogram."""
        idx, counts = np.unique(self.config_index, return_counts=True)
        return {int(i): int(n) for i, n in zip(idx, counts)}

    def p95_latency(self) -> float:
        lat = self.latencies_array()
        if lat.size == 0:
            return 0.0
        xs = np.sort(lat)
        pos = 0.95 * (lat.size - 1)
        lo = int(pos)
        hi = min(lo + 1, lat.size - 1)
        # identical interpolation arithmetic to SimulationResult.p95_latency
        return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))

    def per_server_utilization(self) -> List[float]:
        horizon = max(self.duration_s, 1e-12)
        return [b / horizon for b in self.per_server_busy_s]

    def mean_batch_size(self) -> float:
        return 1.0


# --------------------------------------------------------------------------
# single-scenario fast path (exact: same RNG draw order as the event heap)
# --------------------------------------------------------------------------


def _tick_depth_samples(arrivals: np.ndarray, starts: np.ndarray,
                        duration_s: float,
                        control_tick_s: float) -> List[Tuple[float, int]]:
    """Buffered queue depth at every control tick, computed by counting:
    depth(t) = #{arrived at or before t} - #{dispatched at or before t}.

    Matches the event-heap driver's sampling points (ticks at 0,
    tick, 2*tick, ... < duration) and its convention that arrival /
    dispatch events at exactly the tick time are processed before the tick
    observes (the heap orders equal-time events by push order, and ticks
    are pushed first — but a tick pushed at t sorts before same-t arrivals
    ... by *order*, which increments per push: all ticks are pushed after
    arrivals, so same-time arrivals are processed first)."""
    if duration_s <= 0 or control_tick_s <= 0:
        return []
    # accumulate t += tick exactly like the event heap's tick loop —
    # np.arange's i*tick grid diverges for ticks not representable in
    # binary (e.g. 0.1: the accumulated 10th tick is 0.9999... < 1.0 and
    # the heap emits one more sample than the arange grid)
    tick_list: List[float] = []
    t = 0.0
    while t < duration_s:
        tick_list.append(t)
        t += control_tick_s
    ticks = np.asarray(tick_list, dtype=float)
    arrived = np.searchsorted(arrivals, ticks, side="right")
    started = np.searchsorted(np.sort(starts), ticks, side="right") \
        if starts.size else np.zeros_like(ticks, dtype=int)
    return [(float(t), int(a - s)) for t, a, s in zip(ticks, arrived, started)]


def _run_fast_single(
    service_sampler: ServiceSampler,
    arrivals: Sequence[float],
    duration_s: float,
    *,
    static_index: int,
    seed: int,
    num_servers: int,
    assignment: Optional[Sequence[int]],
    control_tick_s: float,
) -> FastSimulationResult:
    """Exact sequential recursion with the event-heap's RNG draw order.

    Service times are drawn from the same ``random.Random(seed)`` stream in
    dispatch order — which for a shared FIFO queue *is* arrival order — so
    the per-request schedule reproduces :class:`ServingSimulator` to the
    bit at c = 1 (the golden test) and matches its draw sequence at any c.
    """
    rng = random.Random(seed)
    n = len(arrivals)
    c = num_servers
    A = np.asarray(arrivals, dtype=float)
    if n > 1 and not np.all(A[1:] >= A[:-1]):
        raise ValueError(
            "fast path requires arrivals in non-decreasing time order "
            "(the FIFO recursion and the event heap would diverge "
            "silently otherwise)")
    starts = np.empty(n, dtype=float)
    comps = np.empty(n, dtype=float)
    servers = np.zeros(n, dtype=np.int64)
    cfgs = np.empty(n, dtype=np.int64)
    busy = [0.0] * c

    if assignment is not None:
        assign = [int(a) for a in assignment]
        if len(assign) != c:
            raise ValueError(
                f"assignment length {len(assign)} != num_servers {c}")
    else:
        assign = None

    if c == 1:
        # pure Lindley recursion; start = max(A_i, C_{i-1}) picks one of the
        # two floats and C_i = start + draw reuses the event heap's exact
        # operand order, so the schedule is bit-for-bit identical.
        cfg0 = int(assign[0]) if assign is not None else int(static_index)
        free = 0.0
        for i in range(n):
            a = A[i]
            st = a if a >= free else free
            svc = service_sampler(cfg0, rng)
            ct = st + svc
            starts[i] = st
            comps[i] = ct
            free = ct
            busy[0] += ct - st
        cfgs.fill(cfg0)
    else:
        # Kiefer-Wolfowitz workload recursion with the deterministic
        # lowest-numbered-free-server tie-break both runtimes share.
        F = [0.0] * c
        cfg0 = int(static_index)
        for i in range(n):
            a = A[i]
            fmin = min(F)
            st = a if a >= fmin else fmin
            s = 0
            while F[s] > st:       # lowest-numbered free server
                s += 1
            cfg = assign[s] if assign is not None else cfg0
            svc = service_sampler(cfg, rng)
            ct = st + svc
            F[s] = ct
            starts[i] = st
            comps[i] = ct
            servers[i] = s
            cfgs[i] = cfg
            busy[s] += ct - st

    timeline_index = int(assign[0]) if (assign is not None and c == 1) \
        else int(static_index)
    # the scheduler records (0.0, active_index) at reset; a static assignment
    # additionally seeds the assignment timeline
    result = FastSimulationResult(
        arrival_s=A,
        start_s=starts,
        completion_s=comps,
        config_index=cfgs,
        server_id=servers,
        duration_s=duration_s,
        num_servers=c,
        per_server_busy_s=busy,
        config_timeline=[(0.0, static_index)],
        queue_depth_samples=_tick_depth_samples(A, starts, duration_s,
                                                control_tick_s),
        assignment_timeline=(
            [(0.0, tuple(assign))] if assign is not None else []),
        offered=n,
    )
    return result


def chained_lindley(
    arrivals: Sequence[float],
    stage_services: Sequence[np.ndarray],
    *,
    num_servers: Optional[Sequence[int]] = None,
    backend: str = "numpy",
    scan_impl: str = "auto",
) -> np.ndarray:
    """Tandem-network recursion: push one arrival stream through a chain of
    FIFO stages, each stage's departures feeding the next stage's arrivals
    (the workflow-DAG fast path — stage n's completions are stage n+1's
    arrivals).

    ``arrivals`` is the external arrival time per request (any order);
    ``stage_services[j]`` holds stage j's service times *in that stage's
    dispatch order* (FIFO on the stage's own arrival times, stable by
    request index on ties — how a sequential RNG would be consumed).
    Single-server stages use the closed-form prefix-scan Lindley form
    (``C = P + cummax(A - (P - S))`` — associative float reductions, so
    allclose rather than bit-exact vs. the sequential oracle; the exact
    path is :func:`repro.serving.dag.simulate_dag`); multi-server stages
    run the Kiefer-Wolfowitz sorted-workload loop.

    ``backend`` picks the engine: ``"numpy"`` (default, the authoritative
    reference — byte-stable across PRs), ``"jax"`` (raises when jax is
    missing), or ``"auto"`` (jax only for chains big enough to amortize
    dispatch, counting ``stages x slots`` — see :func:`resolve_backend`).
    On jax, all-c = 1 chains run as *one* fused multi-stage recursion:
    ``scan_impl="sequential"`` replays the numpy closed form's exact op
    order per (request, stage) and is bit-exact; ``"associative"``
    (J chained max-plus scans) is held to float64 allclose.  Chains containing c > 1 stages keep those
    stages on the carried comparator-chain scan (bit-exact), with host
    re-sorts between stages.

    Returns a ``(num_stages, n)`` array of completion times aligned to the
    *original* request order, so callers can chain further stages (e.g. a
    fork-join's element-wise max over branch completions) or subtract
    ``arrivals`` from the last row for end-to-end sojourns.
    """
    A = np.asarray(arrivals, dtype=float)
    n = A.size
    servers = ([1] * len(stage_services) if num_servers is None
               else [int(c) for c in num_servers])
    if len(servers) != len(stage_services):
        raise ValueError("need one server count per stage")
    if any(c < 1 for c in servers):
        raise ValueError("server counts must be >= 1")
    if scan_impl not in _SCAN_IMPLS:
        raise ValueError(f"unknown scan_impl {scan_impl!r} "
                         f"(expected one of {_SCAN_IMPLS})")
    stages: List[np.ndarray] = []
    for j, svc in enumerate(stage_services):
        S = np.asarray(svc, dtype=float)
        if S.shape != (n,):
            raise ValueError(
                f"stage {j}: service array shape {S.shape} != ({n},)")
        stages.append(S)
    chosen = resolve_backend(backend, num_servers=max(servers, default=1),
                             total_slots=n, num_stages=len(servers))
    if chosen == "jax" and n > 0 and stages:
        return _chained_jax(A, stages, servers, scan_impl)
    out = np.empty((len(stage_services), n), dtype=float)
    cur = A
    for j, (S, c) in enumerate(zip(stages, servers)):
        order = np.argsort(cur, kind="stable")
        a = cur[order]
        if c == 1:
            P = np.cumsum(S)
            M = np.maximum.accumulate(a - (P - S))
            C = P + M
        else:
            C = np.empty(n, dtype=float)
            free = np.zeros(c, dtype=float)
            for i in range(n):
                f0 = free[0]
                st = a[i] if a[i] > f0 else f0
                ct = st + S[i]
                free[0] = ct
                free.sort()
                C[i] = ct
        nxt = np.empty(n, dtype=float)
        nxt[order] = C
        out[j] = nxt
        cur = nxt
    return out


def simulate(
    service_sampler: ServiceSampler,
    arrivals: Sequence[float],
    duration_s: float,
    *,
    controller: Any = None,
    static_index: int = 0,
    control_tick_s: float = 0.25,
    switch_latency_s: float = 0.010,
    seed: int = 0,
    num_servers: int = 1,
    assignment: Optional[Sequence[int]] = None,
    max_batch_size: int = 1,
    batch_timeout_s: float = 0.0,
    batch_profiles: Optional[Sequence[BatchProfile]] = None,
    max_queue_depth: Optional[int] = None,
    admission_reroute: bool = False,
    queue_discipline: str = "shared",
    steal: bool = False,
    steal_threshold: Optional[int] = None,
    faults: Any = None,
    retry_budget: int = 3,
    request_timeout_s: Optional[float] = None,
    retry_backoff_s: float = 0.05,
):
    """Dispatcher: one serving scenario, fastest engine that is still exact.

    Mirrors ``ServingSimulator(...).run(arrivals, duration_s)``.  Scenarios
    :func:`fast_path_eligible` run the vectorized Lindley / Kiefer-Wolfowitz
    recursion (bit-for-bit identical schedules at c = 1, identical RNG draw
    sequence at any c); everything else constructs the event-heap
    :class:`ServingSimulator` — the exact oracle — with identical
    parameters.  Returns a :class:`FastSimulationResult` or
    :class:`SimulationResult`; both expose the same metric surface.
    """
    arr = np.asarray(arrivals, dtype=float)
    sorted_arrivals = arr.size <= 1 or bool(np.all(arr[1:] >= arr[:-1]))
    if sorted_arrivals and fast_path_eligible(
        controller=controller,
        num_servers=num_servers,
        assignment=assignment,
        max_batch_size=max_batch_size,
        batch_timeout_s=batch_timeout_s,
        batch_profiles=batch_profiles,
        max_queue_depth=max_queue_depth,
        admission_reroute=admission_reroute,
        queue_discipline=queue_discipline,
        steal=steal,
        steal_threshold=steal_threshold,
        faults=faults,
        request_timeout_s=request_timeout_s,
    ):
        return _run_fast_single(
            service_sampler,
            arrivals,
            duration_s,
            static_index=static_index,
            seed=seed,
            num_servers=num_servers,
            assignment=assignment,
            control_tick_s=control_tick_s,
        )
    return ServingSimulator(
        service_sampler,
        controller=controller,
        static_index=static_index,
        control_tick_s=control_tick_s,
        switch_latency_s=switch_latency_s,
        seed=seed,
        num_servers=num_servers,
        assignment=assignment,
        max_batch_size=max_batch_size,
        batch_timeout_s=batch_timeout_s,
        batch_profiles=batch_profiles,
        max_queue_depth=max_queue_depth,
        admission_reroute=admission_reroute,
        queue_discipline=queue_discipline,
        steal=steal,
        steal_threshold=steal_threshold,
        faults=faults,
        retry_budget=retry_budget,
        request_timeout_s=request_timeout_s,
        retry_backoff_s=retry_backoff_s,
    ).run(arrivals, duration_s)


# --------------------------------------------------------------------------
# batched sweep API
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class SweepResult:
    """One metric grid per statistic, all shaped (R, K, L) =
    (replications, configs, loads)."""

    mean_wait_s: np.ndarray
    mean_latency_s: np.ndarray
    p95_latency_s: np.ndarray
    slo_compliance: np.ndarray
    throughput_qps: np.ndarray
    num_requests: np.ndarray          # arrivals simulated per cell
    duration_s: float
    slo_s: Optional[float]

    @property
    def total_requests(self) -> int:
        return int(self.num_requests.sum())

    def over_replications(self) -> dict:
        """Replication-averaged (K, L) grids — the Planner's view."""
        return {
            "mean_wait_s": self.mean_wait_s.mean(axis=0),
            "mean_latency_s": self.mean_latency_s.mean(axis=0),
            "p95_latency_s": self.p95_latency_s.mean(axis=0),
            "slo_compliance": self.slo_compliance.mean(axis=0),
            "throughput_qps": self.throughput_qps.mean(axis=0),
        }


def _fingerprint(payload: bytes) -> int:
    """64-bit content fingerprint — the RNG-stream key material.

    Sweep streams are keyed by cell *content* (the arrival trace's bytes,
    the config's (mean, p95) bits, the rate's bits) rather than by batch
    position, which is what makes every sweep cell a pure function of its
    inputs: permuting configs/loads permutes the result grid identically,
    and evaluating a cell in a smaller batch reproduces it exactly (the
    purity property tests in tests/test_fastsim.py)."""
    h = hashlib.blake2b(payload, digest_size=8).digest()
    return int.from_bytes(h, "little")


def _poisson_trace(rng: np.random.Generator, rate_qps: float,
                   duration_s: float) -> np.ndarray:
    """One homogeneous-Poisson arrival trace: N ~ Poisson(rate * T), times
    are the order statistics of N uniforms on [0, T).

    Materializes the full trace — right for sweep cells, whose padded
    grids need the whole trace anyway.  Huge streamed replays (1e7+
    requests) should instead use the chunked generators in
    :mod:`repro.serving.traces`, which keep memory O(chunk)."""
    n = int(rng.poisson(rate_qps * duration_s))
    return np.sort(rng.uniform(0.0, duration_s, size=n))


# --------------------------------------------------------------------------
# jax backend: the same grids, recursion + reductions on the accelerator
# --------------------------------------------------------------------------

_JAX_AUTO_MIN_SLOTS = 1_000_000   # padded request slots to amortize dispatch
_JAX_MAX_SERVERS = 32             # unrolled insertion network bound (c > 1)
_SCAN_IMPLS = ("auto", "sequential", "associative", "pallas")


def jax_available() -> bool:
    """Can the jax backend run in this process?"""
    return _jax is not None


def jax_unavailable_reason() -> Optional[str]:
    """Why jax is unavailable (None when it is importable) — the reason the
    benchmark gates log when they skip the jax measurements."""
    return _JAX_IMPORT_ERROR


def resolve_backend(backend: str = "auto", *, num_servers: int = 1,
                    total_slots: Optional[int] = None,
                    num_stages: int = 1) -> str:
    """Resolve a ``backend`` request to the engine that will actually run.

    ``"numpy"`` and ``"jax"`` are literal (``"jax"`` raises with the
    import reason when jax is missing, and rejects pools past the
    insertion-network bound ``_JAX_MAX_SERVERS``).  ``"auto"`` picks jax
    only when it is importable, the pool qualifies, and the padded grid
    is big enough to amortize device dispatch and compilation; everything
    else — including jax-less installs — silently gets the numpy engine,
    which computes the same grids.

    The amortization threshold counts *recursion steps*, not flat request
    slots: a pipeline sweep pushes every one of its ``total_slots``
    (= B x N_max) padded slots through ``num_stages`` chained stage
    recursions, so the effective device work is ``total_slots x
    num_stages`` and a 3-stage grid at 3.4e5 slots/stage rightly clears
    the 1e6 bar that a flat grid of the same slot count does not.
    """
    if backend == "numpy":
        return "numpy"
    if backend == "jax":
        if _jax is None:
            raise RuntimeError(
                f"backend='jax' requested but jax is not importable "
                f"({_JAX_IMPORT_ERROR})")
        if num_servers > _JAX_MAX_SERVERS:
            raise ValueError(
                f"jax backend supports num_servers <= {_JAX_MAX_SERVERS} "
                f"(got {num_servers}); use backend='numpy'")
        return "jax"
    if backend != "auto":
        raise ValueError(f"unknown backend {backend!r} "
                         f"(expected 'numpy', 'jax', or 'auto')")
    if _jax is None or num_servers > _JAX_MAX_SERVERS:
        return "numpy"
    if total_slots is not None:
        effective = total_slots * max(int(num_stages), 1)
        if effective < _JAX_AUTO_MIN_SLOTS:
            return "numpy"
    return "jax"


def _resolve_scan_impl(scan_impl: str) -> str:
    """Pick the c = 1 scan implementation.  ``auto`` resolves by platform:
    the sequential ``lax.scan`` on CPU (O(N) work, bit-exact vs the numpy
    loop), the max-plus ``associative_scan`` on accelerators (log-depth
    parallelism across the time axis)."""
    if scan_impl not in _SCAN_IMPLS:
        raise ValueError(f"unknown scan_impl {scan_impl!r} "
                         f"(expected one of {_SCAN_IMPLS})")
    if scan_impl == "pallas":
        raise ValueError(
            "scan_impl='pallas' runs the float32 Lindley kernel, which "
            "cannot take the float64 fast-path grids; use "
            "'sequential' or 'associative'")
    if scan_impl != "auto":
        return scan_impl
    return "sequential" if _jax.default_backend() == "cpu" else "associative"


if _jax is not None:
    import functools as _functools

    def _jax_c1(At, St, impl: str):
        """(waits, lats) of the c = 1 Lindley system; inputs (N, B)."""
        if impl == "sequential":
            # same op order as the numpy reference loop => bit-exact
            def step(comp, inp):
                a, s = inp
                st = _jnp.maximum(a, comp)
                ct = st + s
                return ct, (st - a, ct - a)

            comp0 = _jnp.zeros(At.shape[1], At.dtype)
            _, (waits, lats) = _jax.lax.scan(step, comp0, (At, St))
            return waits, lats
        from ..kernels.lindley_scan import lindley_scan_ref

        C = lindley_scan_ref(At, St)
        return C - St - At, C - At

    def _jax_kw(At, St, c: int):
        """(waits, lats) of the c-server Kiefer-Wolfowitz system.  The
        carry is the ascending workload vector as c arrays; the dispatch
        serves on the earliest-free entry and re-inserts the new
        completion with an unrolled comparator chain — the same sorted
        multiset (hence bit-exact waits) as the numpy path's
        set-column-0-and-sort step."""
        B = At.shape[1]
        F0 = tuple(_jnp.zeros(B, At.dtype) for _ in range(c))

        def step(F, inp):
            a, s = inp
            st = _jnp.maximum(a, F[0])
            ct = st + s
            cur = ct
            out = []
            for j in range(1, c):
                out.append(_jnp.minimum(F[j], cur))
                cur = _jnp.maximum(F[j], cur)
            out.append(cur)
            return tuple(out), (st - a, ct - a)

        _, (waits, lats) = _jax.lax.scan(step, F0, (At, St))
        return waits, lats

    @_jax.jit
    def _jax_chained_seq(At, St):
        """Per-stage completions (J, N, B) of an all-c = 1 tandem chain.

        One fused ``lax.scan`` over requests carrying every stage's
        closed-form registers: per stage j the numpy reference computes
        ``P = cumsum(S)``, ``M = cummax(A - (P - S))``, ``C = P + M`` —
        all per-element ops whose operands never mix across requests
        beyond the two sequential carries, so replaying exactly those
        ops per (request, stage) with carry ``(p_j, m_j)`` produces
        *bit-identical* completions while stage j+1 consumes stage j's
        fresh completion in-register (no host round-trip, no re-sort:
        c = 1 departures are non-decreasing in dispatch order).
        """
        J = St.shape[0]
        zero = _jnp.zeros(At.shape[1:], At.dtype)
        neg = _jnp.full(At.shape[1:], -_jnp.inf, At.dtype)
        carry0 = (tuple(zero for _ in range(J)),
                  tuple(neg for _ in range(J)))

        def step(carry, inp):
            ps, ms = carry
            arr, s_col = inp            # (B,), (J, B)
            nps, nms, comps = [], [], []
            for j in range(J):          # static unroll over stages
                s = s_col[j]
                p = ps[j] + s           # P_i = P_{i-1} + S_i
                t = arr - (p - s)       # A_i - (P_i - S_i)
                m = _jnp.maximum(ms[j], t)
                comp = p + m            # C_i = P_i + M_i
                nps.append(p)
                nms.append(m)
                comps.append(comp)
                arr = comp              # feeds stage j+1
            return (tuple(nps), tuple(nms)), _jnp.stack(comps)

        _, C = _jax.lax.scan(step, carry0, (At, _jnp.moveaxis(St, 0, 1)))
        return _jnp.moveaxis(C, 0, 1)   # (J, N, B)

    @_functools.partial(_jax.jit, static_argnames=("c",))
    def _jax_kw_chain(At, St, *, c: int):
        """Completion times (N, B) of one c-server Kiefer-Wolfowitz stage
        — the PR-6 carried comparator-chain scan, emitting completions
        (not waits) so tandem callers can feed the next stage.  Identical
        float ops to the numpy sorted-workload loop => bit-exact."""
        B = At.shape[1]
        F0 = tuple(_jnp.zeros(B, At.dtype) for _ in range(c))

        def step(F, inp):
            a, s = inp
            st = _jnp.maximum(a, F[0])
            ct = st + s
            cur = ct
            out = []
            for j in range(1, c):
                out.append(_jnp.minimum(F[j], cur))
                cur = _jnp.maximum(F[j], cur)
            out.append(cur)
            return tuple(out), ct

        _, C = _jax.lax.scan(step, F0, (At, St))
        return C

    @_functools.partial(_jax.jit, static_argnames=("impl",))
    def _jax_c1_chain(At, St, *, impl: str):
        """Completion times (N, B) of one c = 1 stage, by scan impl."""
        if impl == "sequential":
            return _jax_chained_seq(At, St[None])[0]
        from ..kernels.lindley_scan import lindley_scan_ref

        return lindley_scan_ref(At, St)

    @_functools.partial(_jax.jit, static_argnames=("impl",))
    def _jax_chained_fused(At, St, *, impl: str):
        """All-c = 1 tandem, fused per impl: one multi-stage sequential
        scan (bit-exact) or J chained max-plus associative scans
        (allclose)."""
        if impl == "sequential":
            return _jax_chained_seq(At, St)
        from ..kernels.lindley_scan import chained_lindley_scan_ref

        return chained_lindley_scan_ref(At, St)

    def _jax_pipeline_grid(A, S, topo_meta, impl: str, out_pos=None):
        """Per-stage completions of a batched workflow DAG: device scans,
        host permutations.

        ``A`` is the (B, N) grid of sorted external arrival times (+inf
        padding) and ``S`` the (J, N, B) dispatch-order service grids —
        host numpy arrays; returns a list of (B, N) per-stage completion
        grids in request order, indexed by topological position
        (``out_pos`` limits which positions are materialized — the
        others stay ``None``).
        ``topo_meta`` is the static topology, one entry per topological
        position: ``(preds, c, needs_sort)`` with ``preds`` the
        predecessor *positions* (empty = external arrivals).

        The split follows the CPU cost profile, not aesthetics: XLA's
        stable sort is ~100x slower than ``np.argsort`` on these grids
        (~0.4 s vs ~5 ms at 4200 x 512), while the Lindley /
        Kiefer-Wolfowitz scans are the one part numpy cannot vectorize.
        So joins (element-wise ``maximum``) and stable argsorts stay in
        numpy — device round-trips are cheap on CPU (`np.asarray` of a
        device buffer is zero-copy) — and only the scans run jitted.
        Maximal runs of c = 1 stages fed straight by their topological
        predecessor collapse into one fused multi-stage device call
        (:func:`_jax_chained_fused`).

        Permutations are lazy: each stage's completions are kept in its
        own *dispatch* order together with the permutation mapping
        dispatch position back to request index, and request order is
        only materialized where per-request identity matters — at
        fork-join merges and at the requested output stages.  A
        single-pred successor consumes the dispatch-order values
        directly, so its argsort runs on the pred's nearly-sorted
        output, where numpy's stable timsort exploits the runs (~4x
        faster than on request-order data), and the per-stage scatter
        back to request order disappears.  Queueing semantics are
        unchanged: dispatch order is sorted arrival order either way
        (completion *values* are identical; under exact float ties the
        tie-broken request pairing may differ from the numpy
        reference's, a measure-zero event for continuous service
        draws).  Padded slots carry ``+inf`` arrivals so they stay
        trailing through every sort and join.
        """
        J = len(topo_meta)
        # per stage: (dispatch-order completions (B, N), perm (B, N) or
        # None; perm[b, t] = request index of dispatch position t)
        disp: list = [None] * J
        req_cache: dict = {}

        def as_request(j):
            vals, perm = disp[j]
            if perm is None:
                return vals
            out = req_cache.get(j)
            if out is None:
                out = np.empty_like(vals)
                np.put_along_axis(out, perm, vals, axis=-1)
                req_cache[j] = out
            return out

        i = 0
        while i < J:
            preds, c, _ = topo_meta[i]
            seg = [i]
            if c == 1:
                k = i + 1
                while (k < J and topo_meta[k][0] == (k - 1,)
                       and topo_meta[k][1] == 1):
                    seg.append(k)
                    k += 1
            if not preds:
                arr, perm, in_sorted = A, None, True
            elif len(preds) == 1:
                arr, perm = disp[preds[0]]
                in_sorted = topo_meta[preds[0]][1] == 1   # c=1: monotone
            else:
                arr = as_request(preds[0])
                for p in preds[1:]:
                    arr = np.maximum(arr, as_request(p))
                perm = None
                in_sorted = all(topo_meta[p][1] == 1
                                and disp[p][1] is None for p in preds)
            if not in_sorted:
                rel = np.argsort(arr, axis=-1, kind="stable")
                arr = np.take_along_axis(arr, rel, axis=-1)
                perm = (rel if perm is None
                        else np.take_along_axis(perm, rel, axis=-1))
            At = _jnp.asarray(np.ascontiguousarray(arr.T))
            if c == 1:
                St = _jnp.asarray(S[seg[0]:seg[-1] + 1])
                C = np.asarray(_jax_chained_fused(At, St, impl=impl))
                for o, j in enumerate(seg):
                    disp[j] = (np.ascontiguousarray(C[o].T), perm)
            else:
                St = _jnp.asarray(S[i])
                C = np.asarray(_jax_kw_chain(At, St, c=c))
                disp[i] = (np.ascontiguousarray(C.T), perm)
            i = seg[-1] + 1
        wanted = range(J) if out_pos is None else out_pos
        out: list = [None] * J
        for j in wanted:
            out[j] = as_request(j)
        return out

    @_functools.partial(_jax.jit,
                        static_argnames=("impl", "c", "has_slo"))
    def _jax_sweep(A, S, counts, slo, *, impl: str, c: int, has_slo: bool):
        """Full device sweep: (B, N) grids in, per-cell statistics out.

        Returns (mean_wait, mean_lat, compliance, lats) with lats (B, N)
        zeroed at padding — the p95 order statistics stay on the host
        (:func:`_p95_cells`), where an O(n) partition beats XLA's CPU
        sort by an order of magnitude."""
        At, St = A.T, S.T                      # (N, B): scan layout
        if c == 1:
            waits, lats = _jax_c1(At, St, impl)
        else:
            waits, lats = _jax_kw(At, St, c)
        n_max = At.shape[0]
        active = _jnp.arange(n_max)[:, None] < counts[None, :]
        waits = _jnp.where(active, waits, 0.0)
        lats = _jnp.where(active, lats, 0.0)
        n_eff = _jnp.maximum(counts, 1).astype(At.dtype)
        mean_wait = waits.sum(axis=0) / n_eff
        mean_lat = lats.sum(axis=0) / n_eff
        if has_slo:
            ok = _jnp.sum((lats <= slo) & active, axis=0)
            compliance = _jnp.where(counts > 0, ok / n_eff, 1.0)
        else:
            compliance = _jnp.ones(At.shape[1], At.dtype)
        return mean_wait, mean_lat, compliance, lats.T

    def _chained_jax(A, stage_S, servers, scan_impl: str) -> np.ndarray:
        """jax engine for :func:`chained_lindley` (single scenario, B = 1).

        All-c = 1 chains take the fused multi-stage path after one host
        argsort of the external arrivals (every downstream stage's
        dispatch order is then the identity); chains with any c > 1
        stage run stage-by-stage with a host re-sort between stages,
        because Kiefer-Wolfowitz completions are not monotone in
        dispatch order."""
        impl = _resolve_scan_impl(scan_impl)
        n = A.size
        out = np.empty((len(stage_S), n), dtype=float)
        with _jax.enable_x64(True):
            if all(c == 1 for c in servers):
                order = np.argsort(A, kind="stable")
                At = _jnp.asarray(A[order][:, None])
                St = _jnp.asarray(np.stack(stage_S)[:, :, None])
                C = np.asarray(_jax_chained_fused(At, St, impl=impl))[:, :, 0]
                out[:, order] = C
            else:
                cur = A
                for j, (S, c) in enumerate(zip(stage_S, servers)):
                    order = np.argsort(cur, kind="stable")
                    At = _jnp.asarray(cur[order][:, None])
                    St = _jnp.asarray(S[:, None])
                    if c == 1:
                        C = np.asarray(_jax_c1_chain(At, St, impl=impl))[:, 0]
                    else:
                        C = np.asarray(_jax_kw_chain(At, St, c=c))[:, 0]
                    nxt = np.empty(n, dtype=float)
                    nxt[order] = C
                    out[j] = nxt
                    cur = nxt
        return out


def _p95_cells(lats: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Per-cell p95 with the repo-wide interpolation convention, via an
    O(n) two-point partition instead of a full sort.  ``lats`` is (B, N)
    with each cell's ``counts[b]`` latencies leading the row; partition
    yields exactly the order statistics the numpy backend's sort-based
    computation reads, so the backends agree bit-for-bit here whenever
    the latency grids do."""
    p95 = np.zeros(len(counts), dtype=float)
    for b, n in enumerate(counts):
        n = int(n)
        if n == 0:
            continue
        pos = 0.95 * (n - 1)
        lo = int(pos)
        hi = min(lo + 1, n - 1)
        part = np.partition(lats[b, :n], (lo, hi))
        p95[b] = part[lo] + (part[hi] - part[lo]) * (pos - lo)
    return p95


def _sweep_jax(A: np.ndarray, S: np.ndarray, cell_counts: np.ndarray,
               c: int, slo_s: Optional[float], scan_impl: str):
    """Host wrapper for the jax backend: scoped x64, device reductions,
    host p95.  Inputs are the same (B, N_max) grids the numpy backend
    consumes — the draws are shared, only the evaluation engine differs."""
    impl = _resolve_scan_impl(scan_impl)
    with _jax.enable_x64(True):
        mean_wait, mean_lat, compliance, lats = _jax_sweep(
            _jnp.asarray(A), _jnp.asarray(S), _jnp.asarray(cell_counts),
            _jnp.asarray(float(slo_s) if slo_s is not None else 0.0),
            impl=impl, c=c, has_slo=slo_s is not None)
        lats_host = np.asarray(lats)
        out = (np.asarray(mean_wait), np.asarray(mean_lat),
               np.asarray(compliance))
    return (*out, _p95_cells(lats_host, cell_counts))


def simulate_batch(
    service_mean_s: Sequence[float],
    service_p95_s: Optional[Sequence[float]] = None,
    *,
    arrival_rates_qps: Optional[Sequence[float]] = None,
    arrival_traces: Optional[Sequence[Sequence[float]]] = None,
    duration_s: float,
    num_servers: int = 1,
    replications: int = 1,
    slo_s: Optional[float] = None,
    seed: int = 0,
    backend: str = "auto",
    scan_impl: str = "auto",
) -> SweepResult:
    """Batched Lindley / Kiefer-Wolfowitz sweep: R replications x K configs
    x L load patterns evaluated as one array program, one result grid out.

    Parameters
    ----------
    service_mean_s: per-config mean service time (the K axis).
    service_p95_s: per-config p95; when given, service times are lognormal
        matched to (mean, p95) exactly as
        :func:`repro.serving.simulator.lognormal_sampler_from_profile`;
        when None, exponential with the given mean (the M/M/c case, where
        the sweep converges to the Erlang-C prediction).
    arrival_rates_qps: the L axis as homogeneous Poisson rates — each
        (replication r, load l) cell draws its own trace.  Mutually
        exclusive with ``arrival_traces``.
    arrival_traces: the L axis as explicit arrival-time traces, replayed
        identically across replications and configs (common random
        numbers on the arrival process); service draws still differ per
        (replication, config).
    num_servers: pool size c (the recursion handles any c >= 1).
    replications: independent stochastic repeats R.
    slo_s: latency SLO for the compliance grid (compliance is 1.0 where
        ``slo_s`` is None).
    backend: ``"numpy"`` (authoritative reference), ``"jax"`` (same grids
        evaluated on the accelerator; raises when jax is missing), or
        ``"auto"`` (jax only for sweeps big enough to amortize dispatch —
        see :func:`resolve_backend`).  Both backends consume *identical*
        host-generated arrival/service draws; the jax grids agree with
        numpy to float64 allclose (bit-for-bit for the default CPU
        sequential scan), and the numpy c = 1 path stays bit-for-bit
        against the event heap.
    scan_impl: c = 1 time-scan choice for the jax backend — ``"auto"``
        (sequential on CPU, associative on accelerators),
        ``"sequential"`` (``lax.scan``, bit-exact vs numpy),
        or ``"associative"`` (max-plus ``lax.associative_scan``);
        ``"pallas"`` (the float32 ``repro.kernels.lindley_scan`` kernel)
        is refused on these float64 grids.  Ignored for c > 1, which
        always uses the comparator-insertion ``lax.scan``, and by the
        numpy backend.

    Determinism: cell (r, k, l) depends only on ``seed``, the replication
    index r, and its coordinates' *inputs* (rate or trace content, config
    stats, c, duration) — never on the batch composition.  Arrival streams
    are keyed ``(seed, r, rate-bits)`` and service streams ``(seed, r,
    config-fingerprint, trace-fingerprint)``, so permuting or slicing the
    config/load axes permutes or slices the result grid identically, and
    growing ``replications`` never changes the earlier replications'
    cells.  (Two loads with the *same* rate share a trace per replication
    — common random numbers by content, by design.)
    """
    means = np.asarray(service_mean_s, dtype=float)
    if means.ndim != 1 or means.size == 0:
        raise ValueError("service_mean_s must be a non-empty 1-D sequence")
    if np.any(means <= 0):
        raise ValueError("service means must be positive")
    K = means.size
    if service_p95_s is not None:
        p95s = np.asarray(service_p95_s, dtype=float)
        if p95s.shape != means.shape:
            raise ValueError("service_p95_s must match service_mean_s")
        ln_params = [lognormal_params(m, p) for m, p in zip(means, p95s)]
    else:
        ln_params = None
    if (arrival_rates_qps is None) == (arrival_traces is None):
        raise ValueError(
            "exactly one of arrival_rates_qps / arrival_traces is required")
    if duration_s <= 0:
        raise ValueError("duration_s must be positive")
    if replications < 1 or num_servers < 1:
        raise ValueError("replications and num_servers must be >= 1")
    if scan_impl not in _SCAN_IMPLS:
        raise ValueError(f"unknown scan_impl {scan_impl!r} "
                         f"(expected one of {_SCAN_IMPLS})")
    R, c = int(replications), int(num_servers)

    # -- per-(r, l) arrival traces ------------------------------------------
    base_seed = seed & 0x7FFFFFFF
    if arrival_traces is not None:
        fixed = [np.asarray(t, dtype=float) for t in arrival_traces]
        L = len(fixed)
        traces = [[fixed[l] for l in range(L)] for _ in range(R)]
    else:
        rates = [float(x) for x in arrival_rates_qps]
        L = len(rates)
        traces = []
        for r in range(R):
            row = []
            for rate in rates:
                rate_fp = _fingerprint(np.float64(rate).tobytes()
                                       + np.float64(duration_s).tobytes())
                g = np.random.Generator(np.random.PCG64(
                    np.random.SeedSequence([base_seed, 1, r, rate_fp])))
                row.append(_poisson_trace(g, rate, duration_s))
            traces.append(row)
    if L == 0:
        raise ValueError("need at least one load pattern")

    # config content fingerprints (service-stream keys)
    if ln_params is not None:
        cfg_fps = [_fingerprint(b"ln" + np.float64(m).tobytes()
                                + np.float64(p).tobytes())
                   for m, p in zip(means, p95s)]
    else:
        cfg_fps = [_fingerprint(b"exp" + np.float64(m).tobytes())
                   for m in means]

    counts = np.array([[traces[r][l].size for l in range(L)]
                       for r in range(R)], dtype=np.int64)
    n_max = int(counts.max()) if counts.size else 0

    # -- assemble the padded request grid, B = R*K*L scenarios --------------
    # Layout is (N, B): step i of the recursion reads/writes contiguous
    # rows.  Padding is *zeros* (arrival 0, service 0), which makes the
    # recursion self-masking — a padded slot dispatches instantly with zero
    # service and leaves every workload register unchanged — so the inner
    # loop needs no masking at all; padded waits/latencies are zeroed once
    # after the loop.
    B = R * K * L
    A = np.zeros((B, n_max), dtype=float)
    S = np.zeros((B, n_max), dtype=float)
    cell_counts = np.zeros(B, dtype=np.int64)

    def cell(r: int, k: int, l: int) -> int:
        return (r * K + k) * L + l

    for r in range(R):
        for l in range(L):
            trace = traces[r][l]
            n = trace.size
            trace_fp = _fingerprint(trace.tobytes())
            for k in range(K):
                b = cell(r, k, l)
                cell_counts[b] = n
                if n == 0:
                    continue
                A[b, :n] = trace
                g = np.random.Generator(np.random.PCG64(np.random.SeedSequence(
                    [base_seed, 2, r, cfg_fps[k], trace_fp])))
                if ln_params is not None:
                    mu, sigma = ln_params[k]
                    S[b, :n] = g.lognormal(mean=mu, sigma=sigma, size=n)
                else:
                    S[b, :n] = g.exponential(scale=means[k], size=n)

    chosen = resolve_backend(backend, num_servers=c, total_slots=B * n_max)
    if chosen == "jax" and n_max > 0:
        mean_wait, mean_lat, compliance, p95 = _sweep_jax(
            A, S, cell_counts, c, slo_s, scan_impl)
    else:
        A = np.ascontiguousarray(A.T)      # (N, B)
        S = np.ascontiguousarray(S.T)

        # -- the vectorized recursion (sequential in i, batched over
        #    scenarios) --
        waits = np.empty((n_max, B), dtype=float)
        lats = np.empty((n_max, B), dtype=float)
        if c == 1:
            comp = np.zeros(B, dtype=float)
            for i in range(n_max):
                a = A[i]
                st = np.maximum(a, comp)                # Lindley step
                comp = st + S[i]
                waits[i] = st - a
                lats[i] = comp - a
        else:
            # Kiefer-Wolfowitz sorted-workload form: each cell's service
            # law is server-independent, so only the multiset of server
            # free times matters — keep it sorted ascending, serve on the
            # earliest-free (column 0), re-sort.  Identical waits to the
            # event heap's lowest-free-id dispatch, without tracking
            # server identities.
            F = np.zeros((B, c), dtype=float)
            for i in range(n_max):
                a = A[i]
                st = np.maximum(a, F[:, 0])
                ct = st + S[i]
                F[:, 0] = ct
                F.sort(axis=1)
                waits[i] = st - a
                lats[i] = ct - a

        active = np.arange(n_max)[:, None] < cell_counts[None, :]   # (N, B)
        if n_max > 0:
            waits *= active
            lats *= active

        # -- per-cell statistics --------------------------------------------
        n_eff = np.maximum(cell_counts, 1).astype(float)
        mean_wait = waits.sum(axis=0) / n_eff
        mean_lat = lats.sum(axis=0) / n_eff
        if slo_s is not None and n_max > 0:
            ok = np.count_nonzero((lats <= slo_s) & active, axis=0)
            compliance = np.where(cell_counts > 0, ok / n_eff, 1.0)
        else:
            compliance = np.ones(B, dtype=float)

        # p95 with the repo-wide interpolation convention: sort each column
        # (inf padding sinks to the tail), index pos = 0.95 * (n - 1).
        p95 = np.zeros(B, dtype=float)
        if n_max > 0:
            padded = np.where(active, lats, np.inf)
            srt = np.sort(padded, axis=0)
            nz = cell_counts > 0
            pos = 0.95 * (cell_counts[nz] - 1)
            lo = pos.astype(np.int64)
            hi = np.minimum(lo + 1, cell_counts[nz] - 1)
            cols_nz = np.flatnonzero(nz)
            xlo = srt[lo, cols_nz]
            xhi = srt[hi, cols_nz]
            p95[cols_nz] = xlo + (xhi - xlo) * (pos - lo)

    shape = (R, K, L)
    return SweepResult(
        mean_wait_s=mean_wait.reshape(shape),
        mean_latency_s=mean_lat.reshape(shape),
        p95_latency_s=p95.reshape(shape),
        slo_compliance=compliance.reshape(shape),
        throughput_qps=(cell_counts / duration_s).reshape(shape),
        num_requests=cell_counts.reshape(shape),
        duration_s=duration_s,
        slo_s=slo_s,
    )
