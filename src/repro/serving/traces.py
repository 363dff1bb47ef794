"""Chunked arrival-trace generation and streaming replay.

The sweep engine (:mod:`repro.serving.fastsim`) materializes every trace —
right for R x K x L grids of bounded cells, hopeless for the million-user
replays the Planner wants validated against realistic day-scale load: a
1e8-request trace is ~0.8 GB *per array*, and the scalar thinning loop in
:func:`repro.serving.workload.generate_arrivals` would take minutes before
a single request is simulated.  This module streams instead:

- **Chunked generators** (:class:`ChunkedPoissonTrace` for rate-function
  loads — diurnal, flash crowd — and :class:`ChunkedMMPPTrace` for the
  Markov-modulated bursty process) yield sorted numpy chunks of arrival
  times covering ``[0, duration_s)`` window by window.  Thinning is
  vectorized per window (Lewis & Shedler with a per-window envelope), so
  generation cost is a few array ops per chunk and resident memory is
  O(chunk), never O(total requests).
- **Streaming replay** (:func:`replay_mix` / :func:`replay_trace`) runs
  the Lindley (c = 1) or Kiefer-Wolfowitz (c > 1) recursion chunk by
  chunk, carrying the workload state across chunk boundaries — the
  replayed system is *identical* to simulating the whole trace at once;
  only the statistics are streamed.  Mean wait / latency, SLO compliance,
  throughput, and max latency are exact; p95 comes from a fixed-memory
  power-of-two rebinned histogram (:class:`StreamingQuantile`) whose
  error is bounded by one bin width (reported as ``p95_resolution_s``).

Engines.  c = 1 replay uses the closed-form prefix-scan form of the
Lindley recursion — with prefix sums ``P_i = sum_{j<=i} S_j`` and initial
backlog ``C_0``, ``C_i = P_i + max(C_0, max_{j<=i}(A_j - P_{j-1}))`` — two
vectorized cumulative ops per chunk, no Python-per-request loop.  c > 1
prefers the jax comparator scan from the fastsim backend work (carried
sorted workload vector, unrolled insertion network) and falls back to a
numpy per-request loop when jax is unavailable.  Replay therefore never
touches the event heap; the event-heap simulator remains the *oracle*
these engines are tested against on small traces.

Determinism and purity.  A trace is fully determined by its constructor
parameters and ``seed`` (the window schedule is part of the identity —
documented on each class).  Service streams are keyed by content
fingerprints ``(seed, lane-config, trace-fingerprint)`` exactly in the
:func:`repro.serving.fastsim.simulate_batch` style, so replaying a subset
of the mix ladder reproduces those lanes' statistics bit-for-bit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .fastsim import (
    _fingerprint,
    jax_available,
    jax_unavailable_reason,
    lognormal_params,
)
from . import fastsim as _fs

__all__ = [
    "ChunkedMMPPTrace",
    "ChunkedPoissonTrace",
    "ReplayStats",
    "StreamingQuantile",
    "bursty_mmpp_trace",
    "diurnal_trace",
    "flash_crowd_trace",
    "DagReplayStats",
    "replay_dag",
    "replay_mix",
    "replay_trace",
]

# Vectorized rate function: array of times -> array of instantaneous rates.
VectorRateFn = Callable[[np.ndarray], np.ndarray]

_DEFAULT_CHUNK_REQUESTS = 262_144


def _thin_window(rng: np.random.Generator, t0: float, t1: float,
                 lam: float, rate_fn: VectorRateFn) -> np.ndarray:
    """Vectorized Lewis-Shedler thinning on one window: homogeneous
    candidates at envelope rate ``lam``, kept with probability
    ``rate(t) / lam``.  Sorted candidates stay sorted through the mask."""
    if lam <= 0.0 or t1 <= t0:
        return np.empty(0, dtype=float)
    n = int(rng.poisson(lam * (t1 - t0)))
    if n == 0:
        return np.empty(0, dtype=float)
    times = np.sort(rng.uniform(t0, t1, size=n))
    keep = rng.uniform(0.0, lam, size=n) <= rate_fn(times)
    return times[keep]


class ChunkedPoissonTrace:
    """Non-homogeneous Poisson arrivals from a vectorized rate function,
    yielded as sorted chunks of O(``window_s`` x rate) times.

    The envelope for each window is probed at 65 evenly spaced points with
    5% headroom (capped by the global ``rate_max``), which is exact for
    the smooth built-in patterns; pass an explicit ``rate_max`` or a
    finer ``window_s`` for rate functions with sub-window spikes.

    Identity: the realized trace is a pure function of ``(label, seed,
    duration_s, window_s, rate_max)`` — the window schedule is part of the
    trace, so two traces differing only in ``window_s`` are *different*
    (equally distributed) traces.  ``fingerprint`` hashes exactly that
    tuple and keys the replay's service streams.
    """

    kind = "nhpp"

    def __init__(self, rate_fn: VectorRateFn, duration_s: float, *,
                 seed: int = 0, label: str = "nhpp",
                 rate_max: Optional[float] = None,
                 window_s: Optional[float] = None):
        if duration_s <= 0:
            raise ValueError("duration_s must be positive")
        self.rate_fn = rate_fn
        self.duration_s = float(duration_s)
        self.seed = int(seed)
        self.label = label
        if rate_max is None:
            probes = rate_fn(np.linspace(0.0, self.duration_s, 2049))
            rate_max = float(np.max(probes)) * 1.05 + 1e-9
        if rate_max <= 0:
            raise ValueError("rate_max must be positive")
        self.rate_max = float(rate_max)
        if window_s is None:
            window_s = _DEFAULT_CHUNK_REQUESTS / self.rate_max
        self.window_s = float(min(max(window_s, 1e-3), self.duration_s))
        self.fingerprint = _fingerprint(
            b"nhpp" + label.encode() + np.float64(self.duration_s).tobytes()
            + np.int64(self.seed).tobytes()
            + np.float64(self.window_s).tobytes()
            + np.float64(self.rate_max).tobytes())

    def chunks(self) -> Iterator[np.ndarray]:
        """Yield sorted arrival-time chunks; concatenated, they are one
        NHPP realization on ``[0, duration_s)``.  Empty windows are
        skipped."""
        rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([self.seed & 0x7FFFFFFF, self.fingerprint])))
        n_windows = int(math.ceil(self.duration_s / self.window_s))
        for w in range(n_windows):
            t0 = w * self.window_s
            t1 = min(t0 + self.window_s, self.duration_s)
            probes = self.rate_fn(np.linspace(t0, t1, 65))
            lam = min(float(np.max(probes)) * 1.05 + 1e-12, self.rate_max)
            chunk = _thin_window(rng, t0, t1, lam, self.rate_fn)
            if chunk.size:
                yield chunk


class ChunkedMMPPTrace:
    """Bursty arrivals as a 2-state Markov-modulated Poisson process.

    The modulating chain alternates base periods (rate ``base_qps``,
    mean sojourn ``mean_gap_s``) and bursts (rate ``base_qps x
    burst_factor``, mean sojourn ``mean_burst_s``) with exponential
    sojourns — the renewal structure behind
    :func:`repro.serving.workload.bursty_pattern`, as a proper doubly
    stochastic process.  The burst rate is an *exact* envelope, so
    thinning here has no probing error.

    The modulating path is drawn from its own stream, so it does not
    depend on the window schedule; only the candidate draws do.  Chunks
    stream with O(window) memory like :class:`ChunkedPoissonTrace`.
    """

    kind = "mmpp"

    def __init__(self, base_qps: float = 1.5, *, burst_factor: float = 4.0,
                 mean_burst_s: float = 10.0, mean_gap_s: float = 25.0,
                 duration_s: float, seed: int = 0,
                 window_s: Optional[float] = None):
        if duration_s <= 0:
            raise ValueError("duration_s must be positive")
        if base_qps <= 0 or burst_factor < 1.0:
            raise ValueError("base_qps must be positive, burst_factor >= 1")
        if mean_burst_s <= 0 or mean_gap_s <= 0:
            raise ValueError("sojourn means must be positive")
        self.base_qps = float(base_qps)
        self.burst_factor = float(burst_factor)
        self.mean_burst_s = float(mean_burst_s)
        self.mean_gap_s = float(mean_gap_s)
        self.duration_s = float(duration_s)
        self.seed = int(seed)
        self.rate_max = self.base_qps * self.burst_factor
        if window_s is None:
            window_s = _DEFAULT_CHUNK_REQUESTS / self.rate_max
        self.window_s = float(min(max(window_s, 1e-3), self.duration_s))
        self.fingerprint = _fingerprint(
            b"mmpp" + np.float64(self.base_qps).tobytes()
            + np.float64(self.burst_factor).tobytes()
            + np.float64(self.mean_burst_s).tobytes()
            + np.float64(self.mean_gap_s).tobytes()
            + np.float64(self.duration_s).tobytes()
            + np.int64(self.seed).tobytes()
            + np.float64(self.window_s).tobytes())

    def chunks(self) -> Iterator[np.ndarray]:
        base = self.seed & 0x7FFFFFFF
        seg_rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([base, self.fingerprint, 1])))
        cand_rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([base, self.fingerprint, 2])))
        rates = (self.base_qps, self.rate_max)
        sojourns = (self.mean_gap_s, self.mean_burst_s)
        # lazily extended piecewise-constant modulating path
        seg_starts: List[float] = [0.0]
        seg_rates: List[float] = [rates[0]]
        state = 0
        seg_end = float(seg_rng.exponential(sojourns[0]))

        def extend_to(t: float) -> None:
            nonlocal state, seg_end
            while seg_end < t:
                state = 1 - state
                seg_starts.append(seg_end)
                seg_rates.append(rates[state])
                seg_end += float(seg_rng.exponential(sojourns[state]))

        def rate_fn(times: np.ndarray) -> np.ndarray:
            idx = np.searchsorted(starts_arr, times, side="right") - 1
            return rates_arr[idx]

        n_windows = int(math.ceil(self.duration_s / self.window_s))
        for w in range(n_windows):
            t0 = w * self.window_s
            t1 = min(t0 + self.window_s, self.duration_s)
            extend_to(t1)
            starts_arr = np.asarray(seg_starts)
            rates_arr = np.asarray(seg_rates)
            chunk = _thin_window(cand_rng, t0, t1, self.rate_max, rate_fn)
            # drop segments fully behind the window front (O(chunk) memory)
            cut = int(np.searchsorted(starts_arr, t1, side="right")) - 1
            if cut > 0:
                del seg_starts[:cut]
                del seg_rates[:cut]
            if chunk.size:
                yield chunk


def diurnal_trace(base_qps: float, *, amplitude: float = 0.8,
                  period_s: float = 86_400.0, duration_s: float,
                  seed: int = 0,
                  window_s: Optional[float] = None) -> ChunkedPoissonTrace:
    """Smooth diurnal cycle ``base x (1 + amplitude sin(2 pi t / T))`` —
    the day-scale load shape, defaulting to a 24 h period (the sweep-cell
    twin :func:`repro.serving.workload.diurnal_pattern` keeps its short
    demo period)."""
    if not 0.0 <= amplitude < 1.0:
        raise ValueError("amplitude must be in [0, 1)")

    def rate(t: np.ndarray) -> np.ndarray:
        return base_qps * (1.0 + amplitude * np.sin(2.0 * np.pi * t / period_s))

    label = f"diurnal:{base_qps!r}:{amplitude!r}:{period_s!r}"
    return ChunkedPoissonTrace(rate, duration_s, seed=seed, label=label,
                               rate_max=base_qps * (1.0 + amplitude) * 1.01,
                               window_s=window_s)


def flash_crowd_trace(base_qps: float, *, peak_factor: float = 10.0,
                      crowd_start_s: float, ramp_s: float = 5.0,
                      hold_s: float = 20.0, duration_s: float, seed: int = 0,
                      window_s: Optional[float] = None) -> ChunkedPoissonTrace:
    """Flash crowd: linear ramp to ``peak_factor x base``, a hold, and a
    symmetric ramp down — :func:`repro.serving.workload.flash_crowd_pattern`
    vectorized for chunked generation."""
    if peak_factor < 1.0:
        raise ValueError("peak_factor must be >= 1")
    peak = base_qps * peak_factor
    up0, up1 = crowd_start_s, crowd_start_s + ramp_s
    dn0, dn1 = up1 + hold_s, up1 + hold_s + ramp_s
    xp = [0.0, up0, up1, dn0, dn1, max(duration_s, dn1 + 1.0)]
    fp = [base_qps, base_qps, peak, peak, base_qps, base_qps]

    def rate(t: np.ndarray) -> np.ndarray:
        return np.interp(t, xp, fp)

    label = (f"flash:{base_qps!r}:{peak_factor!r}:{crowd_start_s!r}"
             f":{ramp_s!r}:{hold_s!r}")
    return ChunkedPoissonTrace(rate, duration_s, seed=seed, label=label,
                               rate_max=peak * 1.01, window_s=window_s)


def bursty_mmpp_trace(base_qps: float = 1.5, *, burst_factor: float = 4.0,
                      mean_burst_s: float = 10.0, mean_gap_s: float = 25.0,
                      duration_s: float, seed: int = 0,
                      window_s: Optional[float] = None) -> ChunkedMMPPTrace:
    """Bursty MMPP with the paper-pattern defaults (2-5x short bursts ->
    one representative 4x burst rate, 10 s mean bursts, 25 s mean gaps)."""
    return ChunkedMMPPTrace(base_qps, burst_factor=burst_factor,
                            mean_burst_s=mean_burst_s, mean_gap_s=mean_gap_s,
                            duration_s=duration_s, seed=seed,
                            window_s=window_s)


class StreamingQuantile:
    """Fixed-memory quantile sketch: a linear histogram over ``[0, hi)``
    that doubles its range (merging bin pairs exactly) whenever a value
    lands past it.  The reported quantile is the upper edge of the bin
    holding the target order statistic, so the error vs the exact order
    statistic is at most one bin width (``resolution_s``); counts are
    never approximated, only positions within a bin."""

    def __init__(self, num_bins: int = 8192, initial_max: float = 1.0):
        if num_bins < 2 or num_bins % 2:
            raise ValueError("num_bins must be an even integer >= 2")
        if initial_max <= 0:
            raise ValueError("initial_max must be positive")
        self._nb = int(num_bins)
        self._hi = float(initial_max)
        self._counts = np.zeros(self._nb, dtype=np.int64)
        self._n = 0

    def _double(self) -> None:
        merged = self._counts.reshape(-1, 2).sum(axis=1)
        self._counts = np.concatenate(
            [merged, np.zeros(self._nb // 2, dtype=np.int64)])
        self._hi *= 2.0

    def update(self, values: np.ndarray) -> None:
        x = np.asarray(values, dtype=float).ravel()
        if x.size == 0:
            return
        if np.any(x < 0.0):
            raise ValueError("StreamingQuantile tracks non-negative values")
        top = float(x.max())
        while top >= self._hi:
            self._double()
        idx = np.minimum((x * (self._nb / self._hi)).astype(np.int64),
                         self._nb - 1)
        self._counts += np.bincount(idx, minlength=self._nb)
        self._n += x.size

    @property
    def count(self) -> int:
        return self._n

    @property
    def resolution(self) -> float:
        """Current bin width — the quantile error bound."""
        return self._hi / self._nb

    def quantile(self, q: float) -> float:
        if not 0.0 <= q <= 1.0:
            raise ValueError("q must be in [0, 1]")
        if self._n == 0:
            return 0.0
        rank = max(int(math.ceil(q * self._n)), 1)
        cum = np.cumsum(self._counts)
        k = int(np.searchsorted(cum, rank, side="left"))
        return (k + 1) * self._hi / self._nb


@dataclass(frozen=True)
class ReplayStats:
    """Streamed per-configuration replay statistics.

    ``mean_wait_s`` / ``mean_latency_s`` / ``slo_compliance`` /
    ``max_latency_s`` / ``throughput_qps`` are exact over the full trace;
    ``p95_latency_s`` is the histogram estimate, exact to within
    ``p95_resolution_s`` (the sketch bin width)."""

    num_requests: int
    duration_s: float
    throughput_qps: float
    mean_wait_s: float
    mean_latency_s: float
    p95_latency_s: float
    p95_resolution_s: float
    slo_compliance: float
    max_latency_s: float
    slo_s: Optional[float]
    engine: str


def _resolve_replay_engine(backend: str, num_servers: int) -> str:
    """Pick the chunk engine: ``closed_form`` (vectorized numpy prefix
    scan, c = 1 only), ``jax`` (carried comparator scan, any c up to the
    fastsim bound), or ``loop`` (numpy per-request fallback for c > 1
    without jax)."""
    if backend not in ("auto", "numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "jax":
        if not jax_available():
            raise RuntimeError(
                f"backend='jax' requested but jax is not importable "
                f"({jax_unavailable_reason()})")
        if num_servers > _fs._JAX_MAX_SERVERS:
            raise ValueError(
                f"jax replay supports num_servers <= {_fs._JAX_MAX_SERVERS}")
        return "jax"
    if num_servers == 1:
        return "closed_form"
    if backend == "auto" and jax_available() \
            and num_servers <= _fs._JAX_MAX_SERVERS:
        return "jax"
    return "loop"


def _chunk_closed_form(A: np.ndarray, S: np.ndarray,
                       comp0: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                   np.ndarray]:
    """c = 1 Lindley chunk via the prefix-scan closed form.

    ``C_i = P_i + max(comp0, max_{j<=i}(A_j - P_{j-1}))`` with
    ``P = cumsum(S)`` — two cumulative ops instead of a per-request loop.
    Waits are clamped at zero: the closed form reassociates the additions,
    so an idle slot can come out at -1e-16 where the sequential recursion
    gives exactly 0 (agreement is allclose at ~1e-13, not bit-for-bit)."""
    P = np.cumsum(S, axis=0)
    M = np.maximum.accumulate(A[:, None] - (P - S), axis=0)
    C = P + np.maximum(M, comp0[None, :])
    waits = np.maximum(C - S - A[:, None], 0.0)
    lats = C - A[:, None]
    return waits, lats, C[-1].copy()


def _chunk_loop_kw(A: np.ndarray, S: np.ndarray,
                   F: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """c > 1 numpy fallback: the Kiefer-Wolfowitz step per request, with
    the (K, c) workload matrix carried in place."""
    n, K = S.shape
    waits = np.empty((n, K), dtype=float)
    lats = np.empty((n, K), dtype=float)
    for i in range(n):
        a = A[i]
        st = np.maximum(a, F[:, 0])
        ct = st + S[i]
        F[:, 0] = ct
        F.sort(axis=1)
        waits[i] = st - a
        lats[i] = ct - a
    return waits, lats


def _make_chunk_jax():
    """Build the jitted carried-state chunk scanner (shape-specialized on
    the padded chunk length and on c via ``F0.shape[0]``)."""
    _jax, _jnp = _fs._jax, _fs._jnp

    @_jax.jit
    def scan_chunk(A, S, F0):
        c = F0.shape[0]

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
            return _jnp.stack(out), (st - a, ct - a)

        F, (waits, lats) = _jax.lax.scan(step, F0, (A, S))
        return waits, lats, F

    return scan_chunk


def _make_chunk_dag_jax(num_stages: int):
    """Build the jitted fused tandem-chunk evaluator: one device program
    statically unrolled over the J stages, each replaying the c = 1
    closed form (``P = cumsum(S)``, ``M = cummax(A - (P - S))``,
    ``C = P + max(M, comp0)``) with stage j+1 consuming stage j's
    completions in-register.  Allclose (~1e-13) vs the numpy chunk, not
    bit-exact: the log-depth prefix scan reassociates the prefix
    additions — the same caveat :func:`_chunk_closed_form` already carries
    vs the sequential recursion.  Returns per-stage waits, sojourns,
    departures and the carried backlog tails.

    The prefixes are explicit ``associative_scan``s, which is what
    ``cumsum`` / ``cummax`` lower to everywhere but the TPU.  There they
    lower to a ``reduce_window`` whose float64 compile takes minutes even
    at 4096 slots (3 minutes at 4096, 6 at 16384, for a described v5e),
    against seconds for the scan at 262144."""
    _jax, _jnp = _fs._jax, _fs._jnp
    scan = _jax.lax.associative_scan

    @_jax.jit
    def chunk(A, S, comp0):           # (n,), (J, n), (J,)
        cur = A
        waits, lats, tails = [], [], []
        for j in range(num_stages):   # static unroll over stages
            s = S[j]
            P = scan(_jnp.add, s)
            M = scan(_jnp.maximum, cur - (P - s))
            C = P + _jnp.maximum(M, comp0[j])
            waits.append(_jnp.maximum(C - s - cur, 0.0))
            lats.append(C - cur)
            tails.append(C[-1])
            cur = C
        return (_jnp.stack(waits), _jnp.stack(lats), cur,
                _jnp.stack(tails))

    return chunk


def replay_mix(trace, service_mean_s: Sequence[float],
               service_p95_s: Optional[Sequence[float]] = None, *,
               num_servers: int = 1, slo_s: Optional[float] = None,
               seed: int = 0, backend: str = "auto",
               quantile_bins: int = 8192) -> List[ReplayStats]:
    """Replay one chunked trace against every configuration of a mix
    ladder simultaneously, streaming the statistics.

    All K lanes see the *same* arrival chunks (common random numbers on
    the arrival process, the ``arrival_traces`` semantics of
    :func:`repro.serving.fastsim.simulate_batch`); each lane draws its own
    service stream keyed ``(seed, lane-config, trace-fingerprint)``.
    Memory is O(chunk x K) regardless of trace length.  ``backend``
    follows the fastsim convention ("auto" resolves per
    :func:`_resolve_replay_engine`; the chosen engine is reported in
    ``ReplayStats.engine``).
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
        cfg_fps = [_fingerprint(b"ln" + np.float64(m).tobytes()
                                + np.float64(p).tobytes())
                   for m, p in zip(means, p95s)]
    else:
        ln_params = None
        cfg_fps = [_fingerprint(b"exp" + np.float64(m).tobytes())
                   for m in means]
    if num_servers < 1:
        raise ValueError("num_servers must be >= 1")
    c = int(num_servers)
    engine = _resolve_replay_engine(backend, c)

    base_seed = seed & 0x7FFFFFFF
    gens = [np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [base_seed, 2, cfg_fps[k], trace.fingerprint]))) for k in range(K)]

    count = 0
    wait_sum = np.zeros(K)
    lat_sum = np.zeros(K)
    ok = np.zeros(K, dtype=np.int64)
    max_lat = np.zeros(K)
    init_hi = max(4.0 * float(means.max()), float(slo_s or 0.0) * 2.0, 1e-6)
    sketches = [StreamingQuantile(quantile_bins, init_hi) for _ in range(K)]

    if engine == "jax":
        scan_chunk = _make_chunk_jax()
        F = np.zeros((c, K), dtype=float)
    else:
        comp0 = np.zeros(K, dtype=float)
        F_loop = np.zeros((K, c), dtype=float)

    for A in trace.chunks():
        n = A.size
        S = np.empty((n, K), dtype=float)
        for k in range(K):
            if ln_params is not None:
                mu, sigma = ln_params[k]
                S[:, k] = gens[k].lognormal(mean=mu, sigma=sigma, size=n)
            else:
                S[:, k] = gens[k].exponential(scale=means[k], size=n)

        if engine == "closed_form":
            waits, lats, comp0 = _chunk_closed_form(A, S, comp0)
        elif engine == "loop":
            waits, lats = _chunk_loop_kw(A, S, F_loop)
        else:
            # pad to a power-of-two length (self-masking zero slots: they
            # dispatch instantly with zero service, leaving the carried
            # workload untouched) so jit specializes on few shapes
            pad = max(4096, 1 << (n - 1).bit_length()) - n
            Ap = np.pad(A, (0, pad))
            Sp = np.pad(S, ((0, pad), (0, 0)))
            with _fs._jax.enable_x64(True):
                w, l, Fj = scan_chunk(_fs._jnp.asarray(Ap),
                                      _fs._jnp.asarray(Sp),
                                      _fs._jnp.asarray(F))
                waits = np.asarray(w)[:n]
                lats = np.asarray(l)[:n]
                F = np.asarray(Fj)

        count += n
        wait_sum += waits.sum(axis=0)
        lat_sum += lats.sum(axis=0)
        if slo_s is not None:
            ok += (lats <= slo_s).sum(axis=0)
        np.maximum(max_lat, lats.max(axis=0), out=max_lat)
        for k in range(K):
            sketches[k].update(lats[:, k])

    duration = float(trace.duration_s)
    n_eff = max(count, 1)
    out = []
    for k in range(K):
        out.append(ReplayStats(
            num_requests=count,
            duration_s=duration,
            throughput_qps=count / duration,
            mean_wait_s=float(wait_sum[k]) / n_eff,
            mean_latency_s=float(lat_sum[k]) / n_eff,
            p95_latency_s=sketches[k].quantile(0.95),
            p95_resolution_s=sketches[k].resolution,
            slo_compliance=(float(ok[k]) / n_eff if slo_s is not None
                            and count > 0 else 1.0),
            max_latency_s=float(max_lat[k]),
            slo_s=slo_s,
            engine=engine,
        ))
    return out


def replay_trace(trace, service_mean_s: float,
                 service_p95_s: Optional[float] = None, *,
                 num_servers: int = 1, slo_s: Optional[float] = None,
                 seed: int = 0, backend: str = "auto",
                 quantile_bins: int = 8192) -> ReplayStats:
    """Single-configuration convenience wrapper over :func:`replay_mix`."""
    return replay_mix(
        trace, [float(service_mean_s)],
        None if service_p95_s is None else [float(service_p95_s)],
        num_servers=num_servers, slo_s=slo_s, seed=seed, backend=backend,
        quantile_bins=quantile_bins)[0]


@dataclass(frozen=True)
class DagReplayStats:
    """Streamed tandem-pipeline replay: per-stage statistics (wait and
    sojourn measured at each stage's own arrival process) plus the
    end-to-end view (latency = sink completion - external arrival; wait =
    sum of per-stage queueing waits).  SLO compliance is end-to-end."""

    stages: Tuple[ReplayStats, ...]
    end_to_end: ReplayStats


def replay_dag(trace, stage_mean_s: Sequence[float],
               stage_p95_s: Optional[Sequence[float]] = None, *,
               slo_s: Optional[float] = None, seed: int = 0,
               backend: str = "auto",
               quantile_bins: int = 8192) -> DagReplayStats:
    """Stream one chunked trace through a *tandem* of single-server stages
    via chained closed-form Lindley recursions — stage n's departures are
    stage n+1's arrivals, chunk by chunk.

    Each stage carries its own backlog scalar across chunk boundaries;
    because a c = 1 FIFO stage's completions are non-decreasing, a chunk's
    departure vector is already a sorted arrival chunk for the next stage,
    so the chaining is exact over the whole trace (identical to replaying
    it unchunked).  One (mean, p95) pair per stage — the pinned pipeline
    rung — with service streams keyed ``(seed, stage, stage-config,
    trace-fingerprint)`` in the :func:`replay_mix` style.  Multi-server or
    fork-join pipelines need :func:`repro.serving.dag.sweep_pipeline` or
    the event-heap :class:`repro.serving.dag.DagSimulator`.

    ``backend`` follows the fastsim convention.  ``"auto"`` and
    ``"numpy"`` run the per-stage numpy closed form (engine
    ``"chained_closed_form"`` — the byte-stable reference, and the
    consistent ``"auto"`` resolution: this is the all-c = 1 case, where
    the flat replay resolves to ``closed_form`` too).  ``"jax"`` fuses
    all J stage recursions into one jitted device program per chunk
    (engine ``"chained_closed_form_jax"``), carrying the per-stage
    backlog vector across chunk boundaries on the host — allclose
    (~1e-13) agreement, identical content-keyed service draws.
    """
    if backend not in ("auto", "numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "jax" and not jax_available():
        raise RuntimeError(
            f"backend='jax' requested but jax is not importable "
            f"({jax_unavailable_reason()})")
    means = np.asarray(stage_mean_s, dtype=float)
    if means.ndim != 1 or means.size == 0:
        raise ValueError("stage_mean_s must be a non-empty 1-D sequence")
    if np.any(means <= 0):
        raise ValueError("stage service means must be positive")
    J = means.size
    if stage_p95_s is not None:
        p95s = np.asarray(stage_p95_s, dtype=float)
        if p95s.shape != means.shape:
            raise ValueError("stage_p95_s must match stage_mean_s")
        ln_params = [lognormal_params(m, p) for m, p in zip(means, p95s)]
        cfg_fps = [_fingerprint(b"ln" + np.float64(m).tobytes()
                                + np.float64(p).tobytes())
                   for m, p in zip(means, p95s)]
    else:
        ln_params = None
        cfg_fps = [_fingerprint(b"exp" + np.float64(m).tobytes())
                   for m in means]

    base_seed = seed & 0x7FFFFFFF
    gens = [np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [base_seed, 3, j, cfg_fps[j], trace.fingerprint])))
        for j in range(J)]

    count = 0
    wait_sum = np.zeros(J)
    lat_sum = np.zeros(J)
    e2e_lat_sum = 0.0
    e2e_ok = 0
    max_lat = np.zeros(J)
    e2e_max = 0.0
    stage_init = [max(4.0 * float(m), 1e-6) for m in means]
    e2e_init = max(4.0 * float(means.sum()), float(slo_s or 0.0) * 2.0, 1e-6)
    sketches = [StreamingQuantile(quantile_bins, hi) for hi in stage_init]
    e2e_sketch = StreamingQuantile(quantile_bins, e2e_init)
    comp0 = np.zeros(J, dtype=float)

    use_jax = backend == "jax"
    if use_jax:
        chunk_jax = _make_chunk_dag_jax(J)

    for A in trace.chunks():
        n = A.size
        if use_jax and n:
            S = np.empty((J, n), dtype=float)
            for j in range(J):
                if ln_params is not None:
                    mu, sigma = ln_params[j]
                    S[j] = gens[j].lognormal(mean=mu, sigma=sigma, size=n)
                else:
                    S[j] = gens[j].exponential(scale=means[j], size=n)
            # pad to a power-of-two length so jit specializes on few
            # shapes; zero-arrival / zero-service pad slots replicate
            # each stage's last completion, leaving the carried backlog
            # tails untouched
            pad = max(4096, 1 << (n - 1).bit_length()) - n
            Ap = np.pad(A, (0, pad))
            Sp = np.pad(S, ((0, 0), (0, pad)))
            with _fs._jax.enable_x64(True):
                wj, lj, dep, tails = chunk_jax(
                    _fs._jnp.asarray(Ap), _fs._jnp.asarray(Sp),
                    _fs._jnp.asarray(comp0))
                waits_g = np.asarray(wj)[:, :n]
                lats_g = np.asarray(lj)[:, :n]
                cur = np.asarray(dep)[:n]
                comp0 = np.asarray(tails)
            for j in range(J):
                wait_sum[j] += waits_g[j].sum()
                lat_sum[j] += lats_g[j].sum()
                max_lat[j] = max(max_lat[j], float(lats_g[j].max()))
                sketches[j].update(lats_g[j])
        else:
            cur = A
            for j in range(J):
                if ln_params is not None:
                    mu, sigma = ln_params[j]
                    S1 = gens[j].lognormal(mean=mu, sigma=sigma, size=n)
                else:
                    S1 = gens[j].exponential(scale=means[j], size=n)
                waits, lats, tail = _chunk_closed_form(cur, S1[:, None],
                                                       comp0[j:j + 1])
                comp0[j] = tail[0]
                w = waits[:, 0]
                l = lats[:, 0]
                wait_sum[j] += w.sum()
                lat_sum[j] += l.sum()
                if n:
                    max_lat[j] = max(max_lat[j], float(l.max()))
                sketches[j].update(l)
                cur = cur + l   # departures: arrivals + stage sojourns
        e2e = cur - A
        count += n
        e2e_lat_sum += e2e.sum()
        if slo_s is not None:
            e2e_ok += int((e2e <= slo_s).sum())
        if n:
            e2e_max = max(e2e_max, float(e2e.max()))
        e2e_sketch.update(e2e)

    duration = float(trace.duration_s)
    n_eff = max(count, 1)
    engine = "chained_closed_form_jax" if use_jax else "chained_closed_form"

    def stats(wsum: float, lsum: float, sketch: StreamingQuantile,
              mx: float, ok: Optional[int]) -> ReplayStats:
        return ReplayStats(
            num_requests=count,
            duration_s=duration,
            throughput_qps=count / duration,
            mean_wait_s=wsum / n_eff,
            mean_latency_s=lsum / n_eff,
            p95_latency_s=sketch.quantile(0.95),
            p95_resolution_s=sketch.resolution,
            slo_compliance=(ok / n_eff if ok is not None and count > 0
                            else 1.0),
            max_latency_s=mx,
            slo_s=slo_s,
            engine=engine,
        )

    stages = tuple(
        stats(float(wait_sum[j]), float(lat_sum[j]), sketches[j],
              float(max_lat[j]), None)
        for j in range(J))
    e2e_stats = stats(float(wait_sum.sum()), float(e2e_lat_sum), e2e_sketch,
                      float(e2e_max),
                      e2e_ok if slo_s is not None else None)
    return DagReplayStats(stages=stages, end_to_end=e2e_stats)
