"""jax backend of the batched fast-path sweep (repro/serving/fastsim.py).

The contract, in order of strictness:

1. **numpy is authoritative**: every jax result is judged against the
   committed numpy engine, never the other way around.
2. **c = 1 sequential scan is bit-exact**: same op order as the numpy
   reference loop, so the per-request latency grids — and therefore the
   p95 and compliance grids, which are order statistics — are *exactly*
   equal; only the mean reductions may differ at float-summation-order
   level (~1e-13).
3. **Associative / Pallas scans are reorderings**: the max-plus operator
   algebra reassociates the same float ops, so parity is tight allclose,
   not bit-exactness.
4. **c > 1 Kiefer-Wolfowitz**: the sorted-workload comparator network
   maintains the same multiset as numpy's set-column-and-sort, so parity
   is again tight allclose.
5. **Grid purity**: the jax sweep is a pure function of its cell inputs —
   permuting the config axis permutes the grids, slicing the load axis
   reproduces the same cells — and backend selection
   (:func:`~repro.serving.fastsim.resolve_backend`) is explicit,
   validated, and falls back to numpy without error when jax is missing.

Max-plus associativity (the property the associative scan and the Pallas
kernel both rely on) is tested directly on the operator.
"""

import numpy as np
import pytest

from repro.serving import fastsim
from repro.serving.fastsim import (
    jax_available,
    jax_unavailable_reason,
    resolve_backend,
    simulate_batch,
)

pytestmark = pytest.mark.jax

needs_jax = pytest.mark.skipif(
    not jax_available(),
    reason=f"jax not importable: {jax_unavailable_reason()}")

MEANS = [0.10, 0.25, 0.45]
P95S = [0.14, 0.35, 0.63]
GRIDS = ["mean_wait_s", "mean_latency_s", "p95_latency_s",
         "slo_compliance", "throughput_qps", "num_requests"]


def _sweep(*, backend, scan_impl="auto", seed=0, num_servers=1,
           rates=(2.0, 6.0), duration_s=60.0, replications=2,
           lognormal=True):
    return simulate_batch(
        MEANS, P95S if lognormal else None,
        arrival_rates_qps=list(rates), duration_s=duration_s,
        num_servers=num_servers, replications=replications,
        slo_s=1.0, seed=seed, backend=backend, scan_impl=scan_impl)


def _assert_parity(ref, got, *, exact_order_stats=False, rtol=1e-9):
    for name in GRIDS:
        a, b = getattr(ref, name), getattr(got, name)
        if exact_order_stats and name in ("p95_latency_s", "slo_compliance",
                                          "num_requests"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-12,
                                       err_msg=name)


# --------------------------------------------------------------------------
# max-plus operator algebra
# --------------------------------------------------------------------------


@needs_jax
def test_maxplus_combine_is_associative():
    """(f3 . f2) . f1 == f3 . (f2 . f1) for random affine max-plus maps
    x -> max(x + a, b): the property that makes the Lindley recursion an
    associative scan at all.  Mathematically exact; in floats the shift
    components compose as a1 + a2 + a3 in either grouping, so parity is
    last-ulp allclose, not bit equality."""
    import jax

    from repro.kernels.lindley_scan import maxplus_combine

    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        for _ in range(50):
            a1, a2, a3 = rng.normal(scale=3.0, size=(3, 8))
            b1, b2, b3 = rng.normal(scale=3.0, size=(3, 8))
            left = maxplus_combine(
                maxplus_combine((a1, b1), (a2, b2)), (a3, b3))
            right = maxplus_combine(
                (a1, b1), maxplus_combine((a2, b2), (a3, b3)))
            np.testing.assert_allclose(np.asarray(left[0]),
                                       np.asarray(right[0]), rtol=1e-14)
            np.testing.assert_allclose(np.asarray(left[1]),
                                       np.asarray(right[1]), rtol=1e-14)


@needs_jax
def test_maxplus_identity_element():
    """(0, -inf) is the identity: padding slots carry it, which is why the
    sweep can right-pad ragged traces without changing any cell.  Adding
    zero and maxing with -inf are exact, so this one IS bit equality."""
    import jax

    from repro.kernels.lindley_scan import maxplus_combine

    rng = np.random.default_rng(1)
    a, b = rng.normal(size=4), rng.normal(size=4)
    ident = (np.zeros(4), np.full(4, -np.inf))
    with jax.enable_x64(True):
        for out in (maxplus_combine(ident, (a, b)),
                    maxplus_combine((a, b), ident)):
            np.testing.assert_array_equal(np.asarray(out[0]), a)
            np.testing.assert_array_equal(np.asarray(out[1]), b)


# --------------------------------------------------------------------------
# parity with the numpy engine
# --------------------------------------------------------------------------


@needs_jax
@pytest.mark.parametrize("seed", [0, 3, 11])
@pytest.mark.parametrize("lognormal", [True, False])
def test_jax_c1_sequential_bit_exact_order_stats(seed, lognormal):
    """The sequential lax.scan replays the numpy loop's op order, so the
    per-request latency grid is bit-for-bit identical: p95, compliance and
    the request counts must be EXACTLY equal; the means may differ only by
    float summation order."""
    ref = _sweep(backend="numpy", seed=seed, lognormal=lognormal)
    got = _sweep(backend="jax", scan_impl="sequential", seed=seed,
                 lognormal=lognormal)
    _assert_parity(ref, got, exact_order_stats=True, rtol=1e-12)


@needs_jax
@pytest.mark.parametrize("scan_impl", ["associative", "pallas"])
def test_jax_c1_reassociated_scans_tight_parity(scan_impl):
    """Max-plus reassociation (associative_scan) computes the same
    recursion in a different grouping: tight allclose, including on the
    order statistics.  The blocked Pallas kernel is the other
    reassociation, but it lowers for float32 only: on these float64 grids
    it is refused with a ValueError that names the working impls, instead
    of failing inside the TPU lowering (tests/test_kernels.py holds the
    kernel to its oracle at float32)."""
    ref = _sweep(backend="numpy", seed=5)
    if scan_impl == "pallas":
        with pytest.raises(ValueError, match="float32 Lindley kernel"):
            _sweep(backend="jax", scan_impl=scan_impl, seed=5)
        return
    got = _sweep(backend="jax", scan_impl=scan_impl, seed=5)
    _assert_parity(ref, got, rtol=1e-9)


@needs_jax
@pytest.mark.parametrize("servers", [[1, 1], [1, 2]])
def test_pallas_scan_impl_refuses_float64(servers):
    """The chained engines refuse the float32 Pallas kernel on float64
    grids too: all-c = 1 chains (fused kernel) and pooled chains (per-stage
    kernel between Kiefer-Wolfowitz stages)."""
    rng = np.random.default_rng(0)
    A = np.sort(rng.uniform(0.0, 10.0, 64))
    services = [rng.exponential(0.1, 64) for _ in range(2)]
    with pytest.raises(ValueError, match="float32 Lindley kernel"):
        fastsim.chained_lindley(A, services, num_servers=servers,
                                backend="jax", scan_impl="pallas")


@needs_jax
@pytest.mark.parametrize("c", [2, 3])
@pytest.mark.parametrize("seed", [1, 7])
def test_jax_kw_multi_server_parity(c, seed):
    """c > 1: the comparator-network re-insertion maintains the same sorted
    workload vector as numpy's set-column-0-and-sort."""
    ref = _sweep(backend="numpy", seed=seed, num_servers=c,
                 rates=(6.0, 14.0))
    got = _sweep(backend="jax", seed=seed, num_servers=c,
                 rates=(6.0, 14.0))
    _assert_parity(ref, got, rtol=1e-9)


@needs_jax
def test_jax_explicit_traces_parity():
    """Common-random-number arrival traces (the Planner.validate shape)
    through both backends."""
    rng = np.random.default_rng(2)
    traces = [np.sort(rng.uniform(0.0, 60.0, size=n)) for n in (150, 90)]
    kw = dict(arrival_traces=[t.tolist() for t in traces],
              duration_s=60.0, replications=2, slo_s=1.0, seed=4)
    ref = simulate_batch(MEANS, P95S, backend="numpy", **kw)
    got = simulate_batch(MEANS, P95S, backend="jax",
                         scan_impl="sequential", **kw)
    _assert_parity(ref, got, exact_order_stats=True, rtol=1e-12)


# --------------------------------------------------------------------------
# sweep-grid purity
# --------------------------------------------------------------------------


@needs_jax
def test_jax_sweep_config_permutation_invariance():
    """Permuting the config axis permutes every grid identically: no
    cross-talk between cells inside the jitted sweep."""
    perm = [2, 0, 1]
    base = simulate_batch(MEANS, P95S, arrival_rates_qps=[3.0, 8.0],
                          duration_s=60.0, replications=2, slo_s=1.0,
                          seed=9, backend="jax")
    permuted = simulate_batch([MEANS[i] for i in perm],
                              [P95S[i] for i in perm],
                              arrival_rates_qps=[3.0, 8.0],
                              duration_s=60.0, replications=2, slo_s=1.0,
                              seed=9, backend="jax")
    for name in GRIDS:
        np.testing.assert_array_equal(getattr(base, name)[:, perm, :],
                                      getattr(permuted, name),
                                      err_msg=name)


@needs_jax
def test_jax_sweep_load_slicing_invariance():
    """A sub-batch over a subset of loads reproduces exactly the same
    cells as the full sweep: each (r, k, l) cell is a pure function of its
    own trace and service stream."""
    rates = [2.0, 5.0, 9.0]
    full = simulate_batch(MEANS, P95S, arrival_rates_qps=rates,
                          duration_s=60.0, replications=2, slo_s=1.0,
                          seed=6, backend="jax")
    sub = simulate_batch(MEANS, P95S, arrival_rates_qps=rates[1:],
                         duration_s=60.0, replications=2, slo_s=1.0,
                         seed=6, backend="jax")
    for name in GRIDS:
        np.testing.assert_array_equal(getattr(full, name)[:, :, 1:],
                                      getattr(sub, name), err_msg=name)


# --------------------------------------------------------------------------
# backend selection
# --------------------------------------------------------------------------


def test_resolve_backend_literals_and_validation():
    assert resolve_backend("numpy") == "numpy"
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")
    if jax_available():
        assert resolve_backend("jax", num_servers=1) == "jax"
        with pytest.raises(ValueError, match="num_servers"):
            resolve_backend("jax",
                            num_servers=fastsim._JAX_MAX_SERVERS + 1)


def test_resolve_backend_auto_thresholds():
    """auto: numpy for small grids (device dispatch would dominate) and
    for pools past the comparator-network bound; jax only for large,
    eligible sweeps — and only when jax imports at all."""
    small = fastsim._JAX_AUTO_MIN_SLOTS - 1
    big = fastsim._JAX_AUTO_MIN_SLOTS
    assert resolve_backend("auto", total_slots=small) == "numpy"
    assert (resolve_backend("auto", total_slots=big)
            == ("jax" if jax_available() else "numpy"))
    assert resolve_backend(
        "auto", num_servers=fastsim._JAX_MAX_SERVERS + 1,
        total_slots=big) == "numpy"
    # no size hint: resolution must still be deterministic, not an error
    assert resolve_backend("auto") in ("numpy", "jax")


def test_missing_jax_fallback_and_error(monkeypatch):
    """With jax absent, auto silently resolves numpy while explicit
    backend='jax' raises with the recorded import reason."""
    monkeypatch.setattr(fastsim, "_jax", None)
    monkeypatch.setattr(fastsim, "_JAX_IMPORT_ERROR", "No module named 'jax'")
    assert not fastsim.jax_available()
    assert "jax" in fastsim.jax_unavailable_reason()
    assert resolve_backend("auto", total_slots=10**9) == "numpy"
    with pytest.raises(RuntimeError, match="not importable"):
        resolve_backend("jax")
    # and the sweep entry point inherits the silent fallback
    res = _sweep(backend="auto", duration_s=20.0, replications=1,
                 rates=(2.0,))
    assert res.total_requests > 0


def test_bad_scan_impl_rejected():
    with pytest.raises(ValueError, match="scan_impl"):
        _sweep(backend="numpy", scan_impl="warp")


def test_resolve_backend_auto_on_dag_sized_grids():
    """The grid sizes the pipeline benchmarks actually produce: a
    smoke-scale DAG validation (few rungs x short grid) must stay on
    numpy under ``auto``, while the full trace-replay-scale validation
    crosses the amortization threshold and picks jax when importable.
    Pins the threshold semantics to the real workloads, not just to
    ``_JAX_AUTO_MIN_SLOTS +- 1``."""

    def grid_slots(*, rungs, rates, replications, duration_s):
        # padded slots = R x K x L x N_max, N_max ~ peak-rate trace + 10%
        return (replications * rungs * len(rates)
                * int(max(rates) * duration_s * 1.1))

    smoke = grid_slots(rungs=3, rates=(2.0, 3.0, 3.75), replications=2,
                       duration_s=90.0)
    assert smoke < fastsim._JAX_AUTO_MIN_SLOTS
    assert resolve_backend("auto", num_servers=1,
                           total_slots=smoke) == "numpy"

    full = grid_slots(rungs=5, rates=(5.5, 7.3, 9.1), replications=8,
                      duration_s=900.0)
    assert full >= fastsim._JAX_AUTO_MIN_SLOTS
    assert resolve_backend("auto", num_servers=1, total_slots=full) == (
        "jax" if jax_available() else "numpy")
    # a fork-join-wide pool disqualifies the grid regardless of size
    assert resolve_backend(
        "auto", num_servers=fastsim._JAX_MAX_SERVERS + 1,
        total_slots=full) == "numpy"


def test_resolve_backend_auto_counts_stage_recursions():
    """Pipeline-aware auto sizing: the amortization bar counts recursion
    steps (``total_slots x num_stages``), so a per-stage grid too small
    for the flat path qualifies once enough chained stages multiply the
    device work — and exactly at the boundary on both sides.
    """
    bar = fastsim._JAX_AUTO_MIN_SLOTS
    want = "jax" if jax_available() else "numpy"

    # flat default (num_stages=1): the bar applies to total_slots alone
    assert resolve_backend("auto", total_slots=bar - 1) == "numpy"
    assert resolve_backend("auto", total_slots=bar) == want
    assert resolve_backend("auto", total_slots=bar - 1,
                           num_stages=1) == "numpy"

    # a 3-stage pipeline clears the bar at a third of the flat slot count
    per_stage = -(-bar // 3)                     # ceil(bar / 3)
    assert per_stage * 3 >= bar
    assert per_stage < bar
    assert resolve_backend("auto", total_slots=per_stage,
                           num_stages=3) == want
    # ... but one slot under the boundary still resolves numpy
    under = (bar - 1) // 3
    assert under * 3 < bar
    assert resolve_backend("auto", total_slots=under,
                           num_stages=3) == "numpy"

    # degenerate stage counts clamp to the flat semantics, never divide
    assert resolve_backend("auto", total_slots=bar, num_stages=0) == want
    assert resolve_backend("auto", total_slots=bar - 1,
                           num_stages=0) == "numpy"


# --------------------------------------------------------------------------
# Planner.validate backend forwarding
# --------------------------------------------------------------------------


def _tiny_plan():
    from repro.core.planner import Planner

    def profiler(config, n):
        _, mean = config
        return [mean * (0.8 + 0.4 * i / (n - 1)) for i in range(n)]

    planner = Planner(profiler=profiler)
    plan = planner.plan({("fast", 0.10): 0.80, ("slow", 0.30): 0.90},
                        slo_p95_s=1.0)
    return planner, plan


@pytest.mark.parametrize("backend", ["numpy", "jax", "auto"])
def test_planner_validate_forwards_backend_verbatim(monkeypatch, backend):
    """``Planner.validate`` must hand its ``backend`` argument to
    :func:`simulate_batch` untouched — resolution (including the jax ->
    numpy fallback) belongs to the sweep engine, so the Planner forwards
    even ``"jax"`` on a host without jax rather than resolving early."""
    seen = {}

    class _StubSweep:
        total_requests = 1234

        def over_replications(self):
            k, l = len(seen["means"]), len(seen["rates"])
            grid = [[0.0] * l for _ in range(k)]
            return {"mean_wait_s": grid, "p95_latency_s": grid,
                    "slo_compliance": [[1.0] * l for _ in range(k)]}

    def stub(means, p95s, *, arrival_rates_qps, backend, **kw):
        seen.update(means=list(means), rates=list(arrival_rates_qps),
                    backend=backend, kw=kw)
        return _StubSweep()

    monkeypatch.setattr(fastsim, "simulate_batch", stub)
    planner, plan = _tiny_plan()
    val = planner.validate(plan, duration_s=30.0, replications=2,
                           backend=backend)
    assert seen["backend"] == backend
    assert len(seen["means"]) == plan.table.ladder_size
    # the stub's grids landed in the validation result unresolved
    assert val.num_requests == 1234
    assert val.slo_compliance == tuple(
        (1.0,) * len(seen["rates"]) for _ in seen["means"])


def test_planner_validate_default_backend_is_auto(monkeypatch):
    seen = {}

    class _StubSweep:
        total_requests = 1

        def over_replications(self):
            return {"mean_wait_s": [[0.0]], "p95_latency_s": [[0.0]],
                    "slo_compliance": [[1.0]]}

    def stub(means, p95s, *, backend, **kw):
        seen["backend"] = backend
        return _StubSweep()

    monkeypatch.setattr(fastsim, "simulate_batch", stub)
    planner, plan = _tiny_plan()
    planner.validate(plan, arrival_rates_qps=[2.0], duration_s=30.0,
                     replications=1)
    assert seen["backend"] == "auto"
