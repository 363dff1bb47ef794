"""jax backend of the workflow-DAG fast path: parity property tests.

Three independent implementations of the same tandem/fork-join queueing
recursion are held against each other over *random* topologies:

1. **numpy** (:func:`repro.serving.fastsim.chained_lindley`,
   :func:`repro.serving.dag.sweep_pipeline` ``backend="numpy"``) — the
   authoritative committed reference.
2. **jax** — the batched device engine under test.  With the sequential
   scan (the CPU ``"auto"`` resolution) it replays numpy's exact op order
   per (request, stage), so grids are bit-equal; the associative and
   Pallas reorderings are held to float64 allclose.
3. **an event-heap oracle** written here, from scratch, against the
   queueing definition only (per-stage FIFO, ``c`` servers, explicit
   service times) — so a shared bug in the two production engines cannot
   self-certify.

Draws are continuous (lognormal / uniform), so exact arrival ties — where
the jax engine's dispatch pairing may legitimately differ from numpy's
stable-by-request-index convention — occur with probability zero.

The jax-less contract rides along: with ``fastsim._jax`` monkeypatched
away, ``backend="auto"`` silently falls back to the numpy engine
everywhere while explicit ``backend="jax"`` raises ``RuntimeError`` with
the recorded import reason — in :func:`chained_lindley`,
:func:`sweep_pipeline`, and :func:`repro.serving.traces.replay_dag`.
"""

import heapq
import random

import numpy as np
import pytest

from proptest import given, settings, st

from repro.serving import fastsim
from repro.serving.dag import (
    DagSimulator,
    StageSpec,
    WorkflowDAG,
    sweep_pipeline,
)
from repro.serving.fastsim import (
    chained_lindley,
    jax_available,
    jax_unavailable_reason,
)
from repro.serving.traces import diurnal_trace, replay_dag
from repro.serving.workload import constant_rate, generate_arrivals

pytestmark = pytest.mark.jax

needs_jax = pytest.mark.skipif(
    not jax_available(),
    reason=f"jax not importable: {jax_unavailable_reason()}")


# --------------------------------------------------------------------------
# the from-scratch event-heap oracle
# --------------------------------------------------------------------------


def _heap_stage(arrivals, services, c):
    """One FIFO stage with ``c`` servers: dispatch in arrival order
    (stable by request index on ties), each request takes the
    earliest-free server.  ``services`` is consumed in dispatch order.
    Returns completions aligned to the *original* request order."""
    order = np.argsort(arrivals, kind="stable")
    free = [0.0] * c
    heapq.heapify(free)
    comp = np.empty(len(arrivals))
    for s, i in zip(services, order):
        t = heapq.heappop(free)
        done = max(arrivals[i], t) + s
        comp[i] = done
        heapq.heappush(free, done)
    return comp


def _heap_tandem(A, stage_services, servers):
    """Chain of heap stages: stage j's completions arrive at stage j+1."""
    cur = np.asarray(A, dtype=float)
    out = []
    for S, c in zip(stage_services, servers):
        cur = _heap_stage(cur, S, c)
        out.append(cur)
    return np.stack(out)


def _draw_chain(seed, *, n_stages, max_c, rate=40.0, n=None):
    """Continuous random arrivals + per-stage dispatch-order services."""
    gen = np.random.Generator(np.random.PCG64(seed))
    n = int(gen.poisson(rate)) + 5 if n is None else n
    A = np.sort(gen.uniform(0.0, 10.0, size=n))
    servers = [int(gen.integers(1, max_c + 1)) for _ in range(n_stages)]
    services = [gen.lognormal(mean=np.log(0.05), sigma=0.6, size=n)
                for _ in range(n_stages)]
    return A, services, servers


# --------------------------------------------------------------------------
# chained_lindley: numpy == jax == oracle over random chains
# --------------------------------------------------------------------------


@needs_jax
@given(st.integers(1, 5), st.integers(1, 3), st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_chained_jax_sequential_bit_exact_vs_numpy(n_stages, max_c, seed):
    """Random chains mixing c = 1 and pooled stages: the jax sequential
    engine reproduces the numpy reference bit-for-bit, and both agree
    with the independent event-heap oracle to float64 allclose (the c = 1
    closed form reassociates the oracle's additions)."""
    A, services, servers = _draw_chain(seed, n_stages=n_stages, max_c=max_c)
    ref = chained_lindley(A, services, num_servers=servers,
                          backend="numpy")
    got = chained_lindley(A, services, num_servers=servers,
                          backend="jax", scan_impl="sequential")
    np.testing.assert_array_equal(ref, got)
    oracle = _heap_tandem(A, services, servers)
    np.testing.assert_allclose(ref, oracle, rtol=1e-9, atol=1e-12)


@needs_jax
@given(st.integers(1, 4), st.integers(0, 10**6))
@settings(max_examples=12, deadline=None)
def test_chained_jax_reassociated_impls_allclose(n_stages, seed):
    """The associative max-plus scan is a float reordering of the same
    recursion: allclose against numpy and the oracle on random all-c = 1
    chains, never judged bit-exact.  (The float32 Pallas kernel is
    refused on these float64 chains; tests/test_kernels.py holds it to
    its oracle at float32.)"""
    A, services, _ = _draw_chain(seed, n_stages=n_stages, max_c=1)
    servers = [1] * n_stages
    ref = chained_lindley(A, services, num_servers=servers,
                          backend="numpy")
    got = chained_lindley(A, services, num_servers=servers,
                          backend="jax", scan_impl="associative")
    np.testing.assert_allclose(ref, got, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(got, _heap_tandem(A, services, servers),
                               rtol=1e-9, atol=1e-12)


# --------------------------------------------------------------------------
# sweep_pipeline: jax grid == numpy grid over random DAGs, and the
# chained fast path tracks the DagSimulator oracle's sink completions
# --------------------------------------------------------------------------


def _random_stage(rng, name, *, max_c=3):
    m = rng.uniform(0.02, 0.12)
    return StageSpec(name=name, mean_s=(m,),
                     p95_s=(m * rng.uniform(1.3, 2.0),),
                     num_servers=rng.randint(1, max_c))


def _random_dag(kind, width, topo_seed):
    rng = random.Random(topo_seed)
    if kind == 0:
        return WorkflowDAG.tandem(
            [_random_stage(rng, f"s{j}") for j in range(width + 1)])
    branches = [_random_stage(rng, f"b{j}") for j in range(max(2, width))]
    join = _random_stage(rng, "join")
    tail = [_random_stage(rng, "tail")] if rng.random() < 0.5 else []
    return WorkflowDAG.fork_join(branches, join, tail=tail)


@needs_jax
@given(st.integers(0, 1), st.integers(1, 3), st.integers(0, 10**6),
       st.floats(3.0, 8.0))
@settings(max_examples=10, deadline=None)
def test_sweep_pipeline_jax_grid_bit_equal(kind, width, topo_seed, rate):
    """Random tandem / fork-join topologies through the full sweep: the
    jax (R, K, L) grid engine — host permutations, fused c = 1 runs,
    comparator-chain pooled stages, element-wise max joins — reproduces
    the numpy per-cell loop's latency / p95 / compliance grids exactly
    (sequential scan on CPU), with the identical content-keyed draws."""
    dag = _random_dag(kind, width, topo_seed)
    kw = dict(arrival_rates_qps=(rate, rate * 1.6), duration_s=15.0,
              replications=2, slo_s=0.8, seed=topo_seed % 1000)
    ref = sweep_pipeline(dag, [(0,) * dag.num_stages], backend="numpy",
                         **kw)
    got = sweep_pipeline(dag, [(0,) * dag.num_stages], backend="jax",
                         scan_impl="sequential", **kw)
    assert ref.num_requests == got.num_requests
    for field in ("mean_latency_s", "p95_latency_s", "slo_compliance"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, field)),
            np.asarray(getattr(got, field)), err_msg=field)


@needs_jax
@given(st.integers(1, 3), st.integers(0, 10**6), st.floats(3.0, 7.0))
@settings(max_examples=8, deadline=None)
def test_chained_jax_matches_event_heap_simulator(width, topo_seed, rate):
    """The jax chained recursion against :class:`DagSimulator` itself on
    random tandems: replaying the oracle's own per-stage dispatch-order
    service draws through ``chained_lindley(backend="jax")`` reproduces
    the oracle's sink completion multiset (allclose — the closed form
    reassociates the heap's additions)."""
    from repro.serving.dag import _stage_seed

    dag = _random_dag(0, width, topo_seed)
    arr = generate_arrivals(constant_rate(rate), 20.0,
                            seed=topo_seed % 997)
    cfg = (0,) * dag.num_stages
    sim_seed = topo_seed % 89
    oracle = DagSimulator(dag, static_stage_indices=cfg,
                          seed=sim_seed).run(arr, 20.0)
    assert len(oracle.completed) == len(arr)

    # the oracle consumes stage j's services from
    # random.Random(_stage_seed(seed, j)) in dispatch order — pre-drawing
    # the same streams yields its exact dispatch-order service arrays
    topo = dag.topological_order()
    services = []
    for j in topo:
        rng_j = random.Random(_stage_seed(sim_seed, j))
        sampler = dag.stages[j].sampler()
        services.append(np.array([sampler(0, rng_j)
                                  for _ in range(len(arr))]))
    servers = [dag.stages[j].num_servers for j in topo]
    comp = chained_lindley(arr, services, num_servers=servers,
                           backend="jax", scan_impl="sequential")
    np.testing.assert_allclose(
        np.sort(comp[-1]),
        np.sort([r.completion_s for r in oracle.completed]),
        rtol=1e-9, atol=1e-12)


# --------------------------------------------------------------------------
# jax-less contract: silent auto fallback, loud explicit failure
# --------------------------------------------------------------------------


def test_dag_paths_without_jax(monkeypatch):
    """Every DAG-path entry point honors the backend contract when jax is
    gone: auto falls back to the numpy engine with identical results,
    explicit 'jax' raises RuntimeError naming the import failure."""
    A, services, servers = _draw_chain(3, n_stages=2, max_c=2)
    dag = _random_dag(0, 1, 5)
    trace = diurnal_trace(20.0, duration_s=30.0, seed=1)
    kw = dict(arrival_rates_qps=(4.0,), duration_s=10.0, replications=1,
              seed=0)
    want_chain = chained_lindley(A, services, num_servers=servers,
                                 backend="numpy")
    want_sweep = sweep_pipeline(dag, [(0, 0)], backend="numpy", **kw)

    monkeypatch.setattr(fastsim, "_jax", None)
    monkeypatch.setattr(fastsim, "_JAX_IMPORT_ERROR",
                        "No module named 'jax'")
    assert not fastsim.jax_available()

    got_chain = chained_lindley(A, services, num_servers=servers,
                                backend="auto")
    np.testing.assert_array_equal(want_chain, got_chain)
    got_sweep = sweep_pipeline(dag, [(0, 0)], backend="auto", **kw)
    np.testing.assert_array_equal(np.asarray(want_sweep.mean_latency_s),
                                  np.asarray(got_sweep.mean_latency_s))
    stats = replay_dag(trace, [0.01, 0.02], [0.015, 0.03], slo_s=1.0,
                       seed=0, backend="auto")
    assert stats.end_to_end.engine == "chained_closed_form"

    for call in (
        lambda: chained_lindley(A, services, num_servers=servers,
                                backend="jax"),
        lambda: sweep_pipeline(dag, [(0, 0)], backend="jax", **kw),
        lambda: replay_dag(trace, [0.01, 0.02], [0.015, 0.03],
                           slo_s=1.0, seed=0, backend="jax"),
    ):
        with pytest.raises(RuntimeError, match="not importable"):
            call()


def test_unknown_backend_rejected_everywhere():
    A, services, servers = _draw_chain(4, n_stages=2, max_c=1)
    with pytest.raises(ValueError, match="unknown backend"):
        chained_lindley(A, services, num_servers=servers, backend="tpu")
    trace = diurnal_trace(10.0, duration_s=20.0, seed=2)
    with pytest.raises(ValueError, match="unknown backend"):
        replay_dag(trace, [0.01], [0.02], slo_s=1.0, seed=0,
                   backend="tpu")
