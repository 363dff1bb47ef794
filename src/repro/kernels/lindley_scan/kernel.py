"""Blocked Lindley-recursion scan as a Pallas TPU kernel.

Recurrence per scenario lane: ``C_i = max(A_i, C_{i-1}) + S_i`` — the c = 1
waiting-time recursion of an M/G/1 FIFO queue, which is the inner loop of
the fast-path simulation sweep (`repro.serving.fastsim`).  Structurally
this is the ssm_scan kernel's problem with (+, max) in place of (*, +): a
first-order linear recurrence in the max-plus semiring, carried across
time chunks in VMEM.

Hardware shape: the scenario axis B sits in lanes (last dim, blocks of
128), time chunks iterate the grid's last dimension *sequentially*, and
the per-lane completion-time carry lives in a VMEM scratch register that
never round-trips to HBM between chunks.  HBM sees one read of A and S
and one write of C — the roofline floor.  VMEM per step with tc = 256,
bs = 128: 3 x (256, 128) fp32 ~ 384 KB.

The time loop inside a chunk is a ``fori_loop`` over VMEM rows; each step
reads one (1, bs) row of A and S from the refs, does one max and one add,
and stores one row of C — sequential in time, parallel across the 128
scenario lanes of the block.  The kernel lowers for float32 only: Mosaic
has no float64, and :mod:`repro.serving.fastsim` refuses
``scan_impl="pallas"`` on float64 grids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lindley_kernel(a_ref, s_ref, c_ref, carry_ref, *, time_chunk: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    # Rows are read from and written to the refs at the loop index: Mosaic
    # lowers a dynamic row of a ref, not a dynamic slice of a loaded value.
    def step(t, comp):
        row = pl.ds(t, 1)
        comp = jnp.maximum(a_ref[row, :], comp) + s_ref[row, :]   # (1, bs)
        c_ref[row, :] = comp
        return comp

    carry_ref[...] = jax.lax.fori_loop(0, time_chunk, step, carry_ref[...])


def _chained_lindley_kernel(a_ref, s_ref, c_ref, carry_ref, *,
                            time_chunk: int, num_stages: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def step(t, comp):                 # J-tuple of (1, bs) carries
        row = pl.ds(t, 1)
        arr = a_ref[row, :]            # (1, bs)
        new_comp = []
        for j in range(num_stages):    # static unroll: J stays in-register
            cj = jnp.maximum(arr, comp[j]) + s_ref[j, row, :]
            c_ref[j, row, :] = cj
            new_comp.append(cj)
            arr = cj                   # stage j+1 consumes stage j departures
        return tuple(new_comp)

    comp0 = tuple(carry_ref[pl.ds(j, 1), :] for j in range(num_stages))
    comp = jax.lax.fori_loop(0, time_chunk, step, comp0)
    for j in range(num_stages):
        carry_ref[pl.ds(j, 1), :] = comp[j]


def lindley_scan(
    arrivals: jax.Array,   # (N, B): FIFO-ordered arrival times
    services: jax.Array,   # (N, B): matching service times
    *,
    block_b: int = 128,
    time_chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Completion times C: (N, B), with C_i = max(A_i, C_{i-1}) + S_i.

    Scenarios (columns) are independent; rows are the sequential FIFO
    order.  ``B`` must divide into ``block_b`` lanes and ``N`` into
    ``time_chunk`` rows (the fastsim caller pads with zero-arrival /
    zero-service slots, which are self-masking: they dispatch instantly
    with zero service and leave the carry unchanged).
    """
    n, b = arrivals.shape
    if services.shape != (n, b):
        raise ValueError(f"shape mismatch: {arrivals.shape} vs {services.shape}")
    block_b = min(block_b, b)
    time_chunk = min(time_chunk, n)
    if b % block_b or n % time_chunk:
        raise ValueError(
            f"dims ({n},{b}) must divide blocks ({time_chunk},{block_b})")
    nb, nt = b // block_b, n // time_chunk

    kernel = functools.partial(_lindley_kernel, time_chunk=time_chunk)
    return pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((time_chunk, block_b), lambda ib, it: (it, ib)),
            pl.BlockSpec((time_chunk, block_b), lambda ib, it: (it, ib)),
        ],
        out_specs=pl.BlockSpec((time_chunk, block_b), lambda ib, it: (it, ib)),
        out_shape=jax.ShapeDtypeStruct((n, b), arrivals.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_b), arrivals.dtype)],
        interpret=interpret,
    )(arrivals, services)


def chained_lindley_scan(
    arrivals: jax.Array,   # (N, B): FIFO-ordered external arrival times
    services: jax.Array,   # (J, N, B): per-stage service times
    *,
    block_b: int = 128,
    time_chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Per-stage completion times C: (J, N, B) for a c = 1 tandem chain.

    The blocked multi-stage variant of :func:`lindley_scan`: each time
    row runs all J stage recursions back-to-back in-register (stage j+1's
    arrival is stage j's freshly computed completion), so the whole
    tandem chain is one kernel launch with a (J, block_b) VMEM carry —
    no host round-trip between stages.  Same padding contract as the
    flat kernel: zero-arrival / zero-service pad slots leave every
    stage's carry unchanged (stage carries are non-decreasing down the
    chain, so the cascaded ``max`` collapses onto each stage's own
    backlog).
    """
    if services.ndim != 3:
        raise ValueError(f"services must be (J, N, B), got {services.shape}")
    j, n, b = services.shape
    if arrivals.shape != (n, b):
        raise ValueError(
            f"shape mismatch: {arrivals.shape} vs {services.shape}")
    block_b = min(block_b, b)
    time_chunk = min(time_chunk, n)
    if b % block_b or n % time_chunk:
        raise ValueError(
            f"dims ({n},{b}) must divide blocks ({time_chunk},{block_b})")
    nb, nt = b // block_b, n // time_chunk

    kernel = functools.partial(
        _chained_lindley_kernel, time_chunk=time_chunk, num_stages=j)
    return pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((time_chunk, block_b), lambda ib, it: (it, ib)),
            pl.BlockSpec((j, time_chunk, block_b),
                         lambda ib, it: (0, it, ib)),
        ],
        out_specs=pl.BlockSpec((j, time_chunk, block_b),
                               lambda ib, it: (0, it, ib)),
        out_shape=jax.ShapeDtypeStruct((j, n, b), arrivals.dtype),
        scratch_shapes=[pltpu.VMEM((j, block_b), arrivals.dtype)],
        interpret=interpret,
    )(arrivals, services)
