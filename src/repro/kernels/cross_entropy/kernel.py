"""Pallas TPU kernel: vocab-chunked cross-entropy (online logsumexp).

The §Perf pair-B hot spot: CE over a 256k vocab materializes (T, V) fp32
intermediates if computed naively.  This kernel streams the vocab dimension
through VMEM in blocks, maintaining the flash-attention-style online
(max, sum-exp) pair plus the gold logit picked up in whichever block holds
the label — the full (T, V) fp32 tensor never exists.

Grid: (T / block_t, V / block_v), vocab innermost so the running stats for a
token block live in VMEM scratch across the vocab sweep.  Block shapes are
MXU/VPU aligned (multiples of 128 on the vocab axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ce_kernel(labels_ref, logits_ref, out_ref, m_ref, s_ref, g_ref, *,
               block_v: int, num_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    chunk = logits_ref[...].astype(jnp.float32)          # (block_t, block_v)
    labels = labels_ref[...]                             # (block_t,)

    # online max / sum-exp update
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(chunk, axis=-1))
    s_ref[...] = s_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(chunk - m_new[:, None]), axis=-1
    )
    m_ref[...] = m_new

    # gold logit: the label falls in exactly one vocab block
    offset = vi * block_v
    local = labels - offset                              # (block_t,)
    in_block = (local >= 0) & (local < block_v)
    cols = jnp.arange(block_v)[None, :]
    hit = cols == jnp.clip(local, 0, block_v - 1)[:, None]
    gold_here = jnp.sum(jnp.where(hit, chunk, 0.0), axis=-1)
    g_ref[...] = g_ref[...] + jnp.where(in_block, gold_here, 0.0)

    @pl.when(vi == num_v - 1)
    def _finish():
        out_ref[...] = m_ref[...] + jnp.log(s_ref[...]) - g_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "interpret"))
def cross_entropy_pallas(
    logits: jax.Array,      # (T, V)
    labels: jax.Array,      # (T,) int32
    *,
    block_t: int = 256,
    block_v: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    t, v = logits.shape
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    if t % block_t or v % block_v:
        raise ValueError(f"({t},{v}) not divisible by blocks ({block_t},{block_v})")
    num_v = v // block_v
    grid = (t // block_t, num_v)

    return pl.pallas_call(
        functools.partial(_ce_kernel, block_v=block_v, num_v=num_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t,), lambda ti, vi: (ti,)),
            pl.BlockSpec((block_t, block_v), lambda ti, vi: (ti, vi)),
        ],
        out_specs=pl.BlockSpec((block_t,), lambda ti, vi: (ti,)),
        out_shape=jax.ShapeDtypeStruct((t,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t,), jnp.float32),   # running max
            pltpu.VMEM((block_t,), jnp.float32),   # running sum-exp
            pltpu.VMEM((block_t,), jnp.float32),   # gold logit
        ],
        interpret=interpret,
    )(labels, logits)
