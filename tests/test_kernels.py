"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Each kernel sweeps shapes and dtypes and asserts allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops as da_ops, ref as da_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
from repro.kernels.ssm_scan import ops as ss_ops, ref as ss_ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# -- flash attention -----------------------------------------------------------


@pytest.mark.parametrize("seq,block", [(128, 128), (256, 128), (512, 256)])
@pytest.mark.parametrize("kv", [2, 1])  # GQA coverage
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(seq, block, kv, dtype, causal):
    key = jax.random.PRNGKey(seq + kv)
    ks = jax.random.split(key, 3)
    B, H, D = 2, 2, 64
    q = jax.random.normal(ks[0], (B, H, seq, D), dtype)
    k = jax.random.normal(ks[1], (B, kv, seq, D), dtype)
    v = jax.random.normal(ks[2], (B, kv, seq, D), dtype)
    out = fa_ops.flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    ref = fa_ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    B, H, S, D = 1, 2, 256, 64
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = fa_ops.flash_attention(
        q, k, v, causal=True, window=window, block_q=128, block_k=128, interpret=True
    )
    ref = fa_ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-5, atol=2e-5
    )


# -- decode attention ------------------------------------------------------------


@pytest.mark.parametrize("cache,block_k", [(1024, 256), (2048, 512), (384, 128)])
@pytest.mark.parametrize("kv", [4, 1])  # MHA-group and MQA
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(cache, block_k, kv, dtype):
    key = jax.random.PRNGKey(cache + kv)
    ks = jax.random.split(key, 4)
    B, H, D = 2, 4, 64
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, cache, kv, D), dtype)
    v = jax.random.normal(ks[2], (B, cache, kv, D), dtype)
    length = jnp.asarray(cache * 3 // 4, jnp.int32)
    out = da_ops.decode_attention(q, k, v, length, block_k=block_k, interpret=True)
    ref = da_ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_decode_attention_respects_length_mask():
    """Positions beyond `length` must not affect the output."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    B, H, S, D = 1, 2, 512, 64
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    length = jnp.asarray(100, jnp.int32)
    out1 = da_ops.decode_attention(q, k, v, length, interpret=True)
    k2 = k.at[:, 100:].set(999.0)
    v2 = v.at[:, 100:].set(-999.0)
    out2 = da_ops.decode_attention(q, k2, v2, length, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# -- rmsnorm ---------------------------------------------------------------------


@pytest.mark.parametrize("rows,dim", [(4, 256), (16, 512), (3, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(rows, dim, dtype):
    key = jax.random.PRNGKey(rows * dim)
    x = jax.random.normal(key, (rows, dim), dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), (dim,), dtype)
    out = rn_ops.rmsnorm(x, g, interpret=True)
    ref = rn_ref.rmsnorm_ref(x, g)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


# -- ssm scan --------------------------------------------------------------------


@pytest.mark.parametrize("seq,d", [(128, 128), (256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssm_scan_matches_ref(seq, d, dtype):
    key = jax.random.PRNGKey(seq + d)
    ks = jax.random.split(key, 3)
    N = 8
    # decay in (0,1) for stability; shapes follow ops signature
    decay = jax.nn.sigmoid(jax.random.normal(ks[0], (1, seq, d, N), dtype))
    drive = 0.1 * jax.random.normal(ks[1], (1, seq, d, N), dtype)
    c = jax.random.normal(ks[2], (1, seq, N), dtype)
    out = ss_ops.ssm_scan(decay, drive, c, block_d=64, time_chunk=64, interpret=True)
    ref = ss_ref.ssm_scan_ref(decay, drive, c)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-4, atol=2e-4
    )


def test_ssm_scan_is_sequential_not_parallaxed():
    """State must propagate: zeroing early drive changes late outputs."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    S, D, N = 128, 64, 8
    decay = jnp.full((1, S, D, N), 0.95)
    drive = 0.1 * jax.random.normal(ks[1], (1, S, D, N))
    c = jax.random.normal(ks[2], (1, S, N))
    out1 = ss_ops.ssm_scan(decay, drive, c, block_d=64, time_chunk=64, interpret=True)
    drive2 = drive.at[:, :4].set(0.0)
    out2 = ss_ops.ssm_scan(decay, drive2, c, block_d=64, time_chunk=64, interpret=True)
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


# -- chunked cross-entropy -------------------------------------------------------

from repro.kernels.cross_entropy import ops as ce_ops, ref as ce_ref


@pytest.mark.parametrize("t,v,bt,bv", [
    (256, 2048, 128, 512),
    (512, 4096, 256, 1024),
    (128, 1024, 128, 1024),   # single vocab block
    (128, 2048, 128, 256),    # many small blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cross_entropy_matches_ref(t, v, bt, bv, dtype):
    key = jax.random.PRNGKey(t + v)
    ks = jax.random.split(key, 2)
    logits = (jax.random.normal(ks[0], (t, v)) * 4).astype(dtype)
    labels = jax.random.randint(ks[1], (t,), 0, v)
    out = ce_ops.cross_entropy(logits, labels, block_t=bt, block_v=bv, interpret=True)
    ref = ce_ref.cross_entropy_ref(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol(dtype))


def test_cross_entropy_gold_on_block_boundaries():
    """Labels exactly at vocab-block edges must pick the right gold logit."""
    t, v, bv = 128, 1024, 256
    key = jax.random.PRNGKey(9)
    logits = jax.random.normal(key, (t, v))
    edges = jnp.array([0, bv - 1, bv, 2 * bv - 1, 2 * bv, v - 1], jnp.int32)
    labels = jnp.tile(edges, t // len(edges) + 1)[:t]
    out = ce_ops.cross_entropy(logits, labels, block_t=128, block_v=bv, interpret=True)
    ref = ce_ref.cross_entropy_ref(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_cross_entropy_rejects_nondivisible():
    logits = jnp.zeros((100, 1000))
    labels = jnp.zeros((100,), jnp.int32)
    with pytest.raises(ValueError):
        ce_ops.cross_entropy(logits, labels, block_t=64, block_v=512, interpret=True)


# -- Lindley scan ------------------------------------------------------------------

from repro.kernels.lindley_scan import ops as ls_ops, ref as ls_ref


def _lindley_inputs(n, b, j=None, seed=0):
    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0.0, n * 0.1, (n, b)), axis=0)
    shape = (n, b) if j is None else (j, n, b)
    services = rng.exponential(0.1, shape)
    return (jnp.asarray(arrivals, jnp.float32),
            jnp.asarray(services, jnp.float32))


@pytest.mark.parametrize("n,b,tc,bb", [
    (256, 128, 256, 128),    # one block
    (512, 256, 128, 128),    # carry across time chunks and lane blocks
])
def test_lindley_scan_matches_ref(n, b, tc, bb):
    a, s = _lindley_inputs(n, b)
    out = ls_ops.lindley_scan(a, s, block_b=bb, time_chunk=tc, interpret=True)
    ref = ls_ref.lindley_scan_ref(a, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("j", [1, 3])
def test_chained_lindley_scan_matches_ref(j):
    a, s = _lindley_inputs(512, 256, j=j, seed=j)
    out = ls_ops.chained_lindley_scan(a, s, block_b=128, time_chunk=128,
                                      interpret=True)
    ref = ls_ref.chained_lindley_scan_ref(a, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
