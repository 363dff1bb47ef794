"""The plain reference's pieces, and its lower-precision control.

Straightforward ``jax.numpy`` at float32 under the highest matmul
precision, importing nothing of the program.  A family module
(``families/<family>.py``) builds its forward from these, one jitted
layer at a time on blocks of rows, so that it fits beside nothing else on
the chip.

Where the configuration states int8 keys and values for a rung, a query
that the rung answers from its cache (a decode position, ``>= kv_int8_from``)
sees every key and value rounded to int8 with one absmax scale per
(position, KV head); prompt positions attend at full precision, as a
prefill does before it writes the cache.

``operand`` selects the control: ``"fp8"`` rounds every matmul operand to
float8 e4m3 with a per-tensor absmax scale, the step below the bf16 that
the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0


def _round_operand(x, operand):
    if operand is None:
        return x
    if operand == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown operand precision {operand!r}")


def mm(spec, x, y, operand):
    return jnp.einsum(spec, _round_operand(x, operand),
                      _round_operand(y, operand),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (R, S, heads, hd); position = index along S."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def int8_round(x):
    """Round (R, S, KV, hd) to int8 with one absmax scale per (R, S, KV)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-8)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def attention(q, k, v, *, window, kv_int8_from, operand):
    """q: (R, S, H, hd); k, v: (R, S, KV, hd) -> (R, S, H, hd)."""
    r, s, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(r, s, kvh, h // kvh, hd)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = kpos <= qpos
    if window > 0:
        mask = mask & (kpos > qpos - window)

    def attend(kk, vv):
        sc = mm("rqkgd,rskd->rkgqs", qg, kk, operand) / np.sqrt(hd)
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("rkgqs,rskd->rqkgd", p, vv, operand)

    out = attend(k, v)
    if kv_int8_from >= 0:
        cached = attend(int8_round(k), int8_round(v))
        from_cache = (jnp.arange(s) >= kv_int8_from)[None, :, None, None, None]
        out = jnp.where(from_cache, cached, out)
    return out.reshape(r, s, h, hd)
