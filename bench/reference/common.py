"""Arithmetic shared by the plain references: precision, init, norms.

Nothing here imports the program.  ``Numerics("f32")`` is the reference:
float32 everywhere, every matrix product at ``Precision.HIGHEST``.
``Numerics("fp8")`` is the control, the step below the bfloat16 that the
configurations state: both operands of every matrix product are rounded to
float8 e4m3 with a per-tensor scale (absolute max over 448), products
accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8_round(x):
    """x rounded to float8 e4m3 under a per-tensor scale, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


class Numerics:
    def __init__(self, kind: str = "f32"):
        if kind not in ("f32", "fp8"):
            raise ValueError(f"unknown numerics {kind!r}")
        self.kind = kind

    def mm(self, eq: str, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self.kind == "fp8":
            a, b = fp8_round(a), fp8_round(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=F32)


def dense_init(key, shape, dtype, scale: float = 1.0):
    """N(0, 1/fan_in) with fan_in = shape[0], drawn in f32, stored in dtype:
    the initialisation the served weights are drawn with."""
    std = scale / max(shape[0], 1) ** 0.5
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def rmsnorm(scale, x, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, positions, theta):
    """Rotary embedding on the two halves of the head dim. x: [B,S,H,D]."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = positions[..., None].astype(F32) * freqs
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def attention(num: Numerics, q, k, v, causal: bool):
    """Softmax attention over whole sequences. q,k,v: [B,S,H,D]."""
    s = num.mm("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask, s, -jnp.inf)
    return num.mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def served_token_gaps(logits, served):
    """Gap by which each served token's logit lies below the best logit.
    logits: [N, V] f32; served: [N] ids."""
    pick = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(logits, -1) - pick
