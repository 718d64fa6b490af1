"""Mamba2 SSD chunk-scan Pallas TPU kernel.

Fuses one whole SSD chunk step per grid iteration: intra-chunk masked
matmuls (MXU) + inter-chunk state contribution + the state-carry update.
The SSM state h[P, N] lives in VMEM scratch and persists across the minor
(sequential) chunk grid dimension — the cross-chunk recurrence never
round-trips HBM, which is the TPU-native replacement for the GPU kernel's
shared-memory chunk state.

Grid: (batch, heads, n_chunks). B/C projections are shared across heads
(n_groups=1) and re-read per head; the C@B^T tile is recomputed in-kernel
per head because an MXU recompute (T x N x T MACs) is cheaper than an HBM
round-trip of the [T, T] tile per head (arithmetic-intensity argument, see
EXPERIMENTS.md roofline notes).

Inputs per block: x[T, P], b[T, N], c[T, N], dt[1, T], da[1, T] (log decay).
Outputs: y[T, P] and the final state h[P, N] (written on the last chunk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, y_ref, hout_ref, h_ref, *,
            chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(F32)             # [T, P]
    b = b_ref[0].astype(F32)                # [T, N]
    c = c_ref[0].astype(F32)                # [T, N]
    dt = dt_ref[0, 0].astype(F32)           # [1, T]
    da = da_ref[0, 0].astype(F32)           # [1, T]

    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols

    def nt(a, m):                           # a @ m.T, exact in f32
        return lax.dot_general(a, m, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=F32)

    # cumulative log decay as a lower-triangular matmul (Mosaic has no
    # cumsum), in both orientations so no in-kernel transpose is needed
    lower = tri.astype(F32)
    ca_col = nt(lower, da)                  # [T, 1]
    ca_row = nt(da, lower)                  # [1, T]
    dt_col = nt((rows == cols).astype(F32), dt)             # [T, 1]
    # intra-chunk: scores[t,s] = (C_t . B_s) exp(ca_t - ca_s) dt_s, s <= t
    cb = lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                         preferred_element_type=F32)        # [T, T]
    ldiff = ca_col - ca_row
    w = jnp.where(tri, jnp.exp(ldiff) * dt, 0.0)
    scores = cb * w
    y_intra = lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                              preferred_element_type=F32)   # [T, P]
    # inter-chunk: y += exp(ca_t) * (C_t . h)
    h = h_ref[0]                                            # [P, N]
    y_inter = lax.dot_general(c, h, (((1,), (1,)), ((), ())),
                              preferred_element_type=F32)   # [T, P]
    y_inter = y_inter * jnp.exp(ca_col)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # carry: h' = exp(ca_T) h + sum_s exp(ca_T - ca_s) dt_s x_s b_s^T
    ca_t = jnp.sum(da, axis=1, keepdims=True)               # [1, 1]
    w_s = jnp.exp(ca_t - ca_col) * dt_col                   # [T, 1]
    xw = x * w_s                                            # [T, P]
    h_new = jnp.exp(ca_t) * h + lax.dot_general(
        xw, b, (((0,), (0,)), ((), ())), preferred_element_type=F32)
    h_ref[0] = h_new

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_chunk_scan(x, b, c, dt, da, *, chunk: int = 128,
                     interpret: bool = False):
    """x: [B,S,H,P]; b, c: [B,S,N]; dt, da: [B,S,H] -> (y[B,S,H,P], h[B,H,P,N]).

    da = dt * A (log decay, negative). Sequence length must divide by chunk.

    The kernel sees head-major copies (x as [B,H,S,P], dt/da as [B,H,1,S])
    so that every block ends in (chunk, P), (chunk, N) or (1, chunk): the
    last two block dims then tile on the TPU (multiples of (8, 128) or the
    whole array dim), which the sequence-major [B,S,H,P] layout cannot.
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0
    nc = s // chunk

    xh = x.transpose(0, 2, 1, 3)                       # [B, H, S, P]
    dth = dt.transpose(0, 2, 1)[:, :, None, :]         # [B, H, 1, S]
    dah = da.transpose(0, 2, 1)[:, :, None, :]

    kernel = functools.partial(_kernel, chunk=chunk)
    yh, h_out = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), F32),
        ],
        scratch_shapes=[pltpu.VMEM((1, p, n), F32)],
        interpret=interpret,
    )(xh, b, c, dth, dah)
    return yh.transpose(0, 2, 1, 3), h_out
