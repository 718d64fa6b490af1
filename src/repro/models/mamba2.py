"""Mamba2 layer (SSD chunkwise-parallel scan), as in Zamba2.

The SSD form splits the sequence into chunks of ``ssm_chunk``: within a chunk
the recurrence is evaluated as masked matmuls (MXU-friendly); across chunks a
small state ``h[B, H, P, N]`` is carried by a scan of length L/chunk. Decode
is the exact single-step recurrence (O(1) per token).

Shapes: d_inner = expand * d_model; P = headdim (64); H = d_inner / P;
N = ssm_state; G = ssm_groups B/C groups, head h reading group h // (H/G).
The layer follows ``transformers``' ``Zamba2MambaMixer``: conv with bias
over concat(x, B, C), time step floored at ``ssm_dt_min``, ``D`` skip, and
a gated RMSNorm that gates first and normalises each group on its own.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L

F32 = jnp.float32
Params = Any

HEADDIM = 64


def dims(cfg: ModelConfig):
    d_inner = cfg.expand * cfg.d_model
    p = min(HEADDIM, d_inner)
    h = d_inner // p
    n = cfg.ssm_state
    return d_inner, h, p, n


def conv_dim(cfg: ModelConfig) -> int:
    d_inner, _, _, n = dims(cfg)
    return d_inner + 2 * cfg.ssm_groups * n


def mamba2_params(cfg: ModelConfig, rng, dtype) -> Params:
    """Projections are kept as separate weights (z / x / B / C / dt) rather
    than one fused in_proj: each output dim then shards independently on the
    `model` axis with no unaligned splits of sharded dims in the HLO.

    ``dt_bias`` is the inverse softplus of a time step drawn log-uniform in
    [0.001, 0.1] and ``a_log`` is log(1..H), as the published model's
    initialisation; the conv bias is drawn uniform in +-1/sqrt(kernel)."""
    d = cfg.d_model
    d_inner, h, p, n = dims(cfg)
    gn = cfg.ssm_groups * n
    c = conv_dim(cfg)
    r = L.split_rngs(rng, 9)
    bound = cfg.conv_kernel ** -0.5
    dt = jnp.exp(jax.random.uniform(r[7], (h,), F32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "ln": L.rmsnorm_params(d, dtype),
        "in_z": L._dense_init(r[0], (d, d_inner), dtype),
        "in_x": L._dense_init(r[1], (d, d_inner), dtype),
        "in_b": L._dense_init(r[2], (d, gn), dtype),
        "in_c": L._dense_init(r[3], (d, gn), dtype),
        "in_dt": L._dense_init(r[4], (d, h), dtype),
        "conv_w": L._dense_init(r[5], (cfg.conv_kernel, c), dtype, 2.0),
        "conv_b": jax.random.uniform(r[6], (c,), F32, -bound,
                                     bound).astype(dtype),
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
        "d_skip": jnp.ones((h,), F32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "out_norm": L.rmsnorm_params(d_inner, dtype),
        "out_proj": L._dense_init(r[8], (d_inner, d), dtype),
    }


def _project(prm: Params, xn):
    """xn -> (z, xbc, dt_raw); xbc = concat(x, B, C) for the shared conv."""
    z = jnp.einsum("bsd,de->bse", xn, prm["in_z"])
    xs = jnp.einsum("bsd,de->bse", xn, prm["in_x"])
    bm = jnp.einsum("bsd,dn->bsn", xn, prm["in_b"])
    cm = jnp.einsum("bsd,dn->bsn", xn, prm["in_c"])
    dt_raw = jnp.einsum("bsd,dh->bsh", xn, prm["in_dt"])
    return z, jnp.concatenate([xs, bm, cm], axis=-1), dt_raw


def _causal_conv(xbc, w, b, conv_state=None):
    """Depthwise causal conv along seq. xbc: [B,S,C]; w: [K,C].

    conv_state: [B, K-1, C] trailing inputs from the previous segment.
    Returns (y, new_conv_state).
    """
    k = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)
    y = sum(xp[:, i:i + xbc.shape[1], :] * w[i] for i in range(k)) + b
    new_state = xp[:, xp.shape[1] - (k - 1):, :]
    return jax.nn.silu(y.astype(F32)).astype(xbc.dtype), new_state


def _time_step(cfg: ModelConfig, prm: Params, dt_raw):
    dt = jax.nn.softplus(dt_raw.astype(F32) + prm["dt_bias"])
    return jnp.maximum(dt, cfg.ssm_dt_min) if cfg.ssm_dt_min else dt


def _split_groups(cfg: ModelConfig, xbc):
    """conv output -> (x [..., H, P], B [..., G, N], C [..., G, N])."""
    d_inner, nh, p, n = dims(cfg)
    g = cfg.ssm_groups
    xs, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    lead = xbc.shape[:-1]
    return (xs.reshape(lead + (nh, p)), bm.reshape(lead + (g, n)),
            cm.reshape(lead + (g, n)))


def _gated_out(cfg: ModelConfig, prm: Params, y, z, x):
    """Gate, RMSNorm per group, project: y [B,S,d_inner] f32 -> [B,S,d]."""
    g = y * jax.nn.silu(z.astype(F32))
    lead = g.shape[:-1]
    g = g.reshape(lead + (cfg.ssm_groups, -1))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    g = g.reshape(lead + (-1,)) * prm["out_norm"]["scale"].astype(F32)
    return jnp.einsum("bse,ed->bsd", g.astype(x.dtype), prm["out_proj"])


def mamba2_apply(cfg: ModelConfig, prm: Params, x, *, extra=None,
                 state=None, return_state: bool = False):
    """One layer, x: [B,S,d] -> x + mixer(norm(x + extra)).

    ``extra`` (Zamba2's shared-block output) enters the norm and not the
    residual.  state = {"h": [B,H,P,N], "conv": [B,K-1,conv_dim]}."""
    b, s, d = x.shape
    d_inner, nh, p, n = dims(cfg)
    g = cfg.ssm_groups
    hg = nh // g
    chunk = min(cfg.ssm_chunk, s)

    xn = L.rmsnorm(prm["ln"], x if extra is None else x + extra, cfg.norm_eps)
    z, xbc, dt_raw = _project(prm, xn)
    conv_in = state["conv"] if state is not None else None
    xbc, conv_state = _causal_conv(xbc, prm["conv_w"], prm["conv_b"], conv_in)
    xs, bmat, cmat = _split_groups(cfg, xbc)
    xs = xs.reshape(b, s, g, hg, p)
    dt = _time_step(cfg, prm, dt_raw).reshape(b, s, g, hg)      # [B,S,G,Hg]
    a = -jnp.exp(prm["a_log"]).reshape(g, hg)
    da = dt * a                                                  # log decay

    # pad to a chunk multiple with zero-contribution steps: dt=0 => decay 1
    # and no state update, so padded steps are exact no-ops on the carry
    pad = (-s) % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        xs, bmat, cmat, dt, da = map(padded, (xs, bmat, cmat, dt, da))
    s_pad = s + pad
    n_chunks = s_pad // chunk

    h0 = (state["h"].astype(F32).reshape(b, g, hg, p, n) if state is not None
          else jnp.zeros((b, g, hg, p, n), F32))

    def to_chunks(t):
        return t.reshape((b, n_chunks, chunk) + t.shape[2:]).swapaxes(0, 1)

    xs_c, b_c, c_c, dt_c, da_c = map(to_chunks, (xs, bmat, cmat, dt, da))
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(h, inp):
        xc, bc, cc, dtc, dac = inp
        ca = jnp.cumsum(dac, axis=1)                            # [B,T,G,Hg]
        # intra-chunk: M[t,s] = (C_t . B_s) exp(ca_t - ca_s) dt_s,  s <= t
        cb = jnp.einsum("btgn,bsgn->btsg", cc.astype(F32), bc.astype(F32))
        ldiff = ca[:, :, None] - ca[:, None, :]                 # [B,T,S,G,Hg]
        # masked before the exp: above the diagonal ldiff is a growth
        m = jnp.exp(jnp.where(tri[None, :, :, None, None], ldiff,
                              -jnp.inf)) * dtc[:, None]
        m = m * cb[..., None]
        # bf16 score tile for the contraction (f32 accumulate): the
        # [T,S,H] tiles dominate the chunk-scan HBM traffic
        y_intra = jnp.einsum("btsgh,bsghp->btghp", m.astype(jnp.bfloat16),
                             xc.astype(jnp.bfloat16),
                             preferred_element_type=F32)
        # inter-chunk: y += C_t . (exp(ca_t) h)
        y_inter = jnp.einsum("btgn,bghpn,btgh->btghp", cc.astype(F32), h,
                             jnp.exp(ca))
        # carry: h' = exp(ca_T) h + sum_s exp(ca_T - ca_s) dt_s B_s x_s^T
        ca_t = ca[:, -1]                                        # [B,G,Hg]
        w_s = jnp.exp(ca_t[:, None] - ca) * dtc                 # [B,T,G,Hg]
        h_new = jnp.exp(ca_t)[..., None, None] * h + jnp.einsum(
            "bsghp,bsgn,bsgh->bghpn", xc.astype(F32), bc.astype(F32), w_s)
        return h_new, y_intra + y_inter

    h_f, ys = lax.scan(body, h0, (xs_c, b_c, c_c, dt_c, da_c))
    y = ys.swapaxes(0, 1).reshape(b, s_pad, nh, p)[:, :s]
    y = y + xs[:, :s].reshape(b, s, nh, p).astype(F32) \
        * prm["d_skip"][None, None, :, None]
    out = x + _gated_out(cfg, prm, y.reshape(b, s, d_inner), z, x)
    if return_state:
        return out, {"h": h_f.reshape(b, nh, p, n),
                     "conv": conv_state.astype(x.dtype)}
    return out


def mamba2_decode(cfg: ModelConfig, prm: Params, x, state, *, extra=None):
    """One-token recurrence. x: [B,1,d]; ``extra`` as in ``mamba2_apply``."""
    b, _, d = x.shape
    d_inner, nh, p, n = dims(cfg)
    hg = nh // cfg.ssm_groups
    xn = L.rmsnorm(prm["ln"], x if extra is None else x + extra, cfg.norm_eps)
    z, xbc, dt_raw = _project(prm, xn)
    xbc, conv_state = _causal_conv(xbc, prm["conv_w"], prm["conv_b"],
                                   state["conv"])
    xs, bmat, cmat = _split_groups(cfg, xbc[:, 0])
    xt = xs.astype(F32)                                          # [B,H,P]
    bt = jnp.repeat(bmat.astype(F32), hg, axis=1)                # [B,H,N]
    ct = jnp.repeat(cmat.astype(F32), hg, axis=1)
    dt = _time_step(cfg, prm, dt_raw[:, 0])                      # [B,H]
    a = -jnp.exp(prm["a_log"])
    dec = jnp.exp(dt * a)                                        # [B,H]
    h = state["h"].astype(F32) * dec[:, :, None, None] + jnp.einsum(
        "bhp,bhn,bh->bhpn", xt, bt, dt)
    y = jnp.einsum("bhn,bhpn->bhp", ct, h)
    y = y + xt * prm["d_skip"][None, :, None]
    out = x + _gated_out(cfg, prm, y.reshape(b, 1, d_inner), z, x)
    return out, {"h": h, "conv": conv_state.astype(x.dtype)}


def empty_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    d_inner, nh, p, n = dims(cfg)
    return {"h": jnp.zeros((batch, nh, p, n), F32),
            "conv": jnp.zeros((batch, cfg.conv_kernel - 1, conv_dim(cfg)),
                              dtype)}
