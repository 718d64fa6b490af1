"""Plain float32 reference of the served xLSTM (mLSTM + sLSTM blocks).

It implements the configuration's documented departures from arXiv:2405.04517
and nothing else of the program:
  * mLSTM with bounded gating: sigmoid input gate, log-sigmoid forget gate,
    no max-stabiliser; the normaliser is max(|q . n_t|, 1);
  * every ``slstm_every``-th block is an sLSTM (exponential input gate with
    the usual stabiliser), followed by a SwiGLU projection of width
    int(4d/3) rounded down to a multiple of 128; no post-up-projection of
    the mLSTM beyond the output gate z;
  * RMSNorm (scale only), no biases except the forget-gate bias (3.0 at
    init) and the sLSTM gate bias; untied output projection.

The mLSTM is computed in its fully parallel form over the whole sequence
(a decay-weighted attention matrix), the sLSTM as a time scan: no chunks,
no cache.  ``logits(params, tokens)`` is the teacher-forced forward pass
that prefill plus recurrent decode must agree with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, Numerics, dense_init, rmsnorm


def _dims(m):
    d, h = m["d_model"], m["n_heads"]
    k = m["slstm_every"]
    return d, h, d // h, m["n_layers"] // k, k - 1


def slstm_ffn_width(d: int) -> int:
    return int(d * 4 / 3) // 128 * 128 or d


def init_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """The served weights, drawn from ``seed`` (the same draws, in the same
    order, as the served model's initialisation)."""
    d, h, dh, groups, m_per_group = _dims(m)
    f = slstm_ffn_width(d)
    ones = jnp.ones((d,), dtype)

    def mlstm(key):
        k = list(jax.random.split(key, 7))
        return {"ln": {"scale": ones},
                "w_up": dense_init(k[0], (d, 2 * d), dtype),
                "wq": dense_init(k[1], (d, d), dtype),
                "wk": dense_init(k[2], (d, d), dtype),
                "wv": dense_init(k[3], (d, d), dtype),
                "wi": dense_init(k[4], (d, h), dtype),
                "wf": dense_init(k[5], (d, h), dtype),
                "bf": jnp.full((h,), 3.0, dtype),
                "w_down": dense_init(k[6], (d, d), dtype)}

    def slstm(key):
        k = list(jax.random.split(key, 4))
        u = list(jax.random.split(k[2], 3))
        return {"ln": {"scale": ones},
                "w_gates": dense_init(k[0], (d, 4 * d), dtype),
                "r_gates": jax.vmap(
                    lambda r: dense_init(r, (dh, 4 * dh), dtype))(
                        jax.random.split(k[1], h)),
                "b_gates": jnp.zeros((4 * d,), dtype),
                "up": {"wi": dense_init(u[0], (d, f), dtype),
                       "wg": dense_init(u[1], (d, f), dtype),
                       "wo": dense_init(u[2], (f, d), dtype)}}

    r_e, r_m, r_s = jax.random.split(jax.random.key(seed), 3)
    e = list(jax.random.split(r_e, 2))
    return {
        "embed": {"embed": dense_init(e[0], (m["vocab_size"], d), dtype),
                  "unembed": dense_init(e[1], (d, m["vocab_size"]), dtype)},
        "mlstm": jax.vmap(jax.vmap(mlstm))(
            jax.random.split(r_m, groups * m_per_group).reshape(
                groups, m_per_group)),
        "slstm": jax.vmap(slstm)(jax.random.split(r_s, groups)),
        "ln_f": {"scale": ones},
    }


def mlstm_block(num: Numerics, m, p, x):
    b, s, d = x.shape
    h = m["n_heads"]
    dh = d // h
    xn = rmsnorm(p["ln"]["scale"], x, m["norm_eps"])
    up = num.mm("bsd,de->bse", xn, p["w_up"])
    v_in, z = up[..., :d], up[..., d:]
    q = num.mm("bsd,de->bse", v_in, p["wq"]).reshape(b, s, h, dh)
    k = num.mm("bsd,de->bse", v_in, p["wk"]).reshape(b, s, h, dh) / dh ** 0.5
    v = num.mm("bsd,de->bse", v_in, p["wv"]).reshape(b, s, h, dh)
    i_gate = jax.nn.sigmoid(num.mm("bsd,dh->bsh", xn, p["wi"]))
    log_f = jax.nn.log_sigmoid(num.mm("bsd,dh->bsh", xn, p["wf"])
                               + p["bf"].astype(F32))
    ld = jnp.cumsum(log_f, axis=1)                                # [B,S,H]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]     # [t,s]
    # decay weight of key s seen from query t: exp(ld_t - ld_s) * i_s
    decay = jnp.where(causal[None, :, :, None],
                      jnp.exp(jnp.where(causal[None, :, :, None],
                                        ld[:, :, None, :] - ld[:, None, :, :],
                                        0.0)) * i_gate[:, None, :, :], 0.0)
    w = num.mm("bthd,bshd->btsh", q, k) * decay                   # [B,t,s,H]
    den = jnp.maximum(jnp.abs(jnp.sum(w, axis=2)), 1.0)           # [B,t,H]
    y = num.mm("btsh,bshd->bthd", w, v) / den[..., None]
    y = y.reshape(b, s, d) * jax.nn.silu(z)
    return x + num.mm("bsd,de->bse", y, p["w_down"])


def slstm_block(num: Numerics, m, p, x):
    b, s, d = x.shape
    h = m["n_heads"]
    dh = d // h
    xn = rmsnorm(p["ln"]["scale"], x, m["norm_eps"])
    gx = num.mm("bsd,de->bse", xn, p["w_gates"]) + p["b_gates"].astype(F32)
    r = p["r_gates"].astype(F32)

    def step(carry, g_t):
        h_prev, c, n, mx = carry
        g = g_t.reshape(b, h, 4 * dh) + num.mm("bhd,hde->bhe", h_prev, r)
        z, i, f, o = (g[..., j * dh:(j + 1) * dh] for j in range(4))
        log_f = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(log_f + mx, i)
        i_p = jnp.exp(i - m_new)
        f_p = jnp.exp(log_f + mx - m_new)
        c = f_p * c + i_p * jnp.tanh(z)
        n = jnp.maximum(f_p * n + i_p, 1e-6)
        h_new = jax.nn.sigmoid(o) * c / n
        return (h_new, c, n, m_new), h_new

    zeros = jnp.zeros((b, h, dh), F32)
    init = (zeros, zeros, zeros, jnp.full((b, h, dh), -30.0, F32))
    _, hs = jax.lax.scan(step, init, jnp.swapaxes(gx, 0, 1))
    y = jnp.swapaxes(hs, 0, 1).reshape(b, s, d)
    u = p["up"]
    g = num.mm("bsd,df->bsf", y, u["wi"]) * jax.nn.silu(
        num.mm("bsd,df->bsf", y, u["wg"]))
    return x + num.mm("bsf,fd->bsd", g, u["wo"])


def logits(params, m: dict, tokens, frames=None, num: Numerics = None):
    """Teacher-forced logits [B, S, V] over ``tokens``."""
    num = num or Numerics("f32")
    _, _, _, groups, m_per_group = _dims(m)
    x = params["embed"]["embed"].astype(F32)[tokens]
    for g in range(groups):
        for j in range(m_per_group):
            x = mlstm_block(num, m, jax.tree.map(lambda a: a[g, j],
                                                 params["mlstm"]), x)
        x = slstm_block(num, m, jax.tree.map(lambda a: a[g],
                                             params["slstm"]), x)
    x = rmsnorm(params["ln_f"]["scale"], x, m["norm_eps"])
    return num.mm("bsd,dv->bsv", x, params["embed"]["unembed"])


def decode_bytes(m: dict, batch: int, prompt_len: int, n_gen: int) -> float:
    """HBM bytes one decode call must move: every bf16 weight except the
    embedding table (one row per sequence is read), and the f32 recurrent
    state read and written once (mLSTM C and n, sLSTM h, c, n, m), plus the
    bf16 logits it writes."""
    d, h, dh, groups, m_per_group = _dims(m)
    f, v = slstm_ffn_width(d), m["vocab_size"]
    mlstm_w = 2 * d * d + 4 * d * d + 2 * d * h + h + d
    slstm_w = 4 * d * d + h * dh * 4 * dh + 4 * d + 3 * d * f + d
    weights = (groups * (m_per_group * mlstm_w + slstm_w) + d * v + d
               + batch * d)
    mlstm_state = groups * m_per_group * batch * h * (dh * dh + dh)
    slstm_state = groups * batch * h * dh * 4
    return float(2 * weights + 4 * 2 * (mlstm_state + slstm_state)
                 + 2 * batch * v)
