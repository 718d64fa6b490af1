"""xLSTM backbone: mLSTM (parallel chunkwise matrix memory) + sLSTM blocks.

Layout: ``n_layers`` blocks, every ``slstm_every``-th block is an sLSTM; the
rest are mLSTM. Blocks are grouped for scanning: one group = (slstm_every-1)
mLSTM blocks + 1 sLSTM block, so the lowered HLO holds one mLSTM body and one
sLSTM body regardless of depth.

mLSTM here uses *bounded* gating (sigmoid input gate, logsigmoid cumulative
decay) so the chunkwise-parallel form needs no cross-chunk max-stabilizer;
this is a documented simplification of the paper's exponential gating (see
DESIGN.md) that keeps the same memory/compute structure: per-chunk matmuls
(MXU-friendly) + an O(L/chunk) state recurrence.

State per mLSTM block: C[B,H,dk,dv], n[B,H,dk]. Per sLSTM block:
(c, n, h)[B,H,dh] (+ stabilizer m). Serving uses these recurrent states —
no KV cache, O(1) per decoded token: this is why xlstm-350m runs long_500k.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import DEFAULT_NEW_TOKENS, ModelConfig
from repro.models import layers as L

F32 = jnp.float32
Params = Any


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_params(cfg: ModelConfig, rng, dtype) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    r = L.split_rngs(rng, 7)
    return {
        "ln": L.rmsnorm_params(d, dtype),
        "w_up": L._dense_init(r[0], (d, 2 * d), dtype),
        "wq": L._dense_init(r[1], (d, d), dtype),
        "wk": L._dense_init(r[2], (d, d), dtype),
        "wv": L._dense_init(r[3], (d, d), dtype),
        "wi": L._dense_init(r[4], (d, h), dtype),
        "wf": L._dense_init(r[5], (d, h), dtype),
        "bf": jnp.full((h,), 3.0, dtype),     # open forget gates at init
        "w_down": L._dense_init(r[6], (d, d), dtype),
    }


def _mlstm_qkvif(cfg, p, x):
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h
    xn = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    up = jnp.einsum("bsd,de->bse", xn, p["w_up"])
    v_in, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bsd,de->bse", v_in, p["wq"]).reshape(b, s, h, dh)
    k = jnp.einsum("bsd,de->bse", v_in, p["wk"]).reshape(b, s, h, dh)
    v = jnp.einsum("bsd,de->bse", v_in, p["wv"]).reshape(b, s, h, dh)
    k = k / dh ** 0.5
    ig = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", xn, p["wi"]).astype(F32))
    fg = jax.nn.log_sigmoid(
        (jnp.einsum("bsd,dh->bsh", xn, p["wf"]) + p["bf"]).astype(F32))
    return q, k, v, ig, fg, z


def mlstm_apply(cfg: ModelConfig, p: Params, x, *, chunk: int = 256,
                state=None, return_state: bool = False):
    """x: [B,S,d]. Chunkwise-parallel mLSTM. state=(C[B,H,dk,dv], n[B,H,dk])."""
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h
    chunk = min(chunk, s)
    n_chunks = s // chunk
    q, k, v, ig, fg, z = _mlstm_qkvif(cfg, p, x)

    if state is None:
        c0 = jnp.zeros((b, h, dh, dh), F32)
        n0 = jnp.zeros((b, h, dh), F32)
    else:
        c0, n0 = state["C"].astype(F32), state["n"].astype(F32)

    def to_chunks(a):
        return a.reshape((b, n_chunks, chunk) + a.shape[2:]).swapaxes(0, 1)

    qs, ks, vs, igs, fgs = map(to_chunks, (q, k, v, ig, fg))

    def body(carry, inp):
        c, n = carry
        qc, kc, vc, ic, fc = inp
        ld = jnp.cumsum(fc, axis=1)                     # [B,T,H] log decay
        # intra-chunk: W[t,s] = exp(ld_t - ld_s) * i_s  for s <= t
        wmask = (ld[:, :, None, :] - ld[:, None, :, :]) + jnp.log(
            jnp.maximum(ic, 1e-9))[:, None, :, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        wts = jnp.where(tri[None, :, :, None], jnp.exp(wmask), 0.0)  # [B,T,S,H]
        scores = jnp.einsum("bthd,bshd->btsh", qc.astype(F32), kc.astype(F32))
        wsc = scores * wts
        # bf16 weight tile for the V contraction (f32 accumulate): the
        # [T,S,H] tiles dominate chunk HBM traffic (Perf iteration H5)
        y_intra = jnp.einsum("btsh,bshd->bthd", wsc.astype(jnp.bfloat16),
                             vc.astype(jnp.bfloat16),
                             preferred_element_type=F32)
        den_intra = jnp.sum(wsc, axis=2)                 # row-sum == q.n_intra
        # inter-chunk: contribution of carried state
        dec_t = jnp.exp(ld)                              # [B,T,H]
        y_inter = jnp.einsum("bthd,bhde,bth->bthe", qc.astype(F32), c, dec_t)
        den_inter = jnp.einsum("bthd,bhd,bth->bth", qc.astype(F32), n, dec_t)
        den = jnp.maximum(jnp.abs(den_intra + den_inter), 1.0)
        y = (y_intra + y_inter) / den[..., None]
        # state update
        ld_tot = ld[:, -1, :]                            # [B,H]
        w_s = jnp.exp(ld_tot[:, None, :] - ld) * ic      # [B,T,H]
        c_new = jnp.exp(ld_tot)[:, :, None, None] * c + jnp.einsum(
            "bshd,bshe,bsh->bhde", kc.astype(F32), vc.astype(F32), w_s)
        n_new = jnp.exp(ld_tot)[:, :, None] * n + jnp.einsum(
            "bshd,bsh->bhd", kc.astype(F32), w_s)
        return (c_new, n_new), y

    (c_f, n_f), ys = lax.scan(body, (c0, n0), (qs, ks, vs, igs, fgs))
    y = ys.swapaxes(0, 1).reshape(b, s, d).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(F32)).astype(x.dtype)
    out = x + jnp.einsum("bsd,de->bse", y, p["w_down"])
    if return_state:
        return out, {"C": c_f, "n": n_f}
    return out


def mlstm_decode(cfg: ModelConfig, p: Params, x, state):
    """One-token recurrent update. x: [B,1,d]."""
    b, _, d = x.shape
    h = cfg.n_heads
    dh = d // h
    q, k, v, ig, fg, z = _mlstm_qkvif(cfg, p, x)
    q, k, v = (a[:, 0].astype(F32) for a in (q, k, v))    # [B,H,dh]
    i_t = ig[:, 0]                                        # [B,H]
    f_t = jnp.exp(fg[:, 0])
    # the outer product (k i) v^T as a broadcast product: the same f32
    # products as an einsum, but the compiler fuses it into the in-place
    # write of the state instead of building each block's new C apart
    c = state["C"].astype(F32) * f_t[:, :, None, None] + \
        (k * i_t[:, :, None])[:, :, :, None] * v[:, :, None, :]
    n = state["n"].astype(F32) * f_t[:, :, None] + k * i_t[:, :, None]
    num = jnp.einsum("bhd,bhde->bhe", q, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)), 1.0)
    y = (num / den[..., None]).reshape(b, 1, d).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(F32)).astype(x.dtype)
    return x + jnp.einsum("bsd,de->bse", y, p["w_down"]), {"C": c, "n": n}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_params(cfg: ModelConfig, rng, dtype) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    f_in = int(d * 4 / 3) // 128 * 128 or d
    r = L.split_rngs(rng, 4)
    return {
        "ln": L.rmsnorm_params(d, dtype),
        "w_gates": L._dense_init(r[0], (d, 4 * d), dtype),   # z i f o
        # one [dh, 4dh] recurrent matrix per head, so fan-in is dh (not the
        # head count): larger weights make the backward pass of the time
        # recurrence overflow to NaN at sequence lengths near 1024
        "r_gates": jax.vmap(lambda k: L._dense_init(k, (dh, 4 * dh), dtype))(
            jax.random.split(r[1], h)),
        "b_gates": jnp.zeros((4 * d,), dtype),
        "up": L.mlp_params(d, f_in, r[2], dtype),
    }


def _slstm_scan(cfg, p, gx, h0, c0, n0, m0):
    """gx: [B,S,4d] precomputed input contributions."""
    b, s, d4 = gx.shape
    d = d4 // 4
    h = cfg.n_heads
    dh = d // h

    def step(carry, g_t):
        hp, cp, np_, mp = carry
        rec = jnp.einsum("bhd,hde->bhe", hp, p["r_gates"].astype(F32))
        g = g_t.astype(F32).reshape(b, h, 4 * dh) + rec
        z, i_, f, o = jnp.split(g, 4, axis=-1)
        z = jnp.tanh(z)
        o = jax.nn.sigmoid(o)
        logf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(logf + mp, i_)
        i_p = jnp.exp(i_ - m_new)
        f_p = jnp.exp(logf + mp - m_new)
        c = f_p * cp + i_p * z
        n = jnp.maximum(f_p * np_ + i_p, 1e-6)
        h_out = o * c / n
        return (h_out, c, n, m_new), h_out

    (hf, cf, nf, mf), ys = lax.scan(step, (h0, c0, n0, m0),
                                    gx.swapaxes(0, 1))
    return ys.swapaxes(0, 1).reshape(b, s, d), (hf, cf, nf, mf)


def slstm_apply(cfg: ModelConfig, p: Params, x, *, state=None,
                return_state: bool = False):
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h
    xn = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    gx = jnp.einsum("bsd,de->bse", xn, p["w_gates"]) + p["b_gates"]
    if state is None:
        zeros = jnp.zeros((b, h, dh), F32)
        st = (zeros, zeros, zeros, jnp.full((b, h, dh), -30.0, F32))
    else:
        st = (state["h"], state["c"], state["n"], state["m"])
    y, (hf, cf, nf, mf) = _slstm_scan(cfg, p, gx, *st)
    y = L.mlp_apply(p["up"], y.astype(x.dtype))
    out = x + y
    if return_state:
        return out, {"h": hf, "c": cf, "n": nf, "m": mf}
    return out


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

class XLSTM:
    """Grouped scan: G groups of ((slstm_every-1) mLSTM + 1 sLSTM)."""

    def __init__(self, cfg: ModelConfig, *, remat: str = "full",
                 seq_chunk: int = 2048, **_):
        assert cfg.family == "ssm"
        self.cfg = cfg
        self.remat = remat
        self.seq_chunk = seq_chunk
        self.dtype = jnp.dtype(cfg.dtype)
        k = cfg.slstm_every
        assert cfg.n_layers % k == 0, "n_layers must divide by slstm_every"
        self.n_groups = cfg.n_layers // k
        self.m_per_group = k - 1

    def _maybe_remat(self, fn):
        return fn if self.remat == "none" else jax.checkpoint(fn)

    def init(self, rng) -> Params:
        cfg, dtype = self.cfg, self.dtype
        r_e, r_m, r_s = jax.random.split(rng, 3)
        g, mpg = self.n_groups, self.m_per_group
        rm = jax.random.split(r_m, g * mpg).reshape(g, mpg)
        rs = jax.random.split(r_s, g)
        return {
            "embed": L.embed_params(cfg, r_e, dtype),
            "mlstm": jax.vmap(jax.vmap(
                lambda r: mlstm_params(cfg, r, dtype)))(rm),
            "slstm": jax.vmap(lambda r: slstm_params(cfg, r, dtype))(rs),
            "ln_f": L.rmsnorm_params(cfg.d_model, dtype),
        }

    def init_abstract(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def backbone(self, params, x, *, chunk: int = 256):
        cfg = self.cfg

        def group(xc, gp):
            mp, sp = gp

            def m_body(xi, mpi):
                return mlstm_apply(cfg, mpi, xi, chunk=chunk), None
            xc, _ = lax.scan(self._maybe_remat(m_body), xc, mp)
            xc = slstm_apply(cfg, sp, xc)
            return xc, None

        x, _ = lax.scan(self._maybe_remat(group), x,
                        (params["mlstm"], params["slstm"]))
        return L.rmsnorm(params["ln_f"], x, cfg.norm_eps)

    def loss_fn(self, params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        x = L.embed_lookup(params["embed"], tokens)
        x = self.backbone(params, x)
        return L.chunked_lm_loss(self.cfg, params["embed"], x, labels,
                                 self.seq_chunk)

    # -- serve: recurrent state ------------------------------------------------

    def init_cache(self, batch: int, seq_len: int) -> dict:
        cfg = self.cfg
        g, mpg = self.n_groups, self.m_per_group
        d = cfg.d_model
        h = cfg.n_heads
        dh = d // h
        return {
            "mlstm": {"C": jnp.zeros((g, mpg, batch, h, dh, dh), F32),
                      "n": jnp.zeros((g, mpg, batch, h, dh), F32)},
            "slstm": {"h": jnp.zeros((g, batch, h, dh), F32),
                      "c": jnp.zeros((g, batch, h, dh), F32),
                      "n": jnp.zeros((g, batch, h, dh), F32),
                      "m": jnp.full((g, batch, h, dh), -30.0, F32)},
        }

    def prefill(self, params, batch, n_gen: int = DEFAULT_NEW_TOKENS):
        """The recurrent state is of fixed size: ``n_gen`` sizes nothing."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = L.embed_lookup(params["embed"], tokens)

        def group(xc, gp):
            mp, sp = gp

            def m_body(xi, mpi):
                xi, st = mlstm_apply(cfg, mpi, xi, return_state=True)
                return xi, st
            xc, m_states = lax.scan(self._maybe_remat(m_body), xc, mp)
            xc, s_state = slstm_apply(cfg, sp, xc, return_state=True)
            return xc, (m_states, s_state)

        x, (m_states, s_states) = lax.scan(self._maybe_remat(group), x,
                                           (params["mlstm"], params["slstm"]))
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.unembed(cfg, params["embed"], x[:, -1:, :])
        return logits, {"mlstm": m_states, "slstm": s_states}

    def decode_step(self, params, cache, tokens, pos):
        """One token through every block.  The stacked state rides in the
        loop carry and each block's slice is read and written back in
        place, so a donated cache is updated where it lies: no copy of the
        whole state into or out of a scan."""
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], tokens)

        def at(tree, *idx):
            return jax.tree.map(lambda a: a[idx], tree)

        def put(tree, new, *idx):
            # not ``.at[idx].set``: its scatter's bounds check makes the
            # compiler copy the block out before the update
            return jax.tree.map(
                lambda a, v: lax.dynamic_update_slice(
                    a, v[(None,) * len(idx)], idx + (0,) * v.ndim),
                tree, new)

        def group(g, carry):
            xc, m, s = carry

            def m_layer(j, c):
                xc, m = c
                xc, st = mlstm_decode(cfg, at(params["mlstm"], g, j), xc,
                                      at(m, g, j))
                return xc, put(m, st, g, j)
            xc, m = lax.fori_loop(0, self.m_per_group, m_layer, (xc, m))
            xc, st = slstm_apply(cfg, at(params["slstm"], g), xc,
                                 state=at(s, g), return_state=True)
            return xc, m, put(s, st, g)

        x, m, s = lax.fori_loop(0, self.n_groups, group,
                                (x, cache["mlstm"], cache["slstm"]))
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.unembed(cfg, params["embed"], x)
        return logits, {"mlstm": m, "slstm": s}
