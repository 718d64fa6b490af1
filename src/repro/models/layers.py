"""Shared neural-net layers (pure JAX, functional params-as-pytrees).

Conventions
-----------
- Params are nested dicts of jnp arrays; layer-stacked params carry a leading
  ``L`` axis and are consumed by ``jax.lax.scan``.
- Activations: ``x[batch, seq, d_model]``; attention heads ``[B, S, H, Dh]``.
- Compute dtype is bf16 with f32 softmax/norm/loss accumulation.
- Attention is *blockwise* (online softmax over KV tiles) so the lowered HLO
  never materialises an [S, S] score matrix; the sliding-window path visits
  only ``window/kv_block + 1`` KV tiles per query tile, so SWA prefill is
  O(S*w), not O(S^2) — this mirrors the Pallas kernel's tiling (kernels/).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

Params = Any
F32 = jnp.float32


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(rng, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) >= 1 else 1
    std = scale / max(fan_in, 1) ** 0.5
    return (jax.random.normal(rng, shape, F32) * std).astype(dtype)


def split_rngs(rng, n):
    return list(jax.random.split(rng, n))


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_params(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, Dh]; positions: [B, S] absolute token positions."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, half, dtype=F32) / half)
    ang = positions[..., None].astype(F32) * freqs  # [B, S, half]
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA + causal + sliding window), blockwise online softmax
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _scores_block(q, k, q_pos, k_pos, window, causal: bool = True,
                  scale: Optional[float] = None):
    """q: [B, Tq, Hkv, G, Dh], k: [B, Tk, Hkv, Dh] -> masked f32 scores,
    scaled by ``scale`` (default head_dim ** -0.5)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(F32), k.astype(F32))
    s = s * (scale or 1.0 / q.shape[-1] ** 0.5)
    mask = (k_pos >= 0)[:, None, :]                           # empty cache slots
    if causal:
        mask &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
    return jnp.where(mask[:, None, None, :, :], s, NEG_INF)


def _online_update(carry, s, v):
    """Streaming softmax accumulate. carry = (m, l, acc).

    The probability tile is cast to bf16 for the PV contraction (f32
    accumulation via preferred_element_type): the [Tq, Tk] tiles are the
    largest tensors crossing fusion boundaries in the lowered step, and
    halving them cuts the attention HBM term ~2x at <1e-3 relative error
    (EXPERIMENTS.md section Perf, iteration H2)."""
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(jnp.bfloat16),
                    v.astype(jnp.bfloat16), preferred_element_type=F32)
    acc = acc * corr[..., None] + pv
    return m_new, l, acc


def blockwise_attention(
    q: jnp.ndarray,            # [B, Sq, Hq, Dh]
    k: jnp.ndarray,            # [B, Skv, Hkv, Dh]
    v: jnp.ndarray,            # [B, Skv, Hkv, Dh]
    q_pos: jnp.ndarray,        # [B, Sq]
    k_pos: jnp.ndarray,        # [B, Skv]
    *,
    window: int = 0,
    kv_block: int = 512,
    q_block: int = 512,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal (optionally sliding-window) attention, O(Sq*w) for SWA."""
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    q = q.reshape(b, sq, hkv, g, dh)

    kv_block = min(kv_block, skv)
    q_block = min(q_block, sq)
    n_kv = -(-skv // kv_block)

    # pad KV to a block multiple: dynamic_slice CLAMPS out-of-range starts,
    # which would make the final partial block overlap (double-counting
    # those keys in the softmax). Padded slots carry pos=-1 and are masked.
    pad_kv = n_kv * kv_block - skv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
        skv = skv + pad_kv

    def attend_tiles(q_tile, qp_tile, kv_start, n_tiles):
        """Stream ``n_tiles`` KV tiles beginning at kv_start (static count)."""
        m0 = jnp.full((b, hkv, g, q_tile.shape[1]), NEG_INF, F32)
        l0 = jnp.zeros_like(m0)
        a0 = jnp.zeros((b, hkv, g, q_tile.shape[1], dh), F32)

        def body(carry, i):
            start = kv_start + i * kv_block
            k_t = lax.dynamic_slice_in_dim(k, start, kv_block, axis=1)
            v_t = lax.dynamic_slice_in_dim(v, start, kv_block, axis=1)
            kp_t = lax.dynamic_slice_in_dim(k_pos, start, kv_block, axis=1)
            s = _scores_block(q_tile, k_t, qp_tile, kp_t, window, causal,
                              scale)
            return _online_update(carry, s, v_t), None

        (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(n_tiles))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # [B, Hkv, G, Tq, Dh]

    if window and skv > window + kv_block:
        # SWA: per query tile only visit tiles covering [q_start - window, q_end]
        n_win = min(window // kv_block + (q_block // kv_block) + 1, n_kv)
        n_q = -(-sq // q_block)
        pad_q = n_q * q_block - sq
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
            q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=0)

        def q_body(_, qi):
            q_start = qi * q_block
            q_tile = lax.dynamic_slice_in_dim(q, q_start, q_block, axis=1)
            qp_tile = lax.dynamic_slice_in_dim(q_pos, q_start, q_block, axis=1)
            kv_start = jnp.clip(q_start + q_block - n_win * kv_block, 0, skv - n_win * kv_block)
            out = attend_tiles(q_tile, qp_tile, kv_start, n_win)
            return None, out

        _, outs = lax.scan(q_body, None, jnp.arange(n_q))
        # outs: [n_q, B, Hkv, G, Tq, Dh] -> [B, Sq, Hq, Dh]
        out = jnp.moveaxis(outs, 0, 3)  # [B, Hkv, G, n_q, Tq, Dh]
        out = out.reshape(b, hkv, g, n_q * q_block, dh)[:, :, :, :sq]
    else:
        out = attend_tiles(q, q_pos, 0, n_kv)
        out = out.reshape(b, hkv, g, sq, dh)

    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, hq, dh)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, q_pos, k_pos, *, window: int = 0,
                     scale: Optional[float] = None):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: [B, 1, Hq, Dh]; caches: [B, S, Hkv, Dh]; k_pos: [B, S] absolute
    positions (-1 for unwritten slots).
    """
    b, _, hq, dh = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, dh)
    s = _scores_block(qg, k_cache, q_pos, k_pos, window,
                      scale=scale)                      # [B,Hkv,G,1,S]
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_cache.astype(F32)) / l[..., None]
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, 1, hq, dh)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention layer (params + apply), with optional KV cache
# ---------------------------------------------------------------------------

def attention_params(cfg: ModelConfig, rng, dtype, d_model: int = 0) -> Params:
    """q/k/v read ``cfg.attn_in`` features where it is set (Zamba2's
    concatenated input), ``d_model`` otherwise; the output is d_model."""
    d = d_model or cfg.d_model
    d_in = cfg.attn_in or d
    dh = cfg.resolved_head_dim
    r = split_rngs(rng, 4)
    p = {
        "wq": _dense_init(r[0], (d_in, cfg.n_heads, dh), dtype),
        "wk": _dense_init(r[1], (d_in, cfg.n_kv_heads, dh), dtype),
        "wv": _dense_init(r[2], (d_in, cfg.n_kv_heads, dh), dtype),
        "wo": _dense_init(r[3], (cfg.n_heads, dh, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, dh), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, dh), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_params(dh, dtype)
        p["k_norm"] = rmsnorm_params(dh, dtype)
    return p


def _project_qkv(cfg: ModelConfig, p: Params, x, positions, rope_theta):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q, k, v


def attention_apply(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    cache: Optional[dict] = None,
    n_gen: Optional[int] = None,
    kv_block: int = 512,
    use_rope: bool = True,
    window: Optional[int] = None,
):
    """Returns (y, new_cache).  With ``cache`` one token is decoded against
    it; otherwise the whole sequence attends causally, and with ``n_gen``
    the prefill emits a cache with room for ``n_gen`` decoded tokens.
    The softmax scale is ``cfg.attn_scale`` where set."""
    theta = cfg.rope_theta if use_rope else 0.0
    win = cfg.sliding_window if window is None else window
    scale = cfg.attn_scale or None
    q, k, v = _project_qkv(cfg, p, x, positions, theta)

    if cache is None:
        out = blockwise_attention(q, k, v, positions, positions,
                                  window=win, kv_block=kv_block, scale=scale)
        new_cache = None if n_gen is None else \
            init_cache_from(cfg, k, v, positions, win, n_gen)
    else:
        # decode: write into ring buffer, attend against the cache
        slot = (cache["idx"] % cache["k"].shape[1]).astype(jnp.int32)
        k_cache = _ring_write(cache["k"], k, slot)
        v_cache = _ring_write(cache["v"], v, slot)
        k_pos = lax.dynamic_update_slice(
            cache["pos"], positions.astype(cache["pos"].dtype)[:, :1],
            (0, slot))
        out = decode_attention(q, k_cache, v_cache, positions, k_pos,
                               window=win, scale=scale)
        new_cache = {"k": k_cache, "v": v_cache, "pos": k_pos,
                     "idx": cache["idx"] + 1}

    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    if cfg.attn_out_bias and "bo" in p:
        y = y + p["bo"]
    return y, new_cache


def _ring_write(cache, val, slot):
    """cache [B,S,H,D]; val [B,1,H,D]; scalar slot."""
    return lax.dynamic_update_slice(cache, val.astype(cache.dtype),
                                    (0, slot, 0, 0))


def init_cache_from(cfg: ModelConfig, k, v, positions, window: int,
                    n_gen: int):
    """Build a cache from prefill keys/values with room for ``n_gen``
    decoded tokens.

    Decode writes position p at slot p %% capacity (idx counts from the
    prompt's length).  Full-attention archs hold prompt + ``n_gen`` slots,
    so no decoded key overwrites another.  Sliding-window archs hold
    min(prompt + n_gen, window) slots (the Mistral rolling buffer), each
    key at the slot of its position, so a ring write always replaces the
    oldest key."""
    s = k.shape[1]
    cap = min(s + n_gen, window) if window else s + n_gen
    pos = positions.astype(jnp.int32)
    if cap > s:
        pad = [(0, 0), (0, cap - s), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        pos = jnp.pad(pos, pad[:2], constant_values=-1)
    elif cap < s or s % cap:
        k, v, pos = (jnp.roll(a[:, s - cap:], s % cap, axis=1)
                     for a in (k, v, pos))
    return {"k": k, "v": v, "pos": pos, "idx": jnp.asarray(s, jnp.int32)}


def empty_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype,
                n_layers: int = 0, d_model: int = 0) -> dict:
    """Abstract/concrete KV cache for one layer (stacked externally)."""
    dh = cfg.resolved_head_dim
    shape = (batch, cache_len, cfg.n_kv_heads, dh)
    lead = (n_layers,) if n_layers else ()
    return {
        "k": jnp.zeros(lead + shape, dtype),
        "v": jnp.zeros(lead + shape, dtype),
        "pos": -jnp.ones(lead + (batch, cache_len), jnp.int32),
        "idx": jnp.zeros(lead, jnp.int32) if lead else jnp.asarray(0, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Cross attention (VLM image layers, whisper decoder)
# ---------------------------------------------------------------------------

def cross_attention_params(cfg: ModelConfig, rng, dtype) -> Params:
    p = attention_params(cfg, rng, dtype)
    p["gate"] = jnp.zeros((), dtype)  # gated cross-attn (llama-vision style)
    return p


def cross_attention_kv(cfg: ModelConfig, p: Params, memory):
    """Precompute memory K/V once (prefill); reused every decode step."""
    k = jnp.einsum("bmd,dhe->bmhe", memory, p["wk"])
    v = jnp.einsum("bmd,dhe->bmhe", memory, p["wv"])
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return k, v


def cross_attention_apply(cfg: ModelConfig, p: Params, x, memory=None, *,
                          kv=None, gated=True):
    """x: [B,S,d] queries; memory: [B,M,d] encoder/image states (no RoPE)."""
    b, s, _ = x.shape
    if kv is None:
        kv = cross_attention_kv(cfg, p, memory)
    k, v = kv
    m = k.shape[1]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
    qpos = jnp.zeros((b, s), jnp.int32)
    kpos = jnp.zeros((b, m), jnp.int32)
    out = blockwise_attention(q, k, v, qpos, kpos, window=0,
                              kv_block=min(512, m), causal=False)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    if gated:
        y = y * jnp.tanh(p["gate"].astype(F32)).astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_params(d: int, f: int, rng, dtype) -> Params:
    r = split_rngs(rng, 3)
    return {
        "wi": _dense_init(r[0], (d, f), dtype),
        "wg": _dense_init(r[1], (d, f), dtype),
        "wo": _dense_init(r[2], (f, d), dtype),
    }


def mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    h = h * jax.nn.silu(g.astype(F32)).astype(h.dtype)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding + chunked cross-entropy over a (model-)sharded vocab
# ---------------------------------------------------------------------------

def embed_params(cfg: ModelConfig, rng, dtype) -> Params:
    r = split_rngs(rng, 2)
    p = {"embed": _dense_init(r[0], (cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(r[1], (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed_lookup(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["embed"], tokens, axis=0)


def unembed(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = p.get("unembed")
    if w is None:
        w = p["embed"].T
    return jnp.einsum("bsd,dv->bsv", x, w)


def chunked_lm_loss(cfg: ModelConfig, p_embed: Params, x: jnp.ndarray,
                    labels: jnp.ndarray, seq_chunk: int = 2048) -> jnp.ndarray:
    """Cross-entropy without materialising [B, S, V] logits.

    Scans over sequence chunks; inside a chunk the [B, C, V] logits live with
    V sharded over `model`, and the reductions (logsumexp, label pick) lower
    to per-shard partials + psum under SPMD.
    """
    b, s, d = x.shape
    chunk = min(seq_chunk, s)
    n = s // chunk
    xs = x[:, : n * chunk].reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels[:, : n * chunk].reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(tot, xl):
        xc, lc = xl
        logits = unembed(cfg, p_embed, xc).astype(F32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot_pick = jnp.sum(
            jnp.where(
                lax.broadcasted_iota(jnp.int32, logits.shape, 2) == lc[..., None],
                logits, 0.0),
            axis=-1)
        return tot + jnp.sum(lse - onehot_pick), None

    total, _ = lax.scan(body, jnp.zeros((), F32), (xs, ls))
    # remainder chunk (shapes in this repo divide evenly; guard anyway)
    rem = s - n * chunk
    if rem:
        logits = unembed(cfg, p_embed, x[:, n * chunk:]).astype(F32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lc = labels[:, n * chunk:]
        pick = jnp.sum(
            jnp.where(lax.broadcasted_iota(jnp.int32, logits.shape, 2) == lc[..., None],
                      logits, 0.0), axis=-1)
        total = total + jnp.sum(lse - pick)
    return total / (b * s)
