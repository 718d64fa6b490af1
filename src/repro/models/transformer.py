"""Decoder-only transformer (dense / MoE / VLM families).

Layers are stacked along a leading ``L`` axis and consumed by ``lax.scan`` so
the lowered HLO contains ONE transformer-layer body regardless of depth —
this keeps 80-layer dry-run compiles tractable and is also the production
pattern (layer-scanned pjit programs).

VLM (llama-3.2-vision): layers are grouped as ``n_layers = G * cross_every``;
each group = one gated cross-attention layer (image memory) followed by
``cross_every`` self-attention layers. Nested scan: outer over groups, inner
over self layers.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import DEFAULT_NEW_TOKENS, ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE

F32 = jnp.float32
Params = Any


def _ffn_params(cfg: ModelConfig, rng, dtype) -> Params:
    if cfg.n_experts:
        return MOE.moe_params(cfg, rng, dtype)
    return L.mlp_params(cfg.d_model, cfg.d_ff, rng, dtype)


def _ffn_apply(cfg: ModelConfig, p: Params, x):
    if cfg.n_experts:
        return MOE.moe_apply(cfg, p, x)
    return L.mlp_apply(p, x)


def _layer_params(cfg: ModelConfig, rng, dtype) -> Params:
    r = L.split_rngs(rng, 2)
    return {
        "ln1": L.rmsnorm_params(cfg.d_model, dtype),
        "attn": L.attention_params(cfg, r[0], dtype),
        "ln2": L.rmsnorm_params(cfg.d_model, dtype),
        "ffn": _ffn_params(cfg, r[1], dtype),
    }


def _layer_apply(cfg: ModelConfig, lp: Params, x, positions, *, cache=None,
                 n_gen=None, kv_block=512):
    h, new_cache = L.attention_apply(
        cfg, lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), positions,
        cache=cache, n_gen=n_gen, kv_block=kv_block)
    x = x + h
    x = x + _ffn_apply(cfg, lp["ffn"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
    return x, new_cache


class Transformer:
    """Functional model wrapper: init / loss / prefill / decode_step."""

    def __init__(self, cfg: ModelConfig, *, remat: str = "full",
                 kv_block: int = 512, seq_chunk: int = 2048):
        assert cfg.family in ("dense", "moe", "vlm")
        self.cfg = cfg
        self.remat = remat
        self.kv_block = kv_block
        self.seq_chunk = seq_chunk
        self.dtype = jnp.dtype(cfg.dtype)
        if cfg.family == "vlm":
            assert cfg.n_layers % cfg.cross_attn_every == 0
            self.n_groups = cfg.n_layers // cfg.cross_attn_every

    # -- params ---------------------------------------------------------------

    def init(self, rng) -> Params:
        cfg, dtype = self.cfg, self.dtype
        r_embed, r_layers, r_cross = jax.random.split(rng, 3)
        p = {"embed": L.embed_params(cfg, r_embed, dtype),
             "ln_f": L.rmsnorm_params(cfg.d_model, dtype)}
        if cfg.family == "vlm":
            g, k = self.n_groups, cfg.cross_attn_every
            rs = jax.random.split(r_layers, g * k).reshape(g, k)
            p["layers"] = jax.vmap(jax.vmap(
                lambda r: _layer_params(cfg, r, dtype)))(rs)
            rc = jax.random.split(r_cross, g)
            p["cross"] = jax.vmap(
                lambda r: L.cross_attention_params(cfg, r, dtype))(rc)
        else:
            rs = jax.random.split(r_layers, cfg.n_layers)
            p["layers"] = jax.vmap(lambda r: _layer_params(cfg, r, dtype))(rs)
        return p

    def init_abstract(self) -> Params:
        return jax.eval_shape(self.init, jax.random.key(0))

    # -- forward --------------------------------------------------------------

    def _maybe_remat(self, fn):
        if self.remat == "none":
            return fn
        policy = None
        if self.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)

    def _forward(self, params: Params, tokens, positions, *, cache=None,
                 n_gen=None, image_embeds=None):
        """Final hidden states and the new cache.  With ``cache`` one token
        is decoded; with ``n_gen`` a prefill emits a cache with room for
        ``n_gen`` decoded tokens; with neither, training."""
        cfg = self.cfg
        remat = self._maybe_remat if cache is None else (lambda fn: fn)
        x = L.embed_lookup(params["embed"], tokens)

        def self_layers(xc, lp, caches):
            def body(xi, lc):
                lpi, ci = lc
                return _layer_apply(cfg, lpi, xi, positions, cache=ci,
                                    n_gen=n_gen, kv_block=self.kv_block)
            return lax.scan(remat(body), xc, (lp, caches))

        if cfg.family == "vlm":
            # the image memory's K/V: from the cache when decoding, else
            # computed (and, in a prefill, emitted) once per group
            def group(xc, gp):
                lp, cp, ckv, sc = gp
                if ckv is None:
                    kv = L.cross_attention_kv(cfg, cp, image_embeds)
                else:
                    kv = (ckv["k"], ckv["v"])
                xc = xc + L.cross_attention_apply(cfg, cp, xc, kv=kv)
                xc, new_sc = self_layers(xc, lp, sc)
                return xc, (new_sc, None if n_gen is None else kv)
            cross, self_cache = (None, None) if cache is None else \
                (cache["cross"], cache["self"])
            x, (self_cache, kvs) = lax.scan(
                remat(group), x,
                (params["layers"], params["cross"], cross, self_cache))
            if kvs is not None:
                cross = {"k": kvs[0], "v": kvs[1]}
            new_cache = {"self": self_cache, "cross": cross}
        else:
            x, new_cache = self_layers(x, params["layers"], cache)
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return x, new_cache

    # -- train ----------------------------------------------------------------

    def loss_fn(self, params: Params, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x, _ = self._forward(params, tokens, positions,
                             image_embeds=batch.get("image_embeds"))
        loss = L.chunked_lm_loss(cfg, params["embed"], x, labels,
                                 self.seq_chunk)
        if cfg.n_experts:
            # cheap aux loss on the first layer's router only (scanned params)
            router0 = jax.tree.map(lambda a: a[0], params["layers"]["ffn"])
            loss = loss + 0.01 * MOE.moe_aux_loss(cfg, router0, x)
        return loss

    # -- serve ----------------------------------------------------------------

    def cache_len(self, seq_len: int) -> int:
        w = self.cfg.sliding_window
        return min(seq_len, w) if w else seq_len

    def init_cache(self, batch: int, seq_len: int) -> dict:
        cfg = self.cfg
        cl = self.cache_len(seq_len)
        if cfg.family == "vlm":
            cache = L.empty_cache(cfg, batch, cl, self.dtype)
            cache = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (self.n_groups, cfg.cross_attn_every) + a.shape).copy(),
                cache)
            dh = cfg.resolved_head_dim
            cache_cross = {
                "k": jnp.zeros((self.n_groups, batch, cfg.n_image_tokens,
                                cfg.n_kv_heads, dh), self.dtype),
                "v": jnp.zeros((self.n_groups, batch, cfg.n_image_tokens,
                                cfg.n_kv_heads, dh), self.dtype),
            }
            return {"self": cache, "cross": cache_cross}
        cache = L.empty_cache(cfg, batch, cl, self.dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(),
            cache)

    def prefill(self, params: Params, batch: dict,
                n_gen: int = DEFAULT_NEW_TOKENS):
        """Process the full prompt; return (last_logits, cache) with room
        for ``n_gen`` decoded tokens."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x, cache = self._forward(params, tokens, positions, n_gen=n_gen,
                                 image_embeds=batch.get("image_embeds"))
        logits = L.unembed(self.cfg, params["embed"], x[:, -1:, :])
        return logits, cache

    def decode_step(self, params: Params, cache, tokens, pos):
        """tokens: [B, 1]; pos: [B, 1] absolute positions."""
        x, new_cache = self._forward(params, tokens, pos, cache=cache)
        logits = L.unembed(self.cfg, params["embed"], x)
        return logits, new_cache
