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

import functools
from typing import Any, Optional

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
                 kv_block=512, window=None):
    h, new_cache = L.attention_apply(
        cfg, lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), positions,
        cache=cache, kv_block=kv_block, window=window)
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

    def backbone(self, params: Params, x, positions, *, image_embeds=None):
        """Full-sequence forward (train / prefill w/o cache emission)."""
        cfg = self.cfg

        if cfg.family == "vlm":
            def group(xc, gp):
                lp, cp = gp
                kv = L.cross_attention_kv(cfg, cp, image_embeds)
                xc = xc + L.cross_attention_apply(cfg, cp, xc, kv=kv)

                def self_layer(xi, lpi):
                    xi, _ = _layer_apply(cfg, lpi, xi, positions,
                                         kv_block=self.kv_block)
                    return xi, None
                xc, _ = lax.scan(self._maybe_remat(self_layer), xc, lp)
                return xc, None
            x, _ = lax.scan(self._maybe_remat(group), x,
                            (params["layers"], params["cross"]))
        else:
            def body(xc, lp):
                xc, _ = _layer_apply(cfg, lp, xc, positions,
                                     kv_block=self.kv_block)
                return xc, None
            x, _ = lax.scan(self._maybe_remat(body), x, params["layers"])
        return L.rmsnorm(params["ln_f"], x, cfg.norm_eps)

    # -- train ----------------------------------------------------------------

    def loss_fn(self, params: Params, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = L.embed_lookup(params["embed"], tokens)
        x = self.backbone(params, x, positions,
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
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = L.embed_lookup(params["embed"], tokens)
        image_embeds = batch.get("image_embeds")

        if cfg.family == "vlm":
            def group(xc, gp):
                lp, cp = gp
                kv = L.cross_attention_kv(cfg, cp, image_embeds)
                xc = xc + L.cross_attention_apply(cfg, cp, xc, kv=kv)

                def self_layer2(xi, lpi):
                    h_in = L.rmsnorm(lpi["ln1"], xi, cfg.norm_eps)
                    q, k, v = L._project_qkv(cfg, lpi["attn"], h_in, positions,
                                             cfg.rope_theta)
                    out = L.blockwise_attention(
                        q, k, v, positions, positions,
                        window=cfg.sliding_window, kv_block=self.kv_block)
                    h = jnp.einsum("bshe,hed->bsd", out, lpi["attn"]["wo"])
                    xi = xi + h
                    xi = xi + _ffn_apply(cfg, lpi["ffn"],
                                         L.rmsnorm(lpi["ln2"], xi, cfg.norm_eps))
                    return xi, L.init_cache_from(cfg, k, v, positions,
                                                 cfg.sliding_window, n_gen)
                xc, caches = lax.scan(self._maybe_remat(self_layer2), xc, lp)
                return xc, (caches, kv)
            x, (self_caches, cross_kvs) = lax.scan(
                self._maybe_remat(group), x, (params["layers"], params["cross"]))
            cache = {"self": self_caches,
                     "cross": {"k": cross_kvs[0], "v": cross_kvs[1]}}
        else:
            def body(xc, lp):
                h_in = L.rmsnorm(lp["ln1"], xc, cfg.norm_eps)
                q, k, v = L._project_qkv(cfg, lp["attn"], h_in, positions,
                                         cfg.rope_theta)
                out = L.blockwise_attention(
                    q, k, v, positions, positions,
                    window=cfg.sliding_window, kv_block=self.kv_block)
                h = jnp.einsum("bshe,hed->bsd", out, lp["attn"]["wo"])
                xc = xc + h
                xc = xc + _ffn_apply(cfg, lp["ffn"],
                                     L.rmsnorm(lp["ln2"], xc, cfg.norm_eps))
                return xc, L.init_cache_from(cfg, k, v, positions,
                                             cfg.sliding_window, n_gen)
            x, cache = lax.scan(self._maybe_remat(body), x, params["layers"])

        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.unembed(cfg, params["embed"], x[:, -1:, :])
        return logits, cache

    def decode_step(self, params: Params, cache, tokens, pos):
        """tokens: [B, 1]; pos: [B, 1] absolute positions."""
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], tokens)

        if cfg.family == "vlm":
            def group_body(xc, gp):
                lp, cp, ckv, sc = gp
                xc = xc + L.cross_attention_apply(cfg, cp, xc,
                                                  kv=(ckv["k"], ckv["v"]))
                def self_layer(xi, lc):
                    lpi, ci = lc
                    xi, nc = _layer_apply(cfg, lpi, xi, pos, cache=ci,
                                          kv_block=self.kv_block)
                    return xi, nc
                xc, new_sc = lax.scan(self_layer, xc, (lp, sc))
                return xc, new_sc
            x, new_self = lax.scan(
                group_body, x,
                (params["layers"], params["cross"], cache["cross"],
                 cache["self"]))
            new_cache = {"self": new_self, "cross": cache["cross"]}
        else:
            def body(xc, lc):
                lp, ci = lc
                xi, nc = _layer_apply(cfg, lp, xc, pos, cache=ci,
                                      kv_block=self.kv_block)
                return xi, nc
            x, new_cache = lax.scan(body, x, (params["layers"], cache))

        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.unembed(cfg, params["embed"], x)
        return logits, new_cache
