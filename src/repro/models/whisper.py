"""Whisper-style encoder/decoder backbone (audio family).

The conv/mel frontend is a STUB per the assignment: the model consumes
precomputed frame embeddings ``frames[B, n_frames, d_model]`` (what the conv
stack would emit). Encoder layers are bidirectional; decoder layers are
causal self-attention + cross-attention into the encoder output.

Adaptations from the published model (see DESIGN.md): RMSNorm instead of
biased LayerNorm, SwiGLU-free plain GELU MLP retained, sinusoidal positions
replaced by RoPE on the decoder (rotary is TPU-friendlier than learned
position tables and does not change backbone cost).
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


def _gelu_mlp_params(d, f, rng, dtype):
    r = L.split_rngs(rng, 2)
    return {"wi": L._dense_init(r[0], (d, f), dtype),
            "wo": L._dense_init(r[1], (f, d), dtype)}


def _gelu_mlp(p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    h = jax.nn.gelu(h.astype(F32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def _enc_layer_params(cfg, rng, dtype):
    r = L.split_rngs(rng, 2)
    return {"ln1": L.rmsnorm_params(cfg.d_model, dtype),
            "attn": L.attention_params(cfg, r[0], dtype),
            "ln2": L.rmsnorm_params(cfg.d_model, dtype),
            "mlp": _gelu_mlp_params(cfg.d_model, cfg.d_ff, r[1], dtype)}


def _dec_layer_params(cfg, rng, dtype):
    r = L.split_rngs(rng, 3)
    return {"ln1": L.rmsnorm_params(cfg.d_model, dtype),
            "attn": L.attention_params(cfg, r[0], dtype),
            "ln_x": L.rmsnorm_params(cfg.d_model, dtype),
            "xattn": L.cross_attention_params(cfg, r[1], dtype),
            "ln2": L.rmsnorm_params(cfg.d_model, dtype),
            "mlp": _gelu_mlp_params(cfg.d_model, cfg.d_ff, r[2], dtype)}


class Whisper:
    def __init__(self, cfg: ModelConfig, *, remat: str = "full",
                 kv_block: int = 512, seq_chunk: int = 2048):
        assert cfg.family == "audio" and cfg.is_encoder_decoder
        self.cfg = cfg
        self.remat = remat
        self.kv_block = kv_block
        self.seq_chunk = seq_chunk
        self.dtype = jnp.dtype(cfg.dtype)

    def _maybe_remat(self, fn):
        return fn if self.remat == "none" else jax.checkpoint(fn)

    def init(self, rng) -> Params:
        cfg, dtype = self.cfg, self.dtype
        r_e, r_enc, r_dec = jax.random.split(rng, 3)
        enc_rngs = jax.random.split(r_enc, cfg.n_encoder_layers)
        dec_rngs = jax.random.split(r_dec, cfg.n_layers)
        return {
            "embed": L.embed_params(cfg, r_e, dtype),
            "enc_layers": jax.vmap(
                lambda r: _enc_layer_params(cfg, r, dtype))(enc_rngs),
            "dec_layers": jax.vmap(
                lambda r: _dec_layer_params(cfg, r, dtype))(dec_rngs),
            "ln_enc": L.rmsnorm_params(cfg.d_model, dtype),
            "ln_f": L.rmsnorm_params(cfg.d_model, dtype),
        }

    def init_abstract(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    # -- encoder ---------------------------------------------------------------

    def encode(self, params: Params, frames: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        b, m, _ = frames.shape
        pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (b, m))

        def body(x, lp):
            h_in = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            q, k, v = L._project_qkv(cfg, lp["attn"], h_in, pos, cfg.rope_theta)
            out = L.blockwise_attention(q, k, v, pos, pos, window=0,
                                        kv_block=self.kv_block, causal=False)
            x = x + jnp.einsum("bshe,hed->bsd", out, lp["attn"]["wo"])
            x = x + _gelu_mlp(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
            return x, None

        x, _ = lax.scan(self._maybe_remat(body), frames.astype(self.dtype),
                        params["enc_layers"])
        return L.rmsnorm(params["ln_enc"], x, cfg.norm_eps)

    # -- decoder ---------------------------------------------------------------

    def _dec_layer(self, lp, x, positions, memory_kv, cache=None):
        cfg = self.cfg
        h, new_cache = L.attention_apply(
            cfg, lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), positions,
            cache=cache, kv_block=self.kv_block, window=0)
        x = x + h
        x = x + L.cross_attention_apply(
            cfg, lp["xattn"], L.rmsnorm(lp["ln_x"], x, cfg.norm_eps),
            kv=memory_kv, gated=False)
        x = x + _gelu_mlp(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return x, new_cache

    def loss_fn(self, params: Params, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        memory = self.encode(params, batch["frames"])
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = L.embed_lookup(params["embed"], tokens)

        def body(xc, lp):
            kv = L.cross_attention_kv(cfg, lp["xattn"], memory)
            xc, _ = self._dec_layer(lp, xc, pos, kv)
            return xc, None

        x, _ = lax.scan(self._maybe_remat(body), x, params["dec_layers"])
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return L.chunked_lm_loss(cfg, params["embed"], x, labels,
                                 self.seq_chunk)

    def prefill(self, params: Params, batch: dict,
                n_gen: int = DEFAULT_NEW_TOKENS):
        cfg = self.cfg
        tokens = batch["tokens"]
        memory = self.encode(params, batch["frames"])
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = L.embed_lookup(params["embed"], tokens)

        def body(xc, lp):
            kv = L.cross_attention_kv(cfg, lp["xattn"], memory)
            h_in = L.rmsnorm(lp["ln1"], xc, cfg.norm_eps)
            q, k, v = L._project_qkv(cfg, lp["attn"], h_in, pos, cfg.rope_theta)
            out = L.blockwise_attention(q, k, v, pos, pos, window=0,
                                        kv_block=self.kv_block)
            xc = xc + jnp.einsum("bshe,hed->bsd", out, lp["attn"]["wo"])
            xc = xc + L.cross_attention_apply(
                cfg, lp["xattn"], L.rmsnorm(lp["ln_x"], xc, cfg.norm_eps),
                kv=kv, gated=False)
            xc = xc + _gelu_mlp(lp["mlp"],
                                L.rmsnorm(lp["ln2"], xc, cfg.norm_eps))
            self_cache = L.init_cache_from(cfg, k, v, pos, 0, n_gen)
            return xc, (self_cache, kv)

        x, (self_cache, cross_kv) = lax.scan(self._maybe_remat(body), x,
                                             params["dec_layers"])
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.unembed(cfg, params["embed"], x[:, -1:, :])
        return logits, {"self": self_cache,
                        "cross": {"k": cross_kv[0], "v": cross_kv[1]}}

    def init_cache(self, batch: int, seq_len: int) -> dict:
        cfg = self.cfg
        n = cfg.n_layers
        self_cache = L.empty_cache(cfg, batch, seq_len, self.dtype, n_layers=n)
        dh = cfg.resolved_head_dim
        cross = {"k": jnp.zeros((n, batch, cfg.n_frames, cfg.n_kv_heads, dh),
                                self.dtype),
                 "v": jnp.zeros((n, batch, cfg.n_frames, cfg.n_kv_heads, dh),
                                self.dtype)}
        return {"self": self_cache, "cross": cross}

    def decode_step(self, params: Params, cache, tokens, pos):
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], tokens)

        def body(xc, lc):
            lp, sc, ck, cv = lc
            xi, nc = self._dec_layer(lp, xc, pos, (ck, cv), cache=sc)
            return xi, nc

        x, new_self = lax.scan(
            body, x, (params["dec_layers"], cache["self"],
                      cache["cross"]["k"], cache["cross"]["v"]))
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = L.unembed(cfg, params["embed"], x)
        return logits, {"self": new_self, "cross": cache["cross"]}
