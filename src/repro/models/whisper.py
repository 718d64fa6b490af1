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
            h, _ = L.attention_apply(
                cfg, lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), pos,
                kv_block=self.kv_block, window=0, causal=False)
            x = x + h
            x = x + _gelu_mlp(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
            return x, None

        x, _ = lax.scan(self._maybe_remat(body), frames.astype(self.dtype),
                        params["enc_layers"])
        return L.rmsnorm(params["ln_enc"], x, cfg.norm_eps)

    # -- decoder ---------------------------------------------------------------

    def _dec_layer(self, lp, x, positions, memory_kv, cache=None,
                   n_gen=None):
        cfg = self.cfg
        h, new_cache = L.attention_apply(
            cfg, lp["attn"], L.rmsnorm(lp["ln1"], x, cfg.norm_eps), positions,
            cache=cache, n_gen=n_gen, kv_block=self.kv_block, window=0)
        x = x + h
        x = x + L.cross_attention_apply(
            cfg, lp["xattn"], L.rmsnorm(lp["ln_x"], x, cfg.norm_eps),
            kv=memory_kv, gated=False)
        x = x + _gelu_mlp(lp["mlp"], L.rmsnorm(lp["ln2"], x, cfg.norm_eps))
        return x, new_cache

    def _decoder(self, params: Params, tokens, pos, *, memory=None,
                 cache=None, n_gen=None):
        """Final decoder hidden states and the new cache.  With ``cache``
        one token is decoded against it; otherwise the tokens attend to
        ``memory``, and with ``n_gen`` a prefill emits a cache with room
        for ``n_gen`` decoded tokens; with neither, training."""
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], tokens)

        def body(xc, lc):
            lp, sc, ckv = lc
            if ckv is None:
                kv = L.cross_attention_kv(cfg, lp["xattn"], memory)
            else:
                kv = (ckv["k"], ckv["v"])
            xc, nc = self._dec_layer(lp, xc, pos, kv, cache=sc, n_gen=n_gen)
            return xc, (nc, None if n_gen is None else kv)

        remat = self._maybe_remat if cache is None else (lambda fn: fn)
        self_cache, cross = (None, None) if cache is None else \
            (cache["self"], cache["cross"])
        x, (self_cache, kvs) = lax.scan(
            remat(body), x, (params["dec_layers"], self_cache, cross))
        if kvs is not None:
            cross = {"k": kvs[0], "v": kvs[1]}
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return x, {"self": self_cache, "cross": cross}

    def loss_fn(self, params: Params, batch: dict) -> jnp.ndarray:
        tokens, labels = batch["tokens"], batch["labels"]
        memory = self.encode(params, batch["frames"])
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x, _ = self._decoder(params, tokens, pos, memory=memory)
        return L.chunked_lm_loss(self.cfg, params["embed"], x, labels,
                                 self.seq_chunk)

    def prefill(self, params: Params, batch: dict,
                n_gen: int = DEFAULT_NEW_TOKENS):
        tokens = batch["tokens"]
        memory = self.encode(params, batch["frames"])
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x, cache = self._decoder(params, tokens, pos, memory=memory,
                                n_gen=n_gen)
        logits = L.unembed(self.cfg, params["embed"], x[:, -1:, :])
        return logits, cache

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
        x, cache = self._decoder(params, tokens, pos, cache=cache)
        return L.unembed(self.cfg, params["embed"], x), cache
