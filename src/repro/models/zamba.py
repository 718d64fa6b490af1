"""Zamba2 hybrid: a Mamba2 backbone with shared attention blocks applied in
turn, as ``transformers``' ``modeling_zamba2`` defines it.

``cfg.block_pattern`` has one character a layer: ``m`` a Mamba2 layer,
``h`` a Mamba2 layer that a shared block feeds.  The a-th application uses
block ``a % n_shared_blocks`` and its own rank-``adapter_rank`` LoRA on the
block's MLP and its own ``linear``.  At an application:

    t = linear_a(block(concat(x, embedding)))     # no residual inside
    x = x + mamba2(rmsnorm(x + t))                # t enters the norm only

The block is RMSNorm over the concatenation (width ``attn_in``), rotary
attention with softmax scale ``attn_scale`` and an output projection back to
d_model, RMSNorm, and a GELU-gated MLP whose ``gate_up`` takes the LoRA.
Each application keeps its own KV cache at serve time; every Mamba2 layer
its own recurrent state.

Parameters: ``runs`` are the stacked Mamba2 layers between applications
(``runs[0]`` before the first, ``runs[a + 1]`` after the a-th; a run may be
empty), scanned; ``apps[a]`` the a-th application's Mamba2 layer, LoRA and
``linear``; ``blocks`` the shared blocks.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import DEFAULT_NEW_TOKENS, ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M2

F32 = jnp.float32
Params = Any


def runs_of(cfg: ModelConfig):
    """(start, stop) of the runs of plain Mamba2 layers around the
    applications: len(hybrid_layers) + 1 runs, any of them possibly empty."""
    edges = (-1,) + cfg.hybrid_layers + (cfg.n_layers,)
    return [(lo + 1, hi) for lo, hi in zip(edges, edges[1:])]


class Zamba:
    def __init__(self, cfg: ModelConfig, *, remat: str = "full",
                 kv_block: int = 512, seq_chunk: int = 2048):
        assert cfg.family == "hybrid" and cfg.n_shared_blocks
        self.cfg = cfg
        self.remat = remat
        self.kv_block = kv_block
        self.seq_chunk = seq_chunk
        self.dtype = jnp.dtype(cfg.dtype)
        self.runs = runs_of(cfg)
        self.n_apps = len(cfg.hybrid_layers)

    def _maybe_remat(self, fn):
        return fn if self.remat == "none" else jax.checkpoint(fn)

    # -- parameters ------------------------------------------------------------

    def _block_params(self, rng):
        cfg, dtype = self.cfg, self.dtype
        r = L.split_rngs(rng, 3)
        return {
            "ln": L.rmsnorm_params(cfg.attn_in, dtype),
            "attn": L.attention_params(cfg, r[0], dtype),
            "ffn_ln": L.rmsnorm_params(cfg.d_model, dtype),
            "gate_up": L._dense_init(r[1], (cfg.d_model, 2 * cfg.d_ff), dtype),
            "down_proj": L._dense_init(r[2], (cfg.d_ff, cfg.d_model), dtype),
        }

    def _app_params(self, rng, layer_rng):
        cfg, dtype = self.cfg, self.dtype
        r = L.split_rngs(rng, 3)
        return {
            "mamba": M2.mamba2_params(cfg, layer_rng, dtype),
            "lora_a": L._dense_init(r[0], (cfg.d_model, cfg.adapter_rank),
                                    dtype),
            "lora_b": L._dense_init(r[1], (cfg.adapter_rank, 2 * cfg.d_ff),
                                    dtype),
            "linear": L._dense_init(r[2], (cfg.d_model, cfg.d_model), dtype),
        }

    def init(self, rng) -> Params:
        cfg, dtype = self.cfg, self.dtype
        r_e, r_l, r_b, r_a = jax.random.split(rng, 4)
        layer = jax.random.split(r_l, cfg.n_layers)
        return {
            "embed": L.embed_params(cfg, r_e, dtype),
            "runs": tuple(
                jax.vmap(lambda r: M2.mamba2_params(cfg, r, dtype))(
                    layer[lo:hi]) for lo, hi in self.runs),
            "apps": tuple(
                self._app_params(r, layer[i]) for r, i in zip(
                    jax.random.split(r_a, self.n_apps), cfg.hybrid_layers)),
            "blocks": tuple(self._block_params(r) for r in jax.random.split(
                r_b, cfg.n_shared_blocks)),
            "ln_f": L.rmsnorm_params(cfg.d_model, dtype),
        }

    def init_abstract(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    # -- layers ----------------------------------------------------------------

    def _run(self, stack, x, states=None, emit=False):
        """The Mamba2 layers of one run: decode against ``states``, or the
        whole sequence, emitting the states with ``emit``."""
        cfg = self.cfg
        with jax.named_scope("zamba2.mamba"):
            if states is not None:
                def step(xi, inp):
                    return M2.mamba2_decode(cfg, inp[0], xi, inp[1])
                return lax.scan(step, x, (stack, states))

            def body(xi, p):
                if emit:
                    return M2.mamba2_apply(cfg, p, xi, return_state=True)
                return M2.mamba2_apply(cfg, p, xi), None
            return lax.scan(self._maybe_remat(body), x, stack)

    def _shared(self, params, a, x, emb, pos, kv=None, n_gen=None):
        """The a-th application's contribution ``t`` (and its KV cache)."""
        cfg = self.cfg
        blk = params["blocks"][a % cfg.n_shared_blocks]
        app = params["apps"][a]
        with jax.named_scope("zamba2.shared_block"):
            h = L.rmsnorm(blk["ln"], jnp.concatenate([x, emb], axis=-1),
                          cfg.norm_eps)
            h, kv = L.attention_apply(cfg, blk["attn"], h, pos, cache=kv,
                                      n_gen=n_gen, kv_block=self.kv_block)
            h = L.rmsnorm(blk["ffn_ln"], h, cfg.norm_eps)
            gate_up = jnp.einsum("bsd,df->bsf", h, blk["gate_up"])
        with jax.named_scope("zamba2.adapter"):
            gate_up = gate_up + jnp.einsum(
                "bsr,rf->bsf", jnp.einsum("bsd,dr->bsr", h, app["lora_a"]),
                app["lora_b"])
        with jax.named_scope("zamba2.shared_block"):
            gate, up = jnp.split(gate_up, 2, axis=-1)
            y = jax.nn.gelu(gate.astype(F32), approximate=False) \
                .astype(up.dtype) * up
            y = jnp.einsum("bsf,fd->bsd", y, blk["down_proj"])
        with jax.named_scope("zamba2.linear"):
            t = jnp.einsum("bsd,de->bse", y, app["linear"])
        return t, kv

    def _hybrid(self, params, a, x, t, state=None, emit=False):
        """The a-th application's Mamba2 layer, fed ``t``."""
        cfg = self.cfg
        p = params["apps"][a]["mamba"]
        with jax.named_scope("zamba2.mamba"):
            if state is not None:
                return M2.mamba2_decode(cfg, p, x, state, extra=t)
            if emit:
                return M2.mamba2_apply(cfg, p, x, extra=t, return_state=True)
            return M2.mamba2_apply(cfg, p, x, extra=t), None

    def _forward(self, params, tokens, pos, cache=None, n_gen=None):
        """Final hidden states and the new decode state.  With ``cache``
        one token is decoded; with ``n_gen`` a prefill emits the state with
        KV caches of prompt + ``n_gen`` slots; with neither, training."""
        cfg = self.cfg
        emb = L.embed_lookup(params["embed"], tokens)
        emit = cache is not None or n_gen is not None

        def app(a, x, kv, state):
            t, kv = self._shared(params, a, x, emb, pos, kv, n_gen)
            x, state = self._hybrid(params, a, x, t, state, emit)
            return x, (kv, state)

        if cache is None and self.remat != "none":
            app = jax.checkpoint(app, static_argnums=(0,))
        x = emb
        kvs, hybrid, runs = [], [], []
        for a, stack in enumerate(params["runs"]):
            if a:
                x, (kv, state) = app(
                    a - 1, x, None if cache is None else cache["kv"][a - 1],
                    None if cache is None else cache["hybrid"][a - 1])
                kvs.append(kv)
                hybrid.append(state)
            x, states = self._run(
                stack, x, None if cache is None else cache["runs"][a], emit)
            runs.append(states)
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        new = {"kv": tuple(kvs), "hybrid": tuple(hybrid), "runs": tuple(runs)}
        return x, (new if emit else None)

    # -- train -----------------------------------------------------------------

    def loss_fn(self, params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x, _ = self._forward(params, tokens, pos)
        return L.chunked_lm_loss(self.cfg, params["embed"], x, labels,
                                 self.seq_chunk)

    # -- serve -----------------------------------------------------------------

    def init_cache(self, batch: int, seq_len: int) -> dict:
        cfg = self.cfg
        state = M2.empty_state(cfg, batch, self.dtype)

        def stacked(n):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), state)
        return {
            "kv": tuple(L.empty_cache(cfg, batch, seq_len, self.dtype)
                        for _ in range(self.n_apps)),
            "hybrid": tuple(M2.empty_state(cfg, batch, self.dtype)
                            for _ in range(self.n_apps)),
            "runs": tuple(stacked(hi - lo) for lo, hi in self.runs),
        }

    def prefill(self, params, batch, n_gen: int = DEFAULT_NEW_TOKENS):
        tokens = batch["tokens"]
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x, cache = self._forward(params, tokens, pos, n_gen=n_gen)
        return L.unembed(self.cfg, params["embed"], x[:, -1:, :]), cache

    def decode_step(self, params, cache, tokens, pos):
        x, cache = self._forward(params, tokens, pos, cache=cache)
        return L.unembed(self.cfg, params["embed"], x), cache
