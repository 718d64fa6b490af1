"""Step functions (train / prefill / decode) shared by the dry-run, the
trainer and the server. Pure functions of explicit state — no globals — so
they lower identically on every mesh.

``make_prefill_step`` / ``make_decode_step`` return logits; the server
wraps them with ``greedy_prefill`` / ``greedy_decode`` so that greedy
sampling runs inside the same jitted program and a served token costs one
dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import DEFAULT_NEW_TOKENS, ModelConfig, RunConfig
from repro.models import api as model_api
from repro.optim import adamw


def make_model(run: RunConfig):
    return model_api.build_model(
        run.model, remat=run.remat, kv_block=run.kv_block,
        seq_chunk=run.seq_chunk)


def make_opt_cfg(run: RunConfig) -> adamw.AdamWConfig:
    return adamw.AdamWConfig(lr=run.learning_rate,
                             weight_decay=run.weight_decay,
                             beta1=run.beta1, beta2=run.beta2)


def make_train_step(run: RunConfig):
    model = make_model(run)
    opt_cfg = make_opt_cfg(run)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        new_params, new_opt = adamw.update(opt_cfg, grads, opt_state, params)
        return new_params, new_opt, loss

    return train_step, model


def make_prefill_step(run: RunConfig):
    model = make_model(run)

    def prefill_step(params, batch, n_gen=DEFAULT_NEW_TOKENS):
        return model.prefill(params, batch, n_gen)

    return prefill_step, model


def make_decode_step(run: RunConfig):
    model = make_model(run)

    def decode_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return decode_step, model


def greedy_token(logits):
    """Greedy choice at the last position: [B, S, V] logits -> [B, 1] int32.

    The barrier keeps the output projection out of the argmax's fusion.
    Fused, the TPU compiler reads the projection's float32 accumulators
    rather than the bf16 logits the model returns, so near ties the
    tokens part from those of the logits step."""
    logits = jax.lax.optimization_barrier(logits)
    return jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)


def greedy_prefill(prefill):
    """``prefill(params, batch, n_gen) -> (logits, cache)`` as a step that
    returns ``(tok, pos, cache)``: the first token and the next position
    (the prompt's length); the cache has room for ``n_gen`` decoded tokens.
    Named ``prefill_step`` so the jitted program keeps its name
    (``jit_prefill_step``)."""
    def prefill_step(params, batch, n_gen=DEFAULT_NEW_TOKENS):
        logits, cache = prefill(params, batch, n_gen)
        tok = greedy_token(logits)
        pos = jnp.full((tok.shape[0], 1), batch["tokens"].shape[1],
                       jnp.int32)
        return tok, pos, cache

    return prefill_step


def greedy_decode(decode):
    """``decode(params, cache, tokens, pos) -> (logits, cache)`` as a step
    that returns ``(tok, pos + 1, cache)``.  Named ``decode_step`` so the
    jitted program keeps its name (``jit_decode_step``)."""
    def decode_step(params, cache, tokens, pos):
        logits, cache = decode(params, cache, tokens, pos)
        return greedy_token(logits), pos + 1, cache

    return decode_step
