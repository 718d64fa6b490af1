"""Plain float32 reference of the served Whisper-style encoder-decoder.

It implements the configuration's documented departures from the published
Whisper and nothing else of the program:
  * RMSNorm (scale only) in place of LayerNorm with bias;
  * rotary position embedding on the self-attention queries and keys of the
    encoder and of the decoder, in place of sinusoidal / learned positions;
  * no conv frontend: the encoder consumes frame embeddings [B, frames, d];
  * GELU in its tanh form; no biases anywhere; untied output projection;
  * attention output projections initialised with fan-in = head count.

Whole sequences, no cache: ``logits(params, frames, tokens)`` is the
teacher-forced forward pass that prefill plus cached decode must agree with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import (F32, Numerics, attention, dense_init,
                                    gelu_tanh, rmsnorm, rope)


def _attn_params(m, key, dtype):
    d, h, kv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    k = list(jax.random.split(key, 4))
    return {"wq": dense_init(k[0], (d, h, dh), dtype),
            "wk": dense_init(k[1], (d, kv, dh), dtype),
            "wv": dense_init(k[2], (d, kv, dh), dtype),
            "wo": dense_init(k[3], (h, dh, d), dtype)}


def _mlp_params(m, key, dtype):
    k = list(jax.random.split(key, 2))
    return {"wi": dense_init(k[0], (m["d_model"], m["d_ff"]), dtype),
            "wo": dense_init(k[1], (m["d_ff"], m["d_model"]), dtype)}


def init_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """The served weights, drawn from ``seed`` (the same draws, in the same
    order, as the served model's initialisation)."""
    d = m["d_model"]
    ones = jnp.ones((d,), dtype)
    r_e, r_enc, r_dec = jax.random.split(jax.random.key(seed), 3)
    e = list(jax.random.split(r_e, 2))

    def enc_layer(key):
        k = list(jax.random.split(key, 2))
        return {"ln1": {"scale": ones}, "attn": _attn_params(m, k[0], dtype),
                "ln2": {"scale": ones}, "mlp": _mlp_params(m, k[1], dtype)}

    def dec_layer(key):
        k = list(jax.random.split(key, 3))
        return {"ln1": {"scale": ones}, "attn": _attn_params(m, k[0], dtype),
                "ln_x": {"scale": ones},
                "xattn": {**_attn_params(m, k[1], dtype),
                          "gate": jnp.zeros((), dtype)},
                "ln2": {"scale": ones}, "mlp": _mlp_params(m, k[2], dtype)}

    return {
        "embed": {"embed": dense_init(e[0], (m["vocab_size"], d), dtype),
                  "unembed": dense_init(e[1], (d, m["vocab_size"]), dtype)},
        "enc_layers": jax.vmap(enc_layer)(
            jax.random.split(r_enc, m["n_encoder_layers"])),
        "dec_layers": jax.vmap(dec_layer)(
            jax.random.split(r_dec, m["n_layers"])),
        "ln_enc": {"scale": ones},
        "ln_f": {"scale": ones},
    }


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _self_attention(num, m, p, x, causal):
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = rope(num.mm("bsd,dhe->bshe", x, p["wq"]), pos, m["rope_theta"])
    k = rope(num.mm("bsd,dhe->bshe", x, p["wk"]), pos, m["rope_theta"])
    v = num.mm("bsd,dhe->bshe", x, p["wv"])
    return num.mm("bshe,hed->bsd", attention(num, q, k, v, causal), p["wo"])


def _mlp(num, p, x):
    return num.mm("bsf,fd->bsd", gelu_tanh(num.mm("bsd,df->bsf", x, p["wi"])),
                  p["wo"])


def encode(num: Numerics, m, params, frames):
    eps = m["norm_eps"]
    x = frames.astype(F32)
    for i in range(m["n_encoder_layers"]):
        p = _layer(params["enc_layers"], i)
        x = x + _self_attention(num, m, p["attn"],
                                rmsnorm(p["ln1"]["scale"], x, eps), False)
        x = x + _mlp(num, p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps))
    return rmsnorm(params["ln_enc"]["scale"], x, eps)


def logits(params, m: dict, tokens, frames, num: Numerics = None):
    """Teacher-forced logits [B, S, V] of the decoder over ``tokens``."""
    num = num or Numerics("f32")
    eps = m["norm_eps"]
    memory = encode(num, m, params, frames)
    x = params["embed"]["embed"].astype(F32)[tokens]
    for i in range(m["n_layers"]):
        p = _layer(params["dec_layers"], i)
        x = x + _self_attention(num, m, p["attn"],
                                rmsnorm(p["ln1"]["scale"], x, eps), True)
        xa = p["xattn"]
        q = num.mm("bsd,dhe->bshe", rmsnorm(p["ln_x"]["scale"], x, eps),
                   xa["wq"])
        k = num.mm("bmd,dhe->bmhe", memory, xa["wk"])
        v = num.mm("bmd,dhe->bmhe", memory, xa["wv"])
        x = x + num.mm("bshe,hed->bsd", attention(num, q, k, v, False),
                       xa["wo"])
        x = x + _mlp(num, p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps))
    x = rmsnorm(params["ln_f"]["scale"], x, eps)
    return num.mm("bsd,dv->bsv", x, params["embed"]["unembed"])


def decode_bytes(m: dict, batch: int, prompt_len: int, n_gen: int) -> float:
    """HBM bytes one decode call must move, averaged over the ``n_gen``
    calls of a request: the decoder weights it reads (bf16; one embedding
    row per sequence, the cross-attention keys and values come from the
    cache), the cross-attention cache (bf16), the self-attention keys and
    values written so far (bf16, mean over the calls), the one new key and
    value it writes, and the bf16 logits it writes."""
    d, h, kv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    layers, v_size, f = m["n_layers"], m["vocab_size"], m["d_ff"]
    bf16 = 2
    per_layer = (2 * d * h * dh + 2 * d * kv * dh      # self q,o + k,v
                 + 2 * d * h * dh                      # cross q,o
                 + 2 * d * f + 3 * d)                  # mlp, three norms
    weights = layers * per_layer + d * v_size + d + batch * d
    cross = layers * batch * m["n_frames"] * kv * dh * 2
    mean_ctx = prompt_len + (n_gen - 1) / 2
    self_kv = layers * batch * (mean_ctx + 1) * kv * dh * 2
    out = batch * v_size
    return float(bf16 * (weights + cross + self_kv + out))
