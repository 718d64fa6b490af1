"""Plain float32 reference of the served Zamba2 (Zyphra/Zamba2-7B-Instruct).

It follows ``transformers``' ``modeling_zamba2`` term by term
(``Zamba2MambaMixer.torch_forward``, ``Zamba2RMSNormGated``,
``Zamba2Attention``, ``Zamba2MLP``, ``Zamba2AttentionDecoderLayer``,
``Zamba2HybridLayer``) and nothing of the program:
  * every layer is ``x + mamba(rmsnorm(x + t))``, where ``t`` is zero for
    a plain Mamba2 layer and, for the a-th hybrid layer, ``linear_a`` of
    shared block ``a % n_shared_blocks`` applied to
    ``concat(x, embedding)``: RMSNorm, rotary attention with softmax scale
    ``(head_dim / 2) ** -0.5``, RMSNorm, GELU-gated MLP whose ``gate_up``
    adds the application's own rank-``adapter_rank`` LoRA; no residual
    inside the block;
  * the Mamba2 mixer: projections to z, x, B, C (``ssm_groups`` groups,
    head h reading group h // (heads / groups)) and dt; a depthwise causal
    conv with bias over concat(x, B, C), then SiLU; dt = softplus(dt +
    dt_bias) floored at ``ssm_dt_min``; the recurrence
    ``h_t = exp(dt A) h_{t-1} + dt x_t B_t^T``, ``y_t = C_t h_t + D x_t``
    run step by step as defined (no chunks); then gate, RMSNorm per group,
    output projection;
  * tied input and output embeddings, final RMSNorm.
Remaining departures from the published model: none in the equations.
The random initial values (the served draws, ``init_params``) stand in
for the published weights.

Whole sequences, no cache: ``logits(params, m, tokens)`` is the
teacher-forced forward pass that prefill plus cached decode must agree with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, Numerics, dense_init, rmsnorm, rope

HEADDIM = 64                 # mamba_headdim


def _mamba_dims(m):
    d_inner = m["expand"] * m["d_model"]
    p = min(HEADDIM, d_inner)
    return d_inner, d_inner // p, p, m["ssm_state"], m["ssm_groups"]


def hybrid_layers(m) -> list:
    return [i for i, c in enumerate(m["block_pattern"]) if c == "h"]


def _runs(m):
    edges = [-1] + hybrid_layers(m) + [m["n_layers"]]
    return [(lo + 1, hi) for lo, hi in zip(edges, edges[1:])]


def init_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """The served weights, drawn from ``seed`` (the same draws, in the same
    order, as the served model's initialisation)."""
    d, f, r = m["d_model"], m["d_ff"], m["adapter_rank"]
    d_inner, h, _, n, g = _mamba_dims(m)
    conv = d_inner + 2 * g * n
    d_in, dh = m["attn_in"], m["head_dim"]

    def mamba(key):
        k = list(jax.random.split(key, 9))
        bound = m["conv_kernel"] ** -0.5
        dt = jnp.exp(jax.random.uniform(k[7], (h,), F32)
                     * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        dt = jnp.maximum(dt, 1e-4)
        return {"ln": {"scale": jnp.ones((d,), dtype)},
                "in_z": dense_init(k[0], (d, d_inner), dtype),
                "in_x": dense_init(k[1], (d, d_inner), dtype),
                "in_b": dense_init(k[2], (d, g * n), dtype),
                "in_c": dense_init(k[3], (d, g * n), dtype),
                "in_dt": dense_init(k[4], (d, h), dtype),
                "conv_w": dense_init(k[5], (m["conv_kernel"], conv), dtype,
                                     2.0),
                "conv_b": jax.random.uniform(k[6], (conv,), F32, -bound,
                                             bound).astype(dtype),
                "a_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
                "d_skip": jnp.ones((h,), F32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "out_norm": {"scale": jnp.ones((d_inner,), dtype)},
                "out_proj": dense_init(k[8], (d_inner, d), dtype)}

    def block(key):
        k = list(jax.random.split(key, 3))
        a = list(jax.random.split(k[0], 4))
        return {"ln": {"scale": jnp.ones((d_in,), dtype)},
                "attn": {"wq": dense_init(a[0], (d_in, m["n_heads"], dh),
                                          dtype),
                         "wk": dense_init(a[1], (d_in, m["n_kv_heads"], dh),
                                          dtype),
                         "wv": dense_init(a[2], (d_in, m["n_kv_heads"], dh),
                                          dtype),
                         "wo": dense_init(a[3], (m["n_heads"], dh, d),
                                          dtype)},
                "ffn_ln": {"scale": jnp.ones((d,), dtype)},
                "gate_up": dense_init(k[1], (d, 2 * f), dtype),
                "down_proj": dense_init(k[2], (f, d), dtype)}

    def app(key, layer_key):
        k = list(jax.random.split(key, 3))
        return {"mamba": mamba(layer_key),
                "lora_a": dense_init(k[0], (d, r), dtype),
                "lora_b": dense_init(k[1], (r, 2 * f), dtype),
                "linear": dense_init(k[2], (d, d), dtype)}

    r_e, r_l, r_b, r_a = jax.random.split(jax.random.key(seed), 4)
    layer = jax.random.split(r_l, m["n_layers"])
    hybrid = hybrid_layers(m)
    return {
        "embed": {"embed": dense_init(jax.random.split(r_e, 2)[0],
                                      (m["vocab_size"], d), dtype)},
        "runs": tuple(jax.vmap(mamba)(layer[lo:hi]) for lo, hi in _runs(m)),
        "apps": tuple(app(k, layer[i]) for k, i in zip(
            jax.random.split(r_a, len(hybrid)), hybrid)),
        "blocks": tuple(block(k) for k in jax.random.split(
            r_b, m["n_shared_blocks"])),
        "ln_f": {"scale": jnp.ones((d,), dtype)},
    }


def _mixer(num: Numerics, m, p, xn):
    """Zamba2MambaMixer over a whole sequence, its recurrence step by step.
    xn: [B, S, d] (normed) -> [B, S, d]."""
    b, s, _ = xn.shape
    d_inner, h, hp, n, g = _mamba_dims(m)
    k = m["conv_kernel"]
    z = num.mm("bsd,de->bse", xn, p["in_z"])
    xbc = jnp.concatenate([num.mm("bsd,de->bse", xn, p[w])
                           for w in ("in_x", "in_b", "in_c")], -1)
    dt_raw = num.mm("bsd,dh->bsh", xn, p["in_dt"])
    # depthwise causal conv: tap j reads the input j - (k - 1) steps back
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(xp[:, j:j + s] * w[j] for j in range(k))
                      + p["conv_b"].astype(F32))
    xs = xbc[..., :d_inner].reshape(b, s, h, hp)
    heads = h // g
    bm = jnp.repeat(xbc[..., d_inner:d_inner + g * n].reshape(b, s, g, n),
                    heads, axis=2)                           # [B,S,H,N]
    cm = jnp.repeat(xbc[..., d_inner + g * n:].reshape(b, s, g, n),
                    heads, axis=2)
    dt = jnp.maximum(jax.nn.softplus(dt_raw + p["dt_bias"]), m["ssm_dt_min"])
    a = -jnp.exp(p["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp                # [B,H,P], [B,H,N], .., [B,H]
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, num.mm("bhn,bhpn->bhp", c_t, state)

    h0 = jnp.zeros((b, h, hp, n), F32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(t, 0, 1)
                                        for t in (xs, bm, cm, dt)))
    y = jnp.swapaxes(y, 0, 1) + xs * p["d_skip"][:, None]   # [B,S,H,P]
    # Zamba2RMSNormGated: gate first, then RMSNorm over each group
    y = y.reshape(b, s, g, d_inner // g) \
        * jax.nn.silu(z).reshape(b, s, g, d_inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + m["norm_eps"])
    y = y.reshape(b, s, d_inner) * p["out_norm"]["scale"].astype(F32)
    return num.mm("bse,ed->bsd", y, p["out_proj"])


def _attention(num: Numerics, m, p, x):
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = rope(num.mm("bsd,dhe->bshe", x, p["wq"]), pos, m["rope_theta"])
    k = rope(num.mm("bsd,dhe->bshe", x, p["wk"]), pos, m["rope_theta"])
    v = num.mm("bsd,dhe->bshe", x, p["wv"])
    k, v = (jnp.repeat(t, m["n_heads"] // m["n_kv_heads"], axis=2)
            for t in (k, v))
    scores = num.mm("bqhd,bkhd->bhqk", q, k) * m["attn_scale"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = num.mm("bhqk,bkhd->bqhd", probs, v)
    return num.mm("bshe,hed->bsd", out, p["wo"])


def _shared(num: Numerics, m, blk, app, x, emb):
    """Zamba2AttentionDecoderLayer then the application's ``linear``."""
    eps = m["norm_eps"]
    hs = rmsnorm(blk["ln"]["scale"], jnp.concatenate([x, emb], -1), eps)
    hs = rmsnorm(blk["ffn_ln"]["scale"], _attention(num, m, blk["attn"], hs),
                 eps)
    gate_up = num.mm("bsd,df->bsf", hs, blk["gate_up"]) + num.mm(
        "bsr,rf->bsf", num.mm("bsd,dr->bsr", hs, app["lora_a"]),
        app["lora_b"])
    gate, up = jnp.split(gate_up, 2, -1)
    y = num.mm("bsf,fd->bsd", jax.nn.gelu(gate, approximate=False) * up,
               blk["down_proj"])
    return num.mm("bsd,de->bse", y, app["linear"])


def logits(params, m: dict, tokens, frames=None, num: Numerics = None):
    """Teacher-forced logits [B, S, V] over ``tokens``."""
    num = num or Numerics("f32")
    eps = m["norm_eps"]
    emb = params["embed"]["embed"].astype(F32)[tokens]
    # the Mamba2 weights of each layer, in layer order
    mixers = []
    for a, (lo, hi) in enumerate(_runs(m)):
        if a:
            mixers.append(params["apps"][a - 1]["mamba"])
        mixers += [jax.tree.map(lambda t, i=i: t[i], params["runs"][a])
                   for i in range(hi - lo)]
    x, a = emb, 0
    for i, c in enumerate(m["block_pattern"]):
        t = 0.0
        if c == "h":
            t = _shared(num, m, params["blocks"][a % m["n_shared_blocks"]],
                        params["apps"][a], x, emb)
            a += 1
        p = mixers[i]
        x = x + _mixer(num, m, p, rmsnorm(p["ln"]["scale"], x + t, eps))
    x = rmsnorm(params["ln_f"]["scale"], x, eps)
    return num.mm("bsd,vd->bsv", x, params["embed"]["embed"])


def _mamba_weights(m) -> int:
    d = m["d_model"]
    d_inner, h, _, n, g = _mamba_dims(m)
    conv = d_inner + 2 * g * n
    return d * (2 * d_inner + 2 * g * n + h) + (m["conv_kernel"] + 1) * conv \
        + d_inner * d + d + d_inner


def _block_weights(m) -> int:
    d, f = m["d_model"], m["d_ff"]
    qkv = m["attn_in"] * (m["n_heads"] + 2 * m["n_kv_heads"]) * m["head_dim"]
    return qkv + m["n_heads"] * m["head_dim"] * d + 3 * d * f \
        + m["attn_in"] + d


def decode_bytes(m: dict, batch: int, prompt_len: int, n_gen: int) -> float:
    """HBM bytes one decode call must move, averaged over the ``n_gen``
    calls of a request: every bf16 weight it reads, a shared block once for
    each of its applications (the tied embedding whole, for the logits, and
    one row per sequence); the f32 recurrent state of every Mamba2 layer
    read and written, and its bf16 conv state; each application's keys and
    values written so far (bf16, mean over the calls) and the one new key
    and value it writes; and the bf16 logits it writes."""
    d, v_size = m["d_model"], m["vocab_size"]
    d_inner, h, hp, n, g = _mamba_dims(m)
    apps = len(hybrid_layers(m))
    kv = m["n_kv_heads"] * m["head_dim"]
    weights = m["n_layers"] * _mamba_weights(m) + apps * (
        _block_weights(m) + d * m["adapter_rank"]
        + m["adapter_rank"] * 2 * m["d_ff"] + d * d) + d * v_size + d \
        + batch * d
    conv = (m["conv_kernel"] - 1) * (d_inner + 2 * g * n)
    ssm = m["n_layers"] * batch * (4 * 2 * h * hp * n + 2 * 2 * conv)
    mean_ctx = prompt_len + (n_gen - 1) / 2
    cache = apps * batch * (mean_ctx + 1) * 2 * kv
    return float(2 * weights + ssm + 2 * cache + 2 * batch * v_size)


def prefill_flops(m: dict, batch: int, prompt_len: int) -> float:
    """FLOPs of one prefill over ``batch`` prompts of ``prompt_len``: the
    matrix products of every layer and application per token, causal
    attention (the triangle, keys up to the query), the recurrence's
    update and read-out (2 FLOPs per state element each), and the logits
    of the last position."""
    d, s = m["d_model"], prompt_len
    d_inner, h, hp, n, g = _mamba_dims(m)
    apps = len(hybrid_layers(m))
    r, f = m["adapter_rank"], m["d_ff"]
    mamba = 2 * (d * (2 * d_inner + 2 * g * n + h) + d_inner * d) \
        + 2 * m["conv_kernel"] * (d_inner + 2 * g * n) + 4 * h * hp * n
    qkv = m["attn_in"] * (m["n_heads"] + 2 * m["n_kv_heads"]) * m["head_dim"]
    app = 2 * (qkv + m["n_heads"] * m["head_dim"] * d + 3 * d * f
               + r * (d + 2 * f) + d * d)
    attn = 2 * 2 * m["n_heads"] * m["head_dim"] * (s + 1) / 2
    per_token = m["n_layers"] * mamba + apps * (app + attn)
    return float(batch * (s * per_token + 2 * d * m["vocab_size"]))
