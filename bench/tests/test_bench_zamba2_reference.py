"""The Zamba2 reference and the served Zamba2, at the reduced size on the
CPU (six layers, pattern ``mhmhmh``: shared blocks 0, 1, 0, so a block is
applied at two depths, each application with its own LoRA and linear):

  * the reference draws the served weights bit for bit;
  * the reference agrees with ``transformers``' ``Zamba2ForCausalLM`` with
    the same weights;
  * the served prefill, then 80 decode steps through the KV caches (past
    the 64 slots a cache used to have) and the Mamba2 states, agree with
    the reference's forward pass over the same tokens;
  * the served path with its weights down-cast from bfloat16 to float8
    misses that tolerance."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.reference import zamba2 as ref  # noqa: E402
from bench.reference.common import fp8_round  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.models import api as model_api  # noqa: E402

SEED = 2**31 + 17
B, P, N = 2, 20, 80


@pytest.fixture(scope="module")
def case():
    cfg = get_arch("zamba2-7b").reduced()
    m = {**dataclasses.asdict(cfg), "head_dim": cfg.resolved_head_dim}
    model = model_api.build_model(cfg, remat="none", kv_block=8, seq_chunk=16)
    params = model.init(jax.random.key(SEED))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, P + N)).astype(np.int32)
    ref_params = ref.init_params(m, SEED)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), ref_params)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, t: ref.logits(p, m, t))(
            f32, jnp.asarray(tokens)))
    return cfg, m, model, params, ref_params, f32, tokens, want


def test_reduced_config_reuses_a_shared_block(case):
    cfg = case[0]
    assert cfg.hybrid_layers == (1, 3, 5) and cfg.n_shared_blocks == 2
    assert cfg.attn_in == 2 * cfg.d_model and cfg.ssm_groups == 2


def test_reference_draws_the_served_weights(case):
    _, _, _, params, ref_params, *_ = case
    got = jax.tree_util.tree_flatten_with_path(ref_params)
    want = jax.tree_util.tree_flatten_with_path(params)
    assert got[1] == want[1]
    for (path, a), (_, b) in zip(got[0], want[0]):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


def _hf_model(cfg, m, f32):
    """``Zamba2ForCausalLM`` of the same sizes, float32, with the weights
    of ``f32`` mapped onto its modules.  Its chunk is the published 256,
    longer than the sequence: ``torch_forward``'s naive chunked scan sums
    the carried states over the wrong chunk axis when a sequence spans
    several chunks, and the published model runs its CUDA kernel there."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hc = transformers.Zamba2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        num_hidden_layers=cfg.n_layers,
        layers_block_type=["hybrid" if c == "h" else "mamba"
                           for c in cfg.block_pattern],
        mamba_d_state=cfg.ssm_state, mamba_d_conv=cfg.conv_kernel,
        mamba_expand=cfg.expand, mamba_ngroups=cfg.ssm_groups,
        n_mamba_heads=cfg.expand * cfg.d_model // ref.HEADDIM,
        chunk_size=256, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        num_mem_blocks=cfg.n_shared_blocks, use_mem_rope=True,
        rope_theta=cfg.rope_theta, adapter_rank=cfg.adapter_rank,
        intermediate_size=cfg.d_ff, rms_norm_eps=cfg.norm_eps,
        time_step_min=cfg.ssm_dt_min, hidden_act="gelu",
        tie_word_embeddings=True, attn_implementation="eager")
    hf = transformers.Zamba2ForCausalLM(hc).eval().float()

    def put(param, value):
        param.data.copy_(torch.tensor(np.asarray(value, np.float32)))

    def t(a):                                 # [in, out] -> Linear weight
        a = np.asarray(a)
        return a.reshape(a.shape[0], -1).T

    model = hf.model
    put(model.embed_tokens.weight, f32["embed"]["embed"])
    put(model.final_layernorm.weight, f32["ln_f"]["scale"])
    mixers = []
    for a, (lo, hi) in enumerate(ref._runs(m)):
        if a:
            mixers.append(f32["apps"][a - 1]["mamba"])
        mixers += [jax.tree.map(lambda x, i=i: x[i], f32["runs"][a])
                   for i in range(hi - lo)]
    app = 0
    for i, kind in enumerate(cfg.block_pattern):
        layer = model.layers[i]
        dec = layer.mamba_decoder if kind == "h" else layer
        p, mx = mixers[i], dec.mamba
        put(dec.input_layernorm.weight, p["ln"]["scale"])
        put(mx.in_proj.weight, np.concatenate(
            [p[k] for k in ("in_z", "in_x", "in_b", "in_c", "in_dt")], 1).T)
        put(mx.conv1d.weight, np.asarray(p["conv_w"]).T[:, None, :])
        put(mx.conv1d.bias, p["conv_b"])
        put(mx.dt_bias, p["dt_bias"])
        put(mx.A_log, p["a_log"])
        put(mx.D, p["d_skip"])
        put(mx.norm.weight, p["out_norm"]["scale"])
        put(mx.out_proj.weight, t(p["out_proj"]))
        if kind == "h":
            ap = f32["apps"][app]
            blk = f32["blocks"][app % cfg.n_shared_blocks]
            st = layer.shared_transformer
            put(layer.linear.weight, t(ap["linear"]))
            put(st.input_layernorm.weight, blk["ln"]["scale"])
            put(st.pre_ff_layernorm.weight, blk["ffn_ln"]["scale"])
            for w in ("q", "k", "v"):
                put(getattr(st.self_attn, f"{w}_proj").weight,
                    t(blk["attn"][f"w{w}"]))
            wo = np.asarray(blk["attn"]["wo"])
            put(st.self_attn.o_proj.weight, wo.reshape(-1, wo.shape[-1]).T)
            put(st.feed_forward.gate_up_proj.weight, t(blk["gate_up"]))
            put(st.feed_forward.down_proj.weight, t(blk["down_proj"]))
            lora = st.feed_forward.gate_up_proj_adapter_list[app]
            put(lora[0].weight, t(ap["lora_a"]))
            put(lora[1].weight, t(ap["lora_b"]))
            app += 1
    return hf


def test_reference_matches_transformers(case):
    """Both in float32 over 100 tokens: agreement to float32 rounding."""
    cfg, m, _, _, _, f32, tokens, want = case
    hf = _hf_model(cfg, m, f32)
    import torch
    with torch.no_grad():
        got = hf(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def _served_logits(model, params, tokens):
    """The served prefill of the first P tokens, with KV caches for N more,
    then N decode steps: the logits at positions P - 1 .. P + N - 1."""
    prefill = jax.jit(model.prefill, static_argnums=(2,))
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, {"tokens": jnp.asarray(tokens[:, :P])}, N)
    assert cache["kv"][0]["k"].shape[1] == P + N
    out = [logits[:, -1]]
    for t in range(P, P + N):
        pos = jnp.full((B, 1), t, jnp.int32)
        logits, cache = decode(params, cache, jnp.asarray(tokens[:, t:t + 1]),
                               pos)
        out.append(logits[:, -1])
    return np.stack([np.asarray(x, np.float32) for x in out], 1)


def _worst(got, want):
    """Largest |error| over every position, in units of the logits' spread.
    Over three seeds the bf16 served path reaches 0.20-0.23 here (its
    activations and KV caches in bf16, the Mamba2 state in f32); with its
    weights down-cast to float8 it reaches 1.9-2.1."""
    return float(np.max(np.abs(got - want))) / float(np.std(want))


TOL = 0.5


def test_prefill_then_80_decode_steps_match_reference(case):
    _, _, model, params, _, _, tokens, want = case
    got = _served_logits(model, params, tokens)
    assert _worst(got, want[:, P - 1:]) < TOL


def test_served_path_down_cast_to_fp8_misses_the_tolerance(case):
    """The tolerance above is tight enough that the served path with each
    weight matrix rounded to float8 e4m3 (per-tensor scale) fails it."""
    _, _, model, params, _, _, tokens, want = case
    fp8 = jax.tree.map(
        lambda a: fp8_round(a.astype(jnp.float32)).astype(a.dtype)
        if a.ndim >= 2 and a.size and a.dtype == jnp.bfloat16 else a, params)
    got = _served_logits(model, fp8, tokens)
    assert _worst(got, want[:, P - 1:]) > TOL
