"""KV caches sized from the number of tokens to decode.

A full-attention cache holds the prompt and every decoded token, so decode
runs past the 64 spare slots a prefill's cache used to have without
overwriting a key (the reduced Zamba2 case is in
``bench/tests/test_bench_zamba2_reference.py``); a sliding-window cache
keeps each key at the slot of its position; and the served whisper cache
keeps its prompt + 64 slots, so the whisper cell's programs are those it
had."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.reference import whisper as ref_whisper  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.configs.base import DEFAULT_NEW_TOKENS  # noqa: E402
from repro.launch.serve import ReplicatedServer  # noqa: E402
from repro.models import api as model_api  # noqa: E402
from repro.models import layers as L  # noqa: E402

SEED = 2**31 + 5


def test_whisper_decodes_past_the_old_headroom():
    """Prefill of 4 tokens, then 80 decode steps, against the reference's
    teacher-forced logits: within a tenth of the logits' spread.  The bf16
    path reaches 0.03 of it here; with the 64 spare slots a cache used to
    have, decode overwrites the first keys from the 65th step on and
    reaches 0.19."""
    cfg = get_arch("whisper-tiny").reduced()
    model = model_api.build_model(cfg, remat="none", kv_block=8, seq_chunk=16)
    params = model.init(jax.random.key(SEED))
    b, p, n = 2, 4, 80
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, p + n)).astype(np.int32)
    frames = jax.random.normal(jax.random.key(1), (b, cfg.n_frames,
                                                   cfg.d_model)
                               ).astype(jnp.bfloat16)
    sizes = {**dataclasses.asdict(cfg), "head_dim": cfg.resolved_head_dim}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_whisper.logits(
            jax.tree.map(lambda a: a.astype(jnp.float32),
                         ref_whisper.init_params(sizes, SEED)),
            sizes, jnp.asarray(tokens), frames))
    logits, cache = jax.jit(model.prefill, static_argnums=(2,))(
        params, {"tokens": jnp.asarray(tokens[:, :p]), "frames": frames}, n)
    assert cache["self"]["k"].shape[2] == p + n
    decode = jax.jit(model.decode_step)
    got = [logits[:, -1]]
    for t in range(p, p + n):
        pos = jnp.full((b, 1), t, jnp.int32)
        logits, cache = decode(params, cache, jnp.asarray(tokens[:, t:t + 1]),
                               pos)
        got.append(logits[:, -1])
    got = np.stack([np.asarray(x, np.float32) for x in got], 1)
    err = np.max(np.abs(got - want[:, p - 1:]), axis=(0, 2))
    assert err.max() < 0.1 * np.std(want), err


def test_served_whisper_cache_keeps_prompt_plus_64_slots():
    """``generate`` sizes the cache from its ``n_gen``: 4 + 64 slots for
    the whisper cell's 64 new tokens, the size the cache always had."""
    srv = ReplicatedServer("whisper-tiny", batch=2, prompt_len=4)
    seen = []
    decode = srv.decode

    def recording(params, cache, tok, pos):
        seen.append(cache["self"]["pos"].shape)
        return decode(params, cache, tok, pos)

    srv.decode = recording
    prompts = np.zeros((2, 4), np.int32)
    srv.generate(prompts, 64)
    assert DEFAULT_NEW_TOKENS == 64
    assert {s[-1] for s in seen} == {4 + 64}
    srv.generate(prompts, 70)
    assert seen[-1][-1] == 4 + 70


@pytest.mark.parametrize("s,window,n_gen,want", [
    (3, 0, 2, [0, 1, 2, -1, -1]),          # full attention: prompt + n_gen
    (3, 8, 2, [0, 1, 2, -1, -1]),          # window not yet full
    (10, 4, 3, [8, 9, 6, 7]),              # position p at slot p % 4
    (8, 4, 3, [4, 5, 6, 7]),
])
def test_cache_slots_hold_positions(s, window, n_gen, want):
    cfg = get_arch("mixtral-8x7b").reduced()
    k = jnp.arange(s, dtype=jnp.float32).reshape(1, s, 1, 1)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    cache = L.init_cache_from(cfg, k, k, pos, window, n_gen)
    assert cache["pos"][0].tolist() == want
    assert int(cache["idx"]) == s
    filled = np.asarray(cache["pos"][0]) >= 0
    np.testing.assert_array_equal(
        np.asarray(cache["k"])[0, filled, 0, 0],
        np.asarray(cache["pos"][0])[filled])
