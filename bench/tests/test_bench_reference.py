"""The plain references against the served models, at reduced sizes on the
CPU: the weights the reference draws from a seed are the served weights
bit for bit, the training loss and the prefill logits agree, and prefill
followed by several cached decode steps agrees with the reference's
teacher-forced forward pass over the same tokens."""
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

from bench.reference import whisper as ref_whisper  # noqa: E402
from bench.reference import xlstm as ref_xlstm  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.models import api as model_api  # noqa: E402

REFS = {"whisper-tiny": ref_whisper, "xlstm-350m": ref_xlstm}
SEED = 2**31 + 17


def sizes(cfg) -> dict:
    return {**dataclasses.asdict(cfg), "head_dim": cfg.resolved_head_dim}


@pytest.fixture(scope="module", params=sorted(REFS))
def case(request):
    arch = request.param
    cfg = get_arch(arch).reduced()
    model = model_api.build_model(cfg, remat="none", kv_block=8, seq_chunk=16)
    params = model.init(jax.random.key(SEED))
    ref_params = REFS[arch].init_params(sizes(cfg), SEED)
    rng = np.random.default_rng(0)
    b, p, n = 2, 16, 6
    tokens = rng.integers(0, cfg.vocab_size, (b, p + n)).astype(np.int32)
    frames = None
    if cfg.family == "audio":
        frames = jax.random.normal(jax.random.key(1),
                                   (b, cfg.n_frames, cfg.d_model)
                                   ).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        ref = REFS[arch].logits(
            jax.tree.map(lambda a: a.astype(jnp.float32), ref_params),
            sizes(cfg), jnp.asarray(tokens), frames)
    return arch, cfg, model, params, ref_params, tokens, frames, np.asarray(ref)


def test_reference_draws_the_served_weights(case):
    _, _, _, params, ref_params, *_ = case
    got = jax.tree_util.tree_flatten_with_path(ref_params)
    want = jax.tree_util.tree_flatten_with_path(params)
    assert got[1] == want[1]
    for (path, a), (_, b) in zip(got[0], want[0]):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


def _close(got, want, what):
    # bf16 activations against f32 reach 3 % (whisper) to 10 % (xlstm, whose
    # recurrences carry the rounding) of the logits' spread at these sizes;
    # the fp8 control misses by 35 % to 130 %
    tol = 0.2 * float(np.std(want))
    err = float(np.max(np.abs(np.asarray(got, np.float32) - want)))
    assert err < tol, f"{what}: max |err| {err:.4g} >= {tol:.4g}"


def test_loss_matches_reference(case):
    _, cfg, model, params, _, tokens, frames, ref = case
    batch = {"tokens": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    if frames is not None:
        batch["frames"] = frames
    loss = float(model.loss_fn(params, batch))
    logp = jax.nn.log_softmax(ref[:, :-1], -1)
    want = -float(np.mean(np.take_along_axis(
        np.asarray(logp), tokens[:, 1:, None], -1)))
    assert abs(loss - want) < 2e-2 * abs(want), (loss, want)


def test_prefill_then_decode_matches_forward(case):
    _, cfg, model, params, _, tokens, frames, ref = case
    p = 16
    batch = {"tokens": jnp.asarray(tokens[:, :p])}
    if frames is not None:
        batch["frames"] = frames
    logits, cache = jax.jit(model.prefill)(params, batch)
    _close(logits[:, -1], ref[:, p - 1], "prefill")
    decode = jax.jit(model.decode_step)
    for t in range(p, tokens.shape[1]):
        pos = jnp.full((tokens.shape[0], 1), t, jnp.int32)
        logits, cache = decode(params, cache,
                               jnp.asarray(tokens[:, t:t + 1]), pos)
        _close(logits[:, -1], ref[:, t], f"decode at {t}")


def test_fp8_control_misses_the_tolerance(case):
    """The tolerance above is tight enough that the fp8 control fails it."""
    arch, cfg, _, _, ref_params, tokens, frames, ref = case
    from bench.reference.common import Numerics
    with jax.default_matmul_precision("highest"):
        ctl = REFS[arch].logits(
            jax.tree.map(lambda a: a.astype(jnp.float32), ref_params),
            sizes(cfg), jnp.asarray(tokens), frames, Numerics("fp8"))
    with pytest.raises(AssertionError):
        _close(ctl, ref, "fp8 control")
