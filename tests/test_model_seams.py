"""Seams between the models and what serves them.

A model describes its decode state twice: ``init_cache`` builds an empty
one, and ``prefill`` emits a filled one.  The server, the replica copy and
the benchmark's references read both, so the two must agree leaf for leaf.
And one attention layer, in ``models/layers.py``, projects q/k/v, attends
and builds or updates the KV cache for every family."""
import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import repro.models
from repro.configs import ARCHS
from repro.models import build_model

B, S, N_GEN = 2, 60, 8      # prompt + new tokens pass a reduced window (64)


def _batch(cfg):
    b = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "audio":
        b["frames"] = jax.ShapeDtypeStruct((B, cfg.n_frames, cfg.d_model),
                                           jnp.bfloat16)
    if cfg.family == "vlm":
        b["image_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return b


def _layout(tree):
    return jax.tree.structure(tree), [(a.shape, jnp.dtype(a.dtype))
                                      for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_cache_layout_matches_prefill(arch):
    """The cache ``init_cache(B, S + n_gen)`` describes is the one
    ``prefill(..., n_gen)`` emits: the same tree, shapes and dtypes."""
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg, remat="none", kv_block=32, seq_chunk=32)
    params = jax.eval_shape(model.init, jax.random.key(0))
    empty = jax.eval_shape(functools.partial(model.init_cache, B, S + N_GEN))
    _, filled = jax.eval_shape(
        functools.partial(model.prefill, n_gen=N_GEN), params, _batch(cfg))
    assert _layout(empty) == _layout(filled)


def test_attention_has_one_home():
    """No model module but ``layers.py`` projects q/k/v, runs the blockwise
    attention or builds a KV cache itself: each goes through
    ``layers.attention_apply``."""
    names = re.compile(r"\b(blockwise_attention|_project_qkv|init_cache_from)\b")
    pkg = Path(repro.models.__file__).parent
    found = {p.name: sorted(set(names.findall(p.read_text())))
             for p in sorted(pkg.glob("*.py")) if p.name != "layers.py"}
    assert not {k: v for k, v in found.items() if v}
    assert names.search((pkg / "layers.py").read_text())
