"""Compile-only guards for a TPU v5e, without a chip.

The TPU compiler is installed alongside JAX and compiles for a *described*
``v5e:2x2`` topology: tiling, VMEM and HBM refusals that interpret mode
cannot show surface here instead of on the chip.  Nothing runs, so these
tests say nothing about results or speed.

Everything touching libtpu happens inside fixtures or tests, never at
import: only one process may load the library, and every pytest-xdist
worker imports this file.  Keep all such compiles in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_chunk_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.launch.serve import serve_run_config
from repro.launch.step_fns import (make_decode_step, make_prefill_step,
                                   make_train_step)
from repro.launch.train import train_run_config
from repro.models import api as model_api
from repro.optim import adamw

HBM_BYTES = 16 * 2**30                  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no libtpu"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Abstract stand-ins for ``tree`` placed on ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _nbytes(tree) -> int:
    return sum(a.size * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


# ------------------------------------------------------------------ kernels

BF16, F32 = jnp.bfloat16, jnp.float32
# (kernel, argument shapes and dtypes, static options) at real widths
KERNELS = {
    # qwen3-8b attention: Hq 32, Hkv 8, head dim 128, S 2048
    "flash_attention": (flash_attention, [((1, 32, 2048, 128), BF16),
                                          ((1, 8, 2048, 128), BF16),
                                          ((1, 8, 2048, 128), BF16)], {}),
    "rmsnorm": (rmsnorm, [((8, 2048, 4096), BF16), ((4096,), BF16)], {}),
    # zamba2-7b SSD scan: H 112, P 64, N 64
    "mamba_chunk_scan": (mamba_chunk_scan, [((1, 2048, 112, 64), BF16),
                                            ((1, 2048, 64), BF16),
                                            ((1, 2048, 64), BF16),
                                            ((1, 2048, 112), F32),
                                            ((1, 2048, 112), F32)],
                         {"chunk": 128}),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes, static = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(functools.partial(kernel, **static)).lower(*args) \
        .compile()
    assert "tpu_custom_call" in compiled.as_text()


# -------------------------------------------------------- xlstm-350m steps

XLSTM = "xlstm-350m"


def test_xlstm_train_step_fits_v5e(one_chip):
    """The chip smoke's train step at full width, 2 x 1024: the step's
    arguments and temporaries plus the replica's copy of the state (the
    replica lives on the same device) must fit one chip's HBM."""
    cfg = get_arch(XLSTM)
    run = train_run_config(cfg, batch=2, seq=1024)
    step, model = make_train_step(run)
    params = jax.eval_shape(model.init, jax.random.key(0))
    opt = jax.eval_shape(adamw.init, params)
    batch = model_api.input_specs(cfg, run.shape)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        *_on(one_chip, (params, opt, batch))).compile()
    mem = compiled.memory_analysis()
    replica = _nbytes((params, opt))
    assert mem.argument_size_in_bytes >= replica
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes + replica \
        < HBM_BYTES


@pytest.fixture(scope="module")
def xlstm_serve():
    """The chip smoke's serving shapes: batch 8, prompt 1024."""
    cfg = get_arch(XLSTM)
    run = serve_run_config(cfg, batch=8, prompt_len=1024)
    prefill, model = make_prefill_step(run)
    decode, _ = make_decode_step(run)
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = model_api.input_specs(cfg, run.shape)
    return prefill, decode, params, batch


def test_xlstm_prefill_compiles_for_v5e(one_chip, xlstm_serve):
    prefill, _, params, batch = xlstm_serve
    compiled = jax.jit(prefill).lower(*_on(one_chip, (params, batch))) \
        .compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_xlstm_decode_compiles_for_v5e(one_chip, xlstm_serve):
    prefill, decode, params, batch = xlstm_serve
    _, cache = jax.eval_shape(prefill, params, batch)
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        *_on(one_chip, (params, cache, tok, tok))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
