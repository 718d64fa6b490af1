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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_chunk_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.launch.serve import serve_run_config
from repro.launch.step_fns import (greedy_decode, greedy_prefill,
                                   make_decode_step, make_prefill_step,
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


@functools.cache
def _serve(arch, batch, prompt_len):
    """Abstract prefill and decode arguments of one server shape."""
    cfg = get_arch(arch)
    run = serve_run_config(cfg, batch=batch, prompt_len=prompt_len)
    prefill, model = make_prefill_step(run)
    decode, _ = make_decode_step(run)
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = model_api.input_specs(cfg, run.shape)
    return prefill, decode, params, batch


ZAMBA = "zamba2-7b-18l"
# (batch, prompt, new tokens): the chip smoke's xlstm serving shape and the
# whisper and zamba2 serving cells'
SERVE_SHAPES = {XLSTM: (8, 1024, 64), "whisper-tiny": (16, 4, 64),
                ZAMBA: (16, 2048, 128)}


def _compile_step(one_chip, arch, step, greedy=False):
    """Compile the prefill or decode of ``arch`` for one v5e chip, the
    logits step or the served one with greedy sampling folded in; the
    step's arguments, outputs (those not written in place of a donated
    argument) and temporaries, and during decode the replica's copy of the
    decode state (it lives on the same device), must fit the chip's HBM."""
    b, prompt_len, n_gen = SERVE_SHAPES[arch]
    prefill, decode, params, batch = _serve(arch, b, prompt_len)
    replica = 0
    if step == "prefill":
        fn = greedy_prefill(prefill) if greedy else prefill
        lowered = jax.jit(fn, static_argnums=(2,)).lower(
            *_on(one_chip, (params, batch)), n_gen)
    else:
        _, cache = jax.eval_shape(functools.partial(prefill, n_gen=n_gen),
                                  params, batch)
        tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        fn = greedy_decode(decode) if greedy else decode
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(
            *_on(one_chip, (params, cache, tok, tok)))
        replica = _nbytes(cache)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes + replica \
        < HBM_BYTES
    return compiled


def test_xlstm_prefill_compiles_for_v5e(one_chip):
    _compile_step(one_chip, XLSTM, "prefill")


def test_xlstm_decode_compiles_for_v5e(one_chip):
    _compile_step(one_chip, XLSTM, "decode")


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_zamba2_serving_steps_fit_v5e(one_chip, step):
    """The zamba2 serving cell's steps at full width, batch 16 with KV
    caches of 2048 + 128 slots: they compile for one v5e, and each with
    its temporaries, and the decode with the replica's copy of the KV
    caches and Mamba2 states, fits the chip's 16 GiB."""
    compiled = _compile_step(one_chip, ZAMBA, step, greedy=True)
    _, _, params, _ = _serve(ZAMBA, 16, 2048)
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        _nbytes(params)


# ------------------------------------------------- argmax apart from the
# output projection

def _computations(hlo: str) -> dict:
    """HLO module text -> {computation name: its instruction lines}."""
    comps, body = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            body = comps.setdefault(
                line.removeprefix("ENTRY ").split()[0].lstrip("%"), [])
        elif line == "}":
            body = None
        elif body is not None:
            body.append(line)
    return comps


def _is_argmax(line: str) -> bool:
    """A variadic reduce to a (value, s32 index) pair: ``argmax``."""
    m = re.search(r"= (\(.*?) reduce\(", line)
    return bool(m) and "s32[" in m.group(1)


def _argmax_fusions(hlo: str) -> dict:
    """{fused computation holding an argmax: whether it, or a fusion nested
    in it, also computes a convolution}."""
    comps = _computations(hlo)
    fused = {c for lines in comps.values() for line in lines
             if " fusion(" in line
             for c in re.findall(r"calls=%([\w.\-]+)", line)}

    def has_conv(name, seen):
        seen.add(name)
        lines = comps.get(name, [])
        return any(" convolution(" in line for line in lines) or any(
            has_conv(c, seen) for line in lines
            for c in re.findall(r"calls=%([\w.\-]+)", line)
            if c not in seen)

    return {name: has_conv(name, set()) for name in fused
            if any(map(_is_argmax, comps[name]))}


def test_argmax_fusion_finder_sees_a_fused_projection():
    """The finder on the form the v5e compiler gives when the output
    projection is fused into the argmax (and on the barrier's form)."""
    fused = """%region_0.1 (a: bf16[], b: s32[], c: bf16[], d: s32[]) -> (bf16[], s32[]) {
  ROOT %tuple.1 = (bf16[], s32[]) tuple(%a, %b)
}

%fused_computation.1 (param_0: bf16[16,384], param_1: bf16[384,51865]) -> (bf16[16]{0:T(256)(128)(2,1)}, s32[16]{0:T(128)}) {
  %convolution.39 = bf16[16,51865]{1,0:T(8,128)(2,1)} convolution(%param_0, %param_1), dim_labels=bf_io->bf
  %iota.22 = s32[16,51865]{1,0} iota(), iota_dimension=1
  ROOT %reduce.64 = (bf16[16]{0:T(256)(128)(2,1)}, s32[16]{0:T(128)}) reduce(%convolution.39, %iota.22, %c0, %c1), dimensions={1}, to_apply=%region_0.1
}

ENTRY %main.2 (p0: bf16[16,384], p1: bf16[384,51865]) -> s32[16] {
  %fusion.1 = (bf16[16]{0}, s32[16]{0}) fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1
  ROOT %gte = s32[16]{0} get-tuple-element(%fusion.1), index=1
}
"""
    assert _argmax_fusions(fused) == {"fused_computation.1": True}
    apart = fused.replace(
        "%convolution.39 = bf16[16,51865]{1,0:T(8,128)(2,1)} convolution("
        "%param_0, %param_1), dim_labels=bf_io->bf",
        "%convolution.39 = bf16[16,51865]{1,0} copy(%param_0)")
    assert _argmax_fusions(apart) == {"fused_computation.1": False}


@pytest.mark.parametrize("step", ["prefill", "decode"])
@pytest.mark.parametrize("arch", ["whisper-tiny", XLSTM])
def test_served_steps_keep_the_projection_out_of_the_argmax(one_chip, arch,
                                                            step):
    """The served steps compile for v5e, and their argmax reads the bf16
    logits the logits step returns: no fusion computes both the output
    projection and the argmax, which would let the compiler choose the
    token from the projection computed another way."""
    compiled = _compile_step(one_chip, arch, step, greedy=True)
    found = _argmax_fusions(compiled.as_text())
    assert found, "no argmax fusion in the compiled step"
    assert not any(found.values()), found
