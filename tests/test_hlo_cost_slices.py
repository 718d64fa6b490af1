"""HLO cost analyzer: slice-charging ground truths (EXPERIMENTS §Perf H3/H6)
and the shard_map-MoE == local-MoE numerical equivalence (iteration M1)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch import hlo_cost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_scan_dynamic_slice_charged_at_slice_size():
    """Scanning over a big stacked array must charge ~slice bytes per step,
    not the full stack per step."""
    stack = jax.ShapeDtypeStruct((64, 256, 256), jnp.float32)  # 16 MB

    def f(stack):
        def body(c, x):
            return c + jnp.tanh(x), None
        out, _ = lax.scan(body, jnp.zeros((256, 256), jnp.float32), stack)
        return out

    r = hlo_cost.analyze(jax.jit(f).lower(stack).compile().as_text())
    full_stack_per_step = 64 * (64 * 256 * 256 * 4)   # the overcount regime
    assert r.bytes < full_stack_per_step / 4, \
        f"stacked-scan bytes look like a full-stack-per-iteration charge: {r.bytes:.2e}"
    # and at least the true traffic: read each slice once + carry updates
    assert r.bytes >= 64 * 256 * 256 * 4


def test_scan_dus_emission_charged_at_update_size():
    """Emitting per-step outputs into a stacked array (scan ys) writes one
    update window per iteration, not the whole output stack."""
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f(x):
        def body(c, _):
            c = jnp.tanh(c)
            return c, c
        _, ys = lax.scan(body, x, None, length=64)
        return ys

    r = hlo_cost.analyze(jax.jit(f).lower(x).compile().as_text())
    full_stack_per_step = 64 * (64 * 256 * 256 * 4)
    assert r.bytes < full_stack_per_step / 4, f"{r.bytes:.2e}"


_MOE_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, r"%s")
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import ARCHS
from repro.models import moe as MOE
from repro.distributed.sharding import use_batch_axes

cfg = dataclasses.replace(ARCHS["mixtral-8x7b"].reduced(), d_model=64,
                          d_ff=32, n_experts=4, n_experts_per_tok=2)
p = MOE.moe_params(cfg, jax.random.key(0), jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 16, 64), jnp.float32)

local = MOE._moe_apply_local(cfg, p, x)          # single-device reference

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh), use_batch_axes(("data",)):
    sharded = jax.jit(lambda p, x: MOE.moe_apply(cfg, p, x))(p, x)

np.testing.assert_allclose(np.asarray(local), np.asarray(sharded),
                           rtol=2e-5, atol=2e-5)
print("MOE_EQUIV_OK")
""" % (os.path.join(ROOT, "src"),)


def test_shard_map_moe_matches_local():
    """The M1 shard_map MoE path must be numerically identical to the
    single-device dispatch (run in a subprocess with 8 forced devices)."""
    proc = subprocess.run([sys.executable, "-c", _MOE_EQUIV],
                          capture_output=True, text=True, timeout=420,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MOE_EQUIV_OK" in proc.stdout
