"""Jit'd dispatch wrappers around the Pallas kernels.

``backend="auto"`` runs the compiled kernels (`interpret=False`) and is
valid only on a TPU: anywhere else it raises rather than quietly running
the interpreter. ``backend="interpret"`` executes the kernel body in Python
for correctness validation on the CPU (the tests pass it explicitly), and
``backend="ref"`` forces the pure-jnp oracle.
"""
from __future__ import annotations

import jax

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.mamba_scan import mamba_chunk_scan as _mamba_pallas
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas


def _resolve(backend: str) -> str:
    if backend != "auto":
        return backend
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"backend='auto' runs the Pallas kernels compiled and needs a "
            f"TPU, but JAX's default backend is {platform!r}; pass "
            f"backend='interpret' or backend='ref' off the chip")
    return "pallas"


def attention(q, k, v, *, causal=True, window=0, q_block=128, kv_block=128,
              backend: str = "auto"):
    """Flash attention. q: [B,Hq,S,D]; k, v: [B,Hkv,S,D]."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         q_block=q_block, kv_block=kv_block,
                         interpret=(backend == "interpret"))


def rmsnorm(x, w, *, eps=1e-5, block_rows=256, backend: str = "auto"):
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.rmsnorm_ref(x, w, eps=eps)
    return _rmsnorm_pallas(x, w, eps=eps, block_rows=block_rows,
                           interpret=(backend == "interpret"))


def mamba_chunk_scan(x, b, c, dt, da, *, chunk=128, backend: str = "auto"):
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.mamba_chunk_scan_ref(x, b, c, dt, da)
    return _mamba_pallas(x, b, c, dt, da, chunk=chunk,
                         interpret=(backend == "interpret"))
