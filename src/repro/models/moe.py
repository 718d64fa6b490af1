"""Mixture-of-Experts FFN (top-k routing, sort-based dispatch).

Dispatch is performed *per sequence* (vmapped over batch) so that under a
batch-sharded `data` axis the argsort/scatter stays local to each shard — no
cross-device token exchange is required in the TP-sharded baseline. (An
expert-parallel all-to-all variant is provided for the perf hillclimb via
``distributed/ep.py``.)

FLOP accounting: per-expert buffers are capacity-bounded at
``ceil(S*k/E * capacity_factor)`` tokens, so expert GEMM FLOPs track
6*N_active*D within the capacity factor — matching the paper-roofline's
MoE MODEL_FLOPS convention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import Params, _dense_init, split_rngs

F32 = jnp.float32


def moe_params(cfg: ModelConfig, rng, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    r = split_rngs(rng, 4)
    return {
        "router": _dense_init(r[0], (d, e), dtype),
        "wi": _dense_init(r[1], (e, d, f), dtype),
        "wg": _dense_init(r[2], (e, d, f), dtype),
        "wo": _dense_init(r[3], (e, f, d), dtype),
    }


def _capacity(cfg: ModelConfig, seq: int) -> int:
    per = seq * cfg.n_experts_per_tok / cfg.n_experts
    cap = int(per * cfg.capacity_factor) + 1
    return min(max(cap, cfg.n_experts_per_tok), seq)


def _dispatch_one(cfg: ModelConfig, gates_logits: jnp.ndarray, seq: int):
    """Route one sequence. gates_logits: [S, E].

    Returns (assign_expert[S*k], assign_slot[S*k], weight[S*k], keep[S*k]).
    """
    k = cfg.n_experts_per_tok
    cap = _capacity(cfg, seq)
    probs = jax.nn.softmax(gates_logits.astype(F32), axis=-1)
    top_w, top_e = lax.top_k(probs, k)                        # [S, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                                # [S*k]
    order = jnp.argsort(flat_e, stable=True)                  # group by expert
    sorted_e = flat_e[order]
    # rank within the expert group = index - first index of this expert
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(seq * k) - first
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)

    keep = rank < cap
    slot = jnp.where(keep, rank, cap)                         # cap row = dropped
    return flat_e, slot, top_w.reshape(-1), keep, cap


def _mesh_for_shard_map():
    """Usable mesh for the explicit-TP path, or None (single-device tests)."""
    m = jax.sharding.get_abstract_mesh()
    if "model" not in m.axis_names or m.shape["model"] <= 1:
        return None
    return m


def moe_apply(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B, S, d] -> [B, S, d].

    On a mesh, the dispatch+expert compute runs under shard_map with
    explicit specs (batch over the data axes, expert d_ff over `model`,
    psum over `model` after the down-projection). This is load-bearing:
    left to GSPMD, the batched scatter/argsort chain loses the batch
    sharding and the expert GEMMs replicate onto every device — a 19x
    per-device FLOP inflation measured on the 16x16 mesh (EXPERIMENTS.md
    section Perf, iteration M1)."""
    mesh = _mesh_for_shard_map()
    if mesh is not None:
        return _moe_apply_sharded(cfg, p, x, mesh)
    return _moe_apply_local(cfg, p, x)


def _moe_apply_sharded(cfg: ModelConfig, p: Params, x, mesh):
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import _BATCH_AXES
    shape = dict(mesh.shape)
    batch = tuple(a for a in _BATCH_AXES.get() if a in mesh.axis_names
                  and shape.get(a, 1) > 1)
    bsz = 1
    for a in batch:
        bsz *= shape[a]
    if x.shape[0] % max(bsz, 1) != 0:
        batch = ()              # tiny decode batches: replicate over data
    bspec = P(batch if batch else None, None, None)

    def inner(xs, router, wi, wg, wo):
        y = _moe_apply_local(
            cfg, {"router": router, "wi": wi, "wg": wg, "wo": wo}, xs)
        return jax.lax.psum(y, "model")

    f = jax.shard_map(
        inner,
        in_specs=(bspec, P(None, None), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None)),
        out_specs=bspec, check_vma=False)
    return f(x, p["router"], p["wi"], p["wg"], p["wo"])


def _moe_apply_local(cfg: ModelConfig, p: Params, x):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    logits = jnp.einsum("bsd,de->bse", x, p["router"])

    def per_seq(xs, gl):
        flat_e, slot, w, keep, cap = _dispatch_one(cfg, gl, s)
        tok = jnp.repeat(jnp.arange(s), k)                    # token of assignment
        # scatter tokens into [E, cap+1, d]; row `cap` swallows drops
        buf = jnp.zeros((e, cap + 1, d), xs.dtype)
        buf = buf.at[flat_e, slot].set(xs[tok], mode="drop")
        h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
        h = h * jax.nn.silu(g.astype(F32)).astype(h.dtype)
        out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"])
        gathered = out_buf[flat_e, slot]                      # [S*k, d]
        gathered = gathered * (w * keep)[:, None].astype(gathered.dtype)
        y = jnp.zeros_like(xs).at[tok].add(gathered)
        return y

    return jax.vmap(per_seq)(x, logits)


def moe_aux_loss(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style)."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = lax.top_k(probs, cfg.n_experts_per_tok)
    frac = jnp.mean(
        jax.nn.one_hot(top_e, cfg.n_experts, dtype=F32), axis=(0, 1, 2))
    imp = jnp.mean(probs, axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac * imp)
