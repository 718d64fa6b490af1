"""Partitioning rules: PyTree path -> PartitionSpec for every model family.

Megatron-style tensor parallelism over the ``model`` axis; batch over
``data`` (and ``pod`` when the multi-pod mesh runs in data-parallel mode;
in the paper's replication mode the ``pod`` axis is deliberately *absent*
from every spec — pod 1 is the replica slice and computes the same values).

Every rule degrades gracefully: if a dimension does not divide by the mesh
axis size (e.g. whisper-tiny's 6 heads on a 16-way model axis, GQA's 8 KV
heads), that dimension is replicated instead. This keeps one rule table
valid for all 10 assigned architectures.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaf-name -> per-dim logical axes, applied right-aligned to the shape so
# leading stacked-layer dims ([L, ...], [G, K, ...]) are replicated.
# "model" entries are dropped per-dim when the size does not divide.
_PARAM_RULES = {
    # embeddings
    "embed":    (("model", None)),
    "unembed":  ((None, "model")),
    # attention
    "wq":       ((None, "model", None)),
    "wk":       ((None, "model", None)),
    "wv":       ((None, "model", None)),
    "wo":       (("model", None, None)),
    "bq":       (("model", None)),
    "bk":       (("model", None)),
    "bv":       (("model", None)),
    "gate":     (()),
    # dense mlp
    "wi":       ((None, "model")),
    "wg":       ((None, "model")),
    # moe (router replicated; experts sharded on d_ff)
    "router":   ((None, None)),
    # xlstm
    "w_up":     ((None, "model")),
    "w_down":   (("model", None)),
    "w_gates":  ((None, "model")),
    "b_gates":  (("model",)),
    "r_gates":  ((None, None, "model")),
    "bf":       ((None,)),
    # mamba2
    "in_z":     ((None, "model")),
    "in_x":     ((None, "model")),
    "in_b":     ((None, None)),         # [d, groups * state]: small
    "in_c":     ((None, None)),
    "in_dt":    ((None, "model")),
    "conv_w":   ((None, None)),
    "conv_b":   ((None,)),
    "a_log":    ((None,)),
    "d_skip":   ((None,)),
    "dt_bias":  ((None,)),
    "out_proj": (("model", None)),
    # zamba2 shared blocks (q/k/v read the 2d-wide concatenation: the
    # attention rules above) and per-application LoRA and linear
    "gate_up":  ((None, "model")),
    "down_proj": (("model", None)),
    "lora_a":   ((None, None)),
    "lora_b":   ((None, "model")),
    "linear":   ((None, "model")),
    # norms
    "scale":    ((None,)),
}

# context-sensitive overrides: (parent, leaf) pairs
_CTX_RULES = {
    # MoE expert weights: [E, d, f] / [E, f, d] — shard d_ff on model
    ("ffn", "wi"): (None, None, "model"),
    ("ffn", "wg"): (None, None, "model"),
    ("ffn", "wo"): (None, "model", None),
    # xlstm mLSTM q/k/v are square [d, d]
    ("mlstm", "wq"): (None, "model"),
    ("mlstm", "wk"): (None, "model"),
    ("mlstm", "wv"): (None, "model"),
    ("mlstm", "wi"): (None, None),      # input-gate proj [d, H], H tiny
    ("mlstm", "wf"): (None, None),
    # xlstm sLSTM up-block is a standard mlp dict -> default rules fine
}


def _path_names(path) -> list:
    out = []
    for k in path:
        name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "name", None)
        if name is None and hasattr(k, "idx"):
            name = str(k.idx)
        out.append(str(name))
    return out


def _fit(axes: Sequence, shape: Tuple[int, ...], mesh_axes: dict) -> P:
    """Right-align the rule to the shape; drop non-dividing mesh axes."""
    rule = list(axes)
    ndim = len(shape)
    full = [None] * (ndim - len(rule)) + rule if len(rule) <= ndim else \
        rule[len(rule) - ndim:]
    spec = []
    for dim, ax in zip(shape, full):
        if ax is None:
            spec.append(None)
        else:
            size = mesh_axes.get(ax, 1)
            spec.append(ax if (size > 1 and dim % size == 0) else None)
    return P(*spec)


def _moe_expert_leaf(names: list) -> bool:
    return "ffn" in names or "experts" in names


def param_pspec(path, leaf, mesh_axes: dict) -> P:
    names = _path_names(path)
    leaf_name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    shape = leaf.shape
    for i in range(len(names) - 1):
        key = (names[i], leaf_name)
        if key in _CTX_RULES:
            # MoE expert rules only apply to 3-dim (stacked [L,E,..] -> 4+)
            rule = _CTX_RULES[key]
            return _fit(rule, shape, mesh_axes)
    if leaf_name in _PARAM_RULES:
        return _fit(_PARAM_RULES[leaf_name], shape, mesh_axes)
    return P()  # replicate unknowns (safe default)


def param_pspecs(abstract_params, mesh: Mesh):
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: param_pspec(p, l, mesh_axes), abstract_params)


def param_shardings(abstract_params, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(abstract_params, mesh))


# ---------------------------------------------------------------------------
# activations / inputs
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh, replication_axis: str = "none"):
    """Mesh axes that shard the global batch. In the paper's replication
    mode (`pod`), the pod axis is excluded everywhere: pod 1 replays pod 0."""
    axes = [a for a in mesh.axis_names if a in ("pod", "data")]
    if replication_axis == "pod" and "pod" in axes:
        axes.remove("pod")
    if replication_axis == "split":
        pass  # the `rep` axis of a split mesh is already not named data/pod
    return tuple(axes)


def input_pspec(shape: Tuple[int, ...], mesh: Mesh,
                replication_axis: str = "none") -> P:
    """Shard dim 0 (global batch) over the batch axes when divisible."""
    ba = batch_axes(mesh, replication_axis)
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in ba:
        n *= mesh_axes[a]
    if shape and shape[0] % n == 0 and n > 1:
        return P(ba, *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def input_shardings(specs: dict, mesh: Mesh, replication_axis: str = "none"):
    return {k: NamedSharding(mesh, input_pspec(v.shape, mesh,
                                               replication_axis))
            for k, v in specs.items()}


# ---------------------------------------------------------------------------
# serve caches / recurrent state
# ---------------------------------------------------------------------------

def cache_pspec(path, leaf, mesh_axes: dict, global_batch: int,
                replication_axis: str = "none") -> P:
    """KV caches: [.., B, S, H, D] — batch over data when divisible, else
    sequence over data; heads over model (head_dim fallback). Recurrent
    states: batch over data, largest feature dim over model."""
    names = _path_names(path)
    leaf_name = names[-1]
    shape = leaf.shape
    data = [a for a in ("pod", "data") if a in mesh_axes]
    if replication_axis == "pod" and "pod" in data:
        data.remove("pod")
    dsz = 1
    for a in data:
        dsz *= mesh_axes[a]
    data_ax = tuple(data) if dsz > 1 else None
    msz = mesh_axes.get("model", 1)

    spec = [None] * len(shape)

    def find_batch():
        for i, d in enumerate(shape):
            if d == global_batch:
                return i
        return -1

    bi = find_batch()
    if leaf_name in ("k", "v"):
        # [..., B, S, H, D]
        if data_ax and bi >= 0 and shape[bi] % dsz == 0:
            spec[bi] = data_ax
        elif data_ax and len(shape) >= 3 and shape[-3] % dsz == 0:
            spec[-3] = data_ax      # shard the sequence/window dim
        if shape[-2] % msz == 0 and msz > 1:
            spec[-2] = "model"
        elif shape[-1] % msz == 0 and msz > 1:
            spec[-1] = "model"
        return P(*spec)
    if leaf_name == "pos":
        # [..., B, S] — mirror the k/v batch/seq choice
        if data_ax and bi >= 0 and shape[bi] % dsz == 0:
            spec[bi] = data_ax
        elif data_ax and shape[-1] % dsz == 0:
            spec[-1] = data_ax
        return P(*spec)
    if leaf_name == "idx":
        return P(*spec)
    # recurrent states (mamba h/conv, xlstm C/n/h/c/m)
    if data_ax and bi >= 0 and shape[bi] % dsz == 0:
        spec[bi] = data_ax
    placed = False
    if len(shape) - (bi + 1) >= 1 and msz > 1:
        # shard the head dim if divisible, else the last feature dim
        for i in range(bi + 1 if bi >= 0 else 0, len(shape)):
            if spec[i] is None and shape[i] % msz == 0 and shape[i] >= msz:
                spec[i] = "model"
                placed = True
                break
    return P(*spec)


def cache_pspecs(abstract_cache, mesh: Mesh, global_batch: int,
                 replication_axis: str = "none"):
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: cache_pspec(p, l, mesh_axes, global_batch,
                                 replication_axis), abstract_cache)


def cache_shardings(abstract_cache, mesh: Mesh, global_batch: int,
                    replication_axis: str = "none"):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        cache_pspecs(abstract_cache, mesh, global_batch, replication_axis))


# ---------------------------------------------------------------------------
# In-model sharding constraints (GSPMD guidance)
# ---------------------------------------------------------------------------
# GSPMD occasionally loses the batch sharding through vmapped scatter/sort
# chains (MoE dispatch, recurrent-state updates) and replicates the whole
# computation ("involuntary full rematerialization"). These helpers pin the
# batch axis on the tensors entering/leaving such regions. They are no-ops
# outside a mesh context (single-device smoke tests).

import contextvars as _contextvars
from contextlib import contextmanager as _contextmanager

_BATCH_AXES = _contextvars.ContextVar("repro_batch_axes", default=("data",))


@_contextmanager
def use_batch_axes(axes):
    """Set which mesh axes shard the batch for in-model constraints
    (('pod','data') for multi-pod DP; ('data',) in replication mode)."""
    tok = _BATCH_AXES.set(tuple(axes))
    try:
        yield
    finally:
        _BATCH_AXES.reset(tok)


def constrain_batch(x, batch_dims: int = 1):
    """Pin x's leading dim(s) to the batch mesh axes; no-op without a mesh."""
    axes = _BATCH_AXES.get()
    if not axes or x.ndim < 1:
        return x
    spec = P(tuple(axes), *([None] * (x.ndim - 1)))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:           # no mesh context (CPU smoke tests)
        return x
