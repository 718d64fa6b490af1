"""Workload protocol + adapters for the repo's three workload families.

A workload is anything that can be driven step-by-step over an explicit
state pytree:

    init_state() -> state
    step(state, t) -> (state, metrics)        # t is the step index
    snapshot(state) -> snap                    # optional; default deep copy
    restore(snap) -> state                     # optional; default deep copy

Determinism contract: ``step`` must be a pure function of (state, t) — the
same state and step index always produce bit-identical results.  That is
what makes replica double-execution equivalent to running on a second slice
and makes promotion O(1) and exact (the paper's FT theorem).

Adapters:
  TrainWorkload   - jitted LM train step + deterministic batch cursor
  DecodeWorkload  - greedy decode loop over (cache, tok, pos, out); the
                    jitted prefill and decode sample the token themselves
  SimAppWorkload  - a simrt generator app (HPCG / CloverLeaf / PIC) run by a
                    sequential in-process op resolver, whole-app state
"""
from __future__ import annotations

import copy
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Protocol, Tuple, \
    runtime_checkable

import numpy as np

from repro.configs.base import DEFAULT_NEW_TOKENS
from repro.obs import span


def copy_tree(tree):
    """Deep device copy — replica state must own its buffers (jitted steps
    donate their inputs; aliased buffers would be invalidated).  Without
    jax (the numpy-only bench environment) plain pytrees deep-copy."""
    try:
        import jax
    except ImportError:
        return copy.deepcopy(tree)
    return jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x, tree)


def state_bytes(state) -> dict:
    """Bytes of a decode state's two kinds of cache: ``kv`` the KV caches
    (every dict that holds ``k`` and ``v``), ``ssm`` the rest of
    ``state["cache"]``, the recurrent state.  Zero for any other state."""
    out = {"kv": 0, "ssm": 0}

    def walk(node, kind):
        if isinstance(node, dict):
            if "k" in node and "v" in node:
                kind = "kv"
            for child in node.values():
                walk(child, kind)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child, kind)
        else:
            out[kind] += int(getattr(node, "nbytes", 0))

    if isinstance(state, dict) and "cache" in state:
        walk(state["cache"], "ssm")
    return out


@runtime_checkable
class Workload(Protocol):
    def init_state(self) -> Any: ...

    def step(self, state: Any, t: int) -> Tuple[Any, Any]: ...


def snapshot_state(workload, state):
    snap = getattr(workload, "snapshot", None)
    return snap(state) if snap is not None else copy_tree(state)


def restore_state(workload, snap):
    restore = getattr(workload, "restore", None)
    return restore(snap) if restore is not None else copy_tree(snap)


class TrainWorkload:
    """The jitted train step as a Workload. ``batch_fn(t)`` must be a pure
    function of the step index (deterministic data cursor)."""

    disk_checkpointable = True

    def __init__(self, *, train_step: Callable, init_state: Callable,
                 batch_fn: Callable[[int], dict]):
        self.train_step = train_step
        self.init_state_fn = init_state
        self.batch_fn = batch_fn

    def init_state(self):
        return self.init_state_fn()

    def step(self, state, t):
        state, loss = self.train_step(state, self.batch_fn(t))
        return state, loss


class DecodeWorkload:
    """Greedy decode as a Workload: state carries the KV cache, the last
    token, the position cursor and the emitted tokens. One step = append the
    current token and decode the next one. Replicating this state IS the
    paper's replication story for serving: the replica's cache stays current,
    so failover is one promotion with no prefill replay.

    ``prefill(params, batch, n_gen) -> (tok, pos, cache)`` and
    ``decode(params, cache, tok, pos) -> (tok, pos, cache)`` sample greedily
    inside their jitted programs (``repro.launch.step_fns.greedy_prefill`` /
    ``greedy_decode``), so a step dispatches one program and does no array
    math of its own.  ``n_gen`` is the number of steps the run takes: the
    prefill's KV caches hold the prompt and every decoded token."""

    disk_checkpointable = False       # ``out`` grows; snapshot in memory

    def __init__(self, *, params, prefill: Callable, decode: Callable,
                 batch: dict, n_gen: int = DEFAULT_NEW_TOKENS):
        self.params = params
        self.prefill = prefill
        self.decode = decode
        self.batch = batch
        self.n_gen = n_gen

    def init_state(self):
        with span("repro.workload.prefill"):
            tok, pos, cache = self.prefill(self.params, self.batch,
                                           self.n_gen)
        return {"cache": cache, "tok": tok, "pos": pos, "out": []}

    def step(self, state, t):
        with span("repro.workload.host_copy"):
            out = state["out"] + [np.asarray(state["tok"])]
        with span("repro.workload.decode"):
            tok, pos, cache = self.decode(self.params, state["cache"],
                                          state["tok"], state["pos"])
        return {"cache": cache, "tok": tok, "pos": pos, "out": out}, None

    @staticmethod
    def tokens(state) -> np.ndarray:
        return np.concatenate(state["out"], axis=1)


class SimAppWorkload:
    """Run a simrt-style generator app (``step(rank, state, t)`` yielding
    communication ops) as a single sequential Workload.

    The composite state is {rank: rank_state}; ops are resolved in-process
    by a deterministic round-robin scheduler.  Fault tolerance happens at
    whole-application granularity in FTSession (the replica is a deep copy
    of all rank states), complementing simrt's message-level pipeline.

    The resolver here is intentionally the *failure-free* subset of the op
    protocol (no roles, no message logging, no mid-step kills) — simrt's
    SimRuntime remains the authoritative implementation of the full
    replicated protocol.  Collectives (allreduce/barrier/bcast/gather/
    reduce_scatter/alltoall) share their semantics with the replicated
    CollectiveEngine through ``repro.comm.ReferenceCollectives``, so the
    two resolvers cannot drift.
    """

    disk_checkpointable = False

    def __init__(self, app):
        self.app = app
        self.n = app.n_ranks

    def init_state(self):
        return {r: self.app.init_state(r) for r in range(self.n)}

    def check(self, states) -> Optional[float]:
        chk = getattr(self.app, "check", None)
        return chk(states) if chk else None

    # -- sequential op resolver ---------------------------------------------

    def step(self, states, t):
        from repro.comm import NOTHING, ReferenceCollectives

        gens = {r: self.app.step(r, states[r], t) for r in range(self.n)}
        inbox: Dict[int, deque] = {r: deque() for r in range(self.n)}
        pending: Dict[int, Optional[tuple]] = {r: None for r in range(self.n)}
        done: Dict[int, Any] = {}
        coll = ReferenceCollectives(self.n)

        def deliver(dst, src, tag, payload):
            inbox[dst].append((src, tag, copy.deepcopy(payload)))

        def take(rank, src, tag):
            box = inbox[rank]
            for i, (s, tg, p) in enumerate(box):
                if (src is None or s == src) and tg == tag:
                    del box[i]
                    return (s, p)
            return None

        def intake(rank, op):
            """Returns a pending descriptor, or None when non-blocking."""
            kind = op[0]
            if kind == "send":
                _, dst, tag, payload = op
                deliver(dst, rank, tag, payload)
                return None
            if kind == "exchange":
                _, outmap, tag = op
                for dst, payload in sorted(outmap.items()):
                    deliver(dst, rank, tag, payload)
                return ("exchange_wait", sorted(outmap.keys()), tag, {})
            if kind == "recv":
                return ("recv", op[1], op[2])
            if kind == "recv_any":
                return ("recv_any", op[1])
            return coll.post(rank, op)       # any registered collective

        def resolve(rank, pend):
            """Attempt to complete ``pend``; NOTHING when still blocked."""
            kind = pend[0]
            if kind == "recv":
                got = take(rank, pend[1], pend[2])
                return got[1] if got is not None else NOTHING
            if kind == "recv_any":
                got = take(rank, None, pend[1])
                return got if got is not None else NOTHING
            if kind == "exchange_wait":
                _, srcs, tag, got = pend
                for s in srcs:
                    if s not in got:
                        m = take(rank, s, tag)
                        if m is not None:
                            got[s] = m[1]
                return got if len(got) == len(srcs) else NOTHING
            if kind == "collective":
                return coll.resolve(rank, pend)
            raise ValueError(kind)

        while len(done) < self.n:
            progressed = False
            for r in range(self.n):
                if r in done:
                    continue
                if pending[r] is None:
                    send_val = None
                else:
                    send_val = resolve(r, pending[r])
                    if send_val is NOTHING:
                        continue
                    pending[r] = None
                try:
                    op = gens[r].send(send_val)
                except StopIteration as stop:
                    done[r] = stop.value if stop.value is not None \
                        else states[r]
                    progressed = True
                    continue
                pending[r] = intake(r, op)
                progressed = True
            if not progressed:
                blocked = {r: pending[r] for r in range(self.n)
                           if r not in done}
                raise RuntimeError(f"deadlock at step {t}: {blocked}")

        return {r: done[r] for r in range(self.n)}, None
