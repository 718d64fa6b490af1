"""Chip smoke: the fault-tolerant train and serve paths, once, on one TPU.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time) through the entry points a user calls: ``repro.launch.train``'s
``build_session`` and ``repro.launch.serve``'s ``ReplicatedServer``, at the
full published width of the models, with random weights from fixed seeds.

Phases, in order.  A failed check raises and the exit code is non-zero:

  device  JAX's first device must be a TPU; there is no CPU fallback.
  train   xlstm-350m, full config, batch 2 x seq 1024.  A ``combined`` run
          of 8 steps with a computational-slice kill at step 3 (replica
          promotion) and a pair death at step 6 (restart from the in-memory
          checkpoint), then a failure-free ``none`` run on the same jitted
          step.  Losses finite, one promotion, one restart, and final
          params bitwise equal: the FT theorem.
  serve   xlstm-350m (batch 8, prompt 1024) and whisper-tiny (batch 8,
          prompt 448), 32 new tokens each, with a kill at token 8 and
          without.  Tokens identical, one promotion.

The lines before the last are smoke numbers, not benchmark numbers: compile
seconds (trace, lowering and backend compile, persistent-cache hits
counted), wall seconds per step call on a host clock stopped after
``block_until_ready``, and the device's ``peak_bytes_in_use``.  The last
line is the JSON verdict, printed only when every check has passed.
"""
from __future__ import annotations

import gc
import json
import os
import statistics
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import FTConfig  # noqa: E402
from repro.ft import FTSession  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import ReplicatedServer  # noqa: E402
from repro.launch.train import build_session  # noqa: E402

TRAIN_ARCH = "xlstm-350m"
TRAIN_STEPS = 8
# tests/test_system.py's schedule: worker 0 (rank 0's computational copy)
# dies -> its replica, worker 8, is promoted; then worker 8 dies -> rank 0
# has no copy left -> restart from the last checkpoint
TRAIN_KILLS = {3: [0], 6: [8]}
# (arch, batch, prompt_len) at full width; 32 new tokens stay inside the
# KV cache's 64 spare slots
SERVE_CELLS = [("xlstm-350m", 8, 1024), ("whisper-tiny", 8, 448)]
SERVE_GEN, SERVE_KILL_AT = 32, 8

_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip smoke check failed: {what}")


class CompileLog:
    """Compile time and persistent-cache hits, from JAX's own monitoring
    events; ``take()`` returns what accrued since the last call."""

    def __init__(self):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.trace_s = self.backend_s = 0.0
        self.backend_compiles = self.cache_hits = 0

    def _duration(self, event, secs, **_):
        if event in _TRACE_EVENTS:
            self.trace_s += secs
        elif event == _BACKEND_EVENT:
            self.backend_s += secs
            self.backend_compiles += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.trace_s + self.backend_s,
               "backend_compile_s": self.backend_s,
               "backend_compiles": self.backend_compiles,
               "persistent_cache_hits": self.cache_hits}
        self._reset()
        return out


class CallTimer:
    """Wraps a jitted step: each call's host wall time, stopped after
    ``block_until_ready``.  The first call compiles; the rest are warm."""

    def __init__(self, fn):
        self.fn = fn
        self.seconds = []

    def __call__(self, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.fn(*args))
        self.seconds.append(time.perf_counter() - t0)
        return out

    def summary(self) -> dict:
        return {"calls": len(self.seconds), "first_call_s": self.seconds[0],
                "warm_call_median_s": statistics.median(self.seconds[1:])}


def peak_bytes() -> int:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def report(phase: str, numbers: dict) -> None:
    print(f"[smoke numbers, not a benchmark] {phase}: "
          f"{json.dumps(numbers, sort_keys=True)}", flush=True)


def device_phase() -> dict:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip smoke needs a TPU, but JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind}): no TPU found")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: kind={device['kind']} count={device['count']}",
          flush=True)
    return device


def _run_timed(session, workload, steps):
    t0 = time.perf_counter()
    rep = session.run(workload, steps)
    jax.block_until_ready(rep.final_state)
    return rep, time.perf_counter() - t0


def _host_leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def train_phase(compiles: CompileLog, *, arch=TRAIN_ARCH, reduced=False,
                batch=2, seq=1024, steps=TRAIN_STEPS) -> None:
    session, workload = build_session(
        arch, reduced=reduced, batch=batch, seq=seq,
        ft=FTConfig(mode="combined", ckpt_interval_s=3),
        kill_schedule=TRAIN_KILLS)
    timer = workload.train_step = CallTimer(workload.train_step)
    faulty, faulty_wall = _run_timed(session, workload, steps)
    check(faulty.steps == steps, f"combined run took {faulty.steps} steps")
    check(bool(np.isfinite(faulty.losses).all()),
          f"non-finite combined loss: {faulty.losses}")
    check(faulty.promotions == 1, f"promotions={faulty.promotions}")
    check(faulty.restarts == 1, f"restarts={faulty.restarts}")
    faulty_params = _host_leaves(faulty.final_state["params"])
    combined = {"wall_s": faulty_wall, "steps": faulty.steps,
                "promotions": faulty.promotions,
                "restarts": faulty.restarts, "ckpts": faulty.ckpt_writes,
                "ckpt_s": faulty.ckpt_s, "restore_s": faulty.restore_s,
                "rolled_back": faulty.rolled_back_steps,
                "loss_last": faulty.losses[-1], **timer.summary(),
                **compiles.take()}
    # the replica and the checkpoint store hold device and host copies of
    # the state: drop them before the next run shares the chip
    del faulty, session
    gc.collect()

    timer.seconds = []
    clean, clean_wall = _run_timed(FTSession(ft=FTConfig(mode="none")),
                                   workload, steps)
    check(bool(np.isfinite(clean.losses).all()),
          f"non-finite none loss: {clean.losses}")
    clean_params = _host_leaves(clean.final_state["params"])
    check(len(clean_params) == len(faulty_params), "param tree changed")
    for a, b in zip(faulty_params, clean_params):
        check(a.dtype == b.dtype and a.shape == b.shape
              and a.tobytes() == b.tobytes(),
              "FT theorem: combined-run params differ from the "
              "failure-free run")
    none = {"wall_s": clean_wall, "steps": clean.steps,
            "wall_per_step_s": clean_wall / clean.steps,
            "loss_last": clean.losses[-1], **timer.summary(),
            **compiles.take()}
    del clean
    gc.collect()
    report(f"train {arch} batch={batch} seq={seq}",
           {"combined": combined, "none": none,
            "params_bitwise_equal": True, "peak_bytes_in_use": peak_bytes()})


def serve_phase(compiles: CompileLog, arch: str, batch: int,
                prompt_len: int, *, reduced=False, n_gen=SERVE_GEN,
                kill_at=SERVE_KILL_AT) -> None:
    srv = ReplicatedServer(arch, reduced=reduced, batch=batch,
                           prompt_len=prompt_len)
    timer = srv.decode = CallTimer(srv.decode)
    prompts = np.random.default_rng(0).integers(
        0, srv.cfg.vocab_size, (batch, prompt_len), dtype=np.int32)
    t0 = time.perf_counter()
    faulty = srv.generate(prompts, n_gen, kill_at=kill_at)
    faulty_wall = time.perf_counter() - t0
    killed = {"wall_s": faulty_wall, **timer.summary(), **compiles.take()}
    check(srv.promotions == 1, f"promotions={srv.promotions}")

    timer.seconds = []
    t0 = time.perf_counter()
    clean = srv.generate(prompts, n_gen)
    clean_wall = time.perf_counter() - t0
    check(clean.shape == (batch, n_gen), f"token shape {clean.shape}")
    check(bool(((clean >= 0) & (clean < srv.cfg.vocab_size)).all()),
          "token id outside the vocabulary")
    check(np.array_equal(faulty, clean),
          "failover changed the generated tokens")
    check(srv.promotions == 1, f"promotions={srv.promotions} after clean run")
    report(f"serve {arch} batch={batch} prompt={prompt_len} gen={n_gen}",
           {"kill_at": killed,
            "clean": {"wall_s": clean_wall,
                      "wall_per_token_s": clean_wall / n_gen,
                      **timer.summary(), **compiles.take()},
            "promotions": srv.promotions, "tokens_identical": True,
            "peak_bytes_in_use": peak_bytes()})
    del srv
    gc.collect()


def main() -> int:
    device = device_phase()
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    compiles = CompileLog()
    train_phase(compiles)
    for arch, batch, prompt_len in SERVE_CELLS:
        serve_phase(compiles, arch, batch, prompt_len)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
