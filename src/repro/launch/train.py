"""Training driver: any assigned arch, any FT mode, on the current devices.

By default it trains *reduced* configs (the CPU tests and examples use
it); ``--full`` trains the published config.  Everything, the replica's
copy of the state included, lives on JAX's default device: one TPU in
``chip_smoke.py``.

The FT loop is the unified ``repro.ft`` API: ``build_workload`` wraps the
jitted train step as a ``TrainWorkload``; ``build_session`` pairs it with an
``FTSession``; ``build_trainer`` keeps the legacy FTTrainer surface.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 50 --ft-mode combined --mtbf 30 --kill 12:0 --kill 30:1
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import RunConfig, get_arch
from repro.configs.base import FTConfig, ModelConfig, ShapeConfig
from repro.core.ft_runtime import FTTrainer
from repro.data import DataConfig, TokenSource
from repro.ft import FTSession, TrainWorkload
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.step_fns import make_train_step
from repro.optim import adamw


def train_run_config(cfg: ModelConfig, *, batch: int, seq: int,
                     lr: float = 1e-3) -> RunConfig:
    """The RunConfig the train driver compiles its step from."""
    shape = ShapeConfig("cli", seq_len=seq, global_batch=batch, kind="train")
    return RunConfig(model=cfg, shape=shape, remat="none",
                     seq_chunk=min(seq, 512), kv_block=min(seq, 128),
                     learning_rate=lr)


def build_workload(arch: str, *, reduced: bool = True, batch: int = 8,
                   seq: int = 128, seed: int = 0,
                   lr: float = 1e-3) -> TrainWorkload:
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    step_fn, model = make_train_step(
        train_run_config(cfg, batch=batch, seq=seq, lr=lr))
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    data = TokenSource(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))

    def batch_fn(step):
        b = data.batch_at(step)
        if cfg.family == "audio":
            b["frames"] = jnp.zeros((batch, cfg.n_frames, cfg.d_model),
                                    jnp.bfloat16)
        if cfg.family == "vlm":
            b["image_embeds"] = jnp.zeros(
                (batch, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
        return b

    def init_state():
        params = model.init(jax.random.key(seed))
        return {"params": params, "opt": adamw.init(params)}

    def train_step(state, b):
        params, opt, loss = jitted(state["params"], state["opt"], b)
        return {"params": params, "opt": opt}, loss

    return TrainWorkload(train_step=train_step, init_state=init_state,
                         batch_fn=batch_fn)


def build_session(arch: str, *, reduced: bool = True, batch: int = 8,
                  seq: int = 128, ft: FTConfig, ckpt_dir=None,
                  kill_schedule=None, injector=None, seed: int = 0,
                  n_logical_workers: int = 8, workers_per_node: int = 4,
                  lr: float = 1e-3):
    """The new-API entry point: returns (FTSession, TrainWorkload)."""
    workload = build_workload(arch, reduced=reduced, batch=batch, seq=seq,
                              seed=seed, lr=lr)
    if injector is None:
        injector = dict(kill_schedule or {})
    session = FTSession(ft=ft, ckpt_dir=ckpt_dir, injector=injector,
                        n_logical_workers=n_logical_workers,
                        workers_per_node=workers_per_node)
    return session, workload


def build_trainer(arch: str, *, reduced: bool = True, batch: int = 8,
                  seq: int = 128, ft: FTConfig, ckpt_dir=None,
                  kill_schedule=None, seed: int = 0,
                  n_logical_workers: int = 8, lr: float = 1e-3) -> FTTrainer:
    """Legacy surface: an FTTrainer shim over build_session's plumbing."""
    workload = build_workload(arch, reduced=reduced, batch=batch, seq=seq,
                              seed=seed, lr=lr)
    return FTTrainer(train_step=workload.train_step,
                     init_state=workload.init_state_fn,
                     batch_fn=workload.batch_fn, ft=ft, ckpt_dir=ckpt_dir,
                     n_logical_workers=n_logical_workers,
                     kill_schedule=kill_schedule)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ft-mode", default="combined",
                    choices=["none", "checkpoint", "replication", "combined"])
    ap.add_argument("--mtbf", type=float, default=1e9)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=float, default=0.0)
    ap.add_argument("--kill", action="append", default=[],
                    help="step:worker[,worker...] failure injection")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    kills = {}
    for spec in args.kill:
        s, ws = spec.split(":")
        kills[int(s)] = [int(w) for w in ws.split(",")]

    ft = FTConfig(mode=args.ft_mode, mtbf_s=args.mtbf,
                  ckpt_interval_s=args.ckpt_interval)
    session, workload = build_session(
        args.arch, reduced=args.reduced, batch=args.batch, seq=args.seq,
        ft=ft, ckpt_dir=args.ckpt_dir, kill_schedule=kills, seed=args.seed)
    # repro: allow[wallclock] -- genuine wall measurement
    t0 = time.perf_counter()
    rep = session.run(workload, args.steps)
    jax.block_until_ready(rep.final_state)
    # repro: allow[wallclock] -- genuine wall measurement
    dt = time.perf_counter() - t0
    print(f"arch={args.arch} mode={args.ft_mode} steps={rep.steps} "
          f"loss[first,last]=({rep.losses[0]:.4f},{rep.losses[-1]:.4f}) "
          f"failures={rep.failures} promotions={rep.promotions} "
          f"restarts={rep.restarts} ckpts={rep.ckpt_writes} "
          f"rolled_back={rep.rolled_back_steps} wall={dt:.1f}s")
    if not (np.isfinite(rep.losses).all()):
        print("ERROR: non-finite loss", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
