"""Serving driver: batched prefill + decode with replication failover.

The paper's replication story applied to inference, now driven through the
unified ``repro.ft`` API: the decode loop is a ``DecodeWorkload`` whose
state carries the KV cache; ``FTSession`` owns replica management, so when
the computational slice fails mid-generation the replica's cache is CURRENT
and failover costs one promotion (no prefill replay).  ReplicatedServer
itself contains no replication or promotion logic anymore.

Request batches reach the serving rank through ``BatchFanout``: a
``ReplicaTransport`` bcast from an unreplicated frontend rank, so the
computational copy arrives cmp→cmp and the replica copy over the §5
intercomm fill-in — serving inherits the exact logging/replay/dedup path
training messages use instead of relying on whole-app state copies to
carry the batch to the replica.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
      --batch 4 --prompt-len 32 --gen 16 --kill-at 8
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.clock import VirtualClock, pricing_from_ft
from repro.comm import CollectiveEngine, NOTHING, ReplicaTransport
from repro.configs import RunConfig, get_arch
from repro.configs.base import FTConfig, ModelConfig, ShapeConfig
from repro.core.coordinator import ClusterTopology
from repro.core.replica_map import ReplicaMap
from repro.ft import DecodeWorkload, FTSession, StepKillInjector
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.step_fns import (greedy_decode, greedy_prefill,
                                   make_decode_step, make_prefill_step)
from repro.obs import span


class BatchFanout:
    """Routes each request batch over a ReplicaTransport bcast.

    Two logical ranks: rank 0 is the serving rank (replicated when the
    server replicates), rank 1 the unreplicated frontend holding the
    batch.  A ``bcast`` rooted at the frontend delivers the batch cmp→cmp
    to the serving computational worker and — because the destination is
    replicated and the source is not — over the intercomm fill-in to the
    replica worker, logged with send-IDs like any training message.  Both
    received copies must be bitwise identical; the cmp copy feeds the
    workload.

    With ``ft.topology`` set the fan-out traffic is α‑β-priced and charged
    into the fan-out's ``VirtualClock`` (repro.clock); ``generate`` merges
    it into the run's ``RunReport.time.comm`` — serving batches spend time
    in the same ledger training messages do.
    """

    SERVE_RANK, FRONTEND_RANK = 0, 1

    def __init__(self, replication: bool, ft: FTConfig = None, obs=None):
        self.rmap = ReplicaMap(2, 1 if replication else 0)
        cluster = ClusterTopology(self.rmap.world_size, 1)
        pricing = pricing_from_ft(ft or FTConfig(), cluster)
        self.clock = VirtualClock(cost_model=pricing.cost_model)
        self.transport = ReplicaTransport(self.rmap, 2,
                                          cost_model=pricing.cost_model)
        self.engine = CollectiveEngine(self.transport)
        # observability (repro.obs): the fan-out traffic counts into the
        # same recorder the serving session uses — per-band counters via
        # the transport observer, per-link heat when priced
        self.obs = obs
        if obs is not None:
            self.transport.add_observer(obs)
            self.engine.obs = obs
            if pricing.cost_model is not None and obs.links is None:
                self.transport.link_usage = \
                    obs.attach_links(pricing.cost_model)
        self.eps = {w: self.transport.register(w) for w in self.rmap.alive()}
        self.fanouts = 0

    def fan_out(self, batch: np.ndarray) -> np.ndarray:
        """One bcast round; returns the batch as received by the serving
        computational worker."""
        with span("repro.serve.fanout"):
            self.engine.begin_step()
            step = self.fanouts
            pend = {
                w: self.engine.post(
                    ep,
                    ("bcast",
                     batch if self.rmap.role_of(w)[1] == self.FRONTEND_RANK
                     else None,
                     self.FRONTEND_RANK),
                    step)
                for w, ep in self.eps.items()}
            got = {}
            while len(got) < len(pend):
                for w, ep in self.eps.items():
                    if w in got:
                        continue
                    out = self.engine.resolve(ep, pend[w])
                    if out is not NOTHING:
                        got[w] = out
            cmp_w = self.rmap.cmp[self.SERVE_RANK]
            rep_w = self.rmap.rep[self.SERVE_RANK]
            if rep_w is not None:
                np.testing.assert_array_equal(got[cmp_w], got[rep_w])
            self.fanouts += 1
            # priced fan-out traffic -> the clock's comm ledger (0.0 unpriced)
            self.clock.charge_comm(self.transport)
            return got[cmp_w]


def serve_run_config(cfg: ModelConfig, *, batch: int,
                     prompt_len: int) -> RunConfig:
    """The RunConfig the server compiles its prefill and decode from."""
    shape = ShapeConfig("serve", seq_len=prompt_len, global_batch=batch,
                        kind="prefill")
    return RunConfig(model=cfg, shape=shape, remat="none",
                     kv_block=min(prompt_len, 128),
                     seq_chunk=min(prompt_len, 512))


class ReplicatedServer:
    """Model plumbing (prefill/decode jits, params) + a thin ``generate``
    that delegates all fault tolerance to FTSession."""

    def __init__(self, arch: str, *, reduced: bool = True, batch: int = 4,
                 prompt_len: int = 32, replication: bool = True,
                 seed: int = 0, topology: str = None, obs=None):
        cfg = get_arch(arch)
        if reduced:
            cfg = cfg.reduced()
        self.cfg = cfg
        run = serve_run_config(cfg, batch=batch, prompt_len=prompt_len)
        prefill, self.model = make_prefill_step(run)
        decode, _ = make_decode_step(run)
        # greedy sampling inside the jitted programs: one dispatch a token
        self.prefill = jax.jit(greedy_prefill(prefill))
        self.decode = jax.jit(greedy_decode(decode), donate_argnums=(1,))
        self.params = self.model.init(jax.random.key(seed))
        self.replication = replication
        self.batch = batch
        self.topology = topology
        # one recorder shared by the fan-out transport and every serving
        # session (obs=True builds it; None keeps everything unwired)
        self.obs = None
        if obs is not None:
            from repro.obs import ObsRecorder
            self.obs = ObsRecorder() if obs is True else obs
        self.fanout = BatchFanout(replication,
                                  ft=FTConfig(mode="none", topology=topology),
                                  obs=self.obs)
        self.failures = 0
        self.promotions = 0
        self.batches = 0                 # generate calls, the spans' serial
        self.last_report = None

    def _extras(self, batch_tokens):
        b = {"tokens": batch_tokens}
        if self.cfg.family == "audio":
            b["frames"] = jnp.zeros(
                (self.batch, self.cfg.n_frames, self.cfg.d_model),
                jnp.bfloat16)
        if self.cfg.family == "vlm":
            b["image_embeds"] = jnp.zeros(
                (self.batch, self.cfg.n_image_tokens, self.cfg.d_model),
                jnp.bfloat16)
        return b

    def workload(self, prompt_tokens: np.ndarray) -> DecodeWorkload:
        """The decode loop as a Workload (also used by tests directly)."""
        return DecodeWorkload(params=self.params, prefill=self.prefill,
                              decode=self.decode,
                              batch=self._extras(jnp.asarray(prompt_tokens)))

    def session(self, kill_at: int = -1) -> FTSession:
        """One logical serving rank; replication adds its replica slice.
        ``allow_restart=False``: without a replica or checkpoint a mid-decode
        death is fatal (a restart would need a prefill replay)."""
        mode = "replication" if self.replication else "none"
        injector = StepKillInjector({kill_at: [0]}) if kill_at >= 0 else None
        with span("repro.serve.session"):
            return FTSession(ft=FTConfig(mode=mode, topology=self.topology),
                             injector=injector,
                             n_logical_workers=1, workers_per_node=1,
                             allow_restart=False, obs=self.obs)

    def generate(self, prompt_tokens: np.ndarray, n_gen: int,
                 kill_at: int = -1) -> np.ndarray:
        """Greedy decode; kill_at k kills the computational slice after k
        generated tokens (replication failover or abort).  The batch
        reaches the serving rank over the transport bcast (logged,
        deduped), not by Python reference."""
        self.batches += 1
        with span("repro.serve.generate", batch=self.batches):
            session = self.session(kill_at)
            comm0 = self.fanout.clock.breakdown.comm
            prompt_tokens = self.fanout.fan_out(np.asarray(prompt_tokens))
            try:
                rep = session.run(self.workload(prompt_tokens), n_gen)
            except RuntimeError:
                # fatal (unrecoverable) kill: still record the failure
                self.failures += 1
                raise
            # the batch fan-out's priced traffic lands in the same ledger as
            # the run's own time (0.0 without a topology)
            rep.time.comm += self.fanout.clock.breakdown.comm - comm0
            self.last_report = rep
            self.failures += rep.failures
            self.promotions += rep.promotions
            return DecodeWorkload.tokens(rep.final_state)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kill-at", type=int, default=-1)
    ap.add_argument("--no-replication", action="store_true")
    ap.add_argument("--topology", default=None,
                    help="price fan-out + session time over this topo graph "
                         "(flat|fattree|dragonfly|torus3d)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    srv = ReplicatedServer(args.arch, reduced=args.reduced, batch=args.batch,
                           prompt_len=args.prompt_len,
                           replication=not args.no_replication,
                           topology=args.topology)
    prompts = np.random.default_rng(0).integers(
        0, srv.cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32)
    # repro: allow[wallclock] -- genuine wall measurement
    t0 = time.perf_counter()
    toks = srv.generate(prompts, args.gen, kill_at=args.kill_at)
    # repro: allow[wallclock] -- genuine wall measurement
    dt = time.perf_counter() - t0
    print(f"arch={args.arch} generated={toks.shape} "
          f"failures={srv.failures} promotions={srv.promotions} "
          f"wall={dt:.1f}s tok/s={toks.size / dt:.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
