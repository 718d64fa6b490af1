"""FTSession: the workload-agnostic FT driver.

One loop, every workload: failure intake (injector -> interception ->
coordinators -> plan_recovery), strategy-owned step execution (replica
double-execution in replication modes), Young-Daly checkpointing, O(1)
promotion and elastic restart — producing a ``RunReport`` with a typed
event stream and the shared priced ``TimeBreakdown`` (repro.clock).

Time accounting: the session's *schedule* clock is step-indexed — it
advances exactly ``step_time_s`` per executed step, bitwise-identical to
the pre-clock ``vtime`` float loop, so time-indexed failure injectors and
the coordinator checkpoint timer replay identically across the refactor.
Everything else the run spends processor time on (priced checkpoint
pushes, restores, repair) is charged into the ``RunReport.time`` ledger
WITHOUT moving the schedule clock (``VirtualClock.charge(...,
advance=False)``); efficiency reads come from the ledger.

This generalizes the old FTTrainer (which survives as a thin shim in
repro.core.ft_runtime) and subsumes ReplicatedServer's hand-rolled cache
failover (repro.launch.serve now drives a DecodeWorkload through here).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional

from repro.clock import (TimeBreakdown, VirtualClock, injection_horizon,
                         pricing_from_ft)
from repro.configs.base import FTConfig
from repro.core.coordinator import ClusterTopology, CoordinatorSet
from repro.core.replica_map import ReplicaMap
from repro.core.shrink import plan_recovery
from repro.ft.injector import FailureInjector, as_injector
from repro.ft.strategy import FTStrategy, make_strategy
from repro.obs import span


@dataclass
class StepEvent:
    step: int
    kind: str
    detail: dict = field(default_factory=dict)


@dataclass
class RunReport:
    """Workload-agnostic run outcome (generalizes the old TrainReport)."""

    steps: int = 0
    metrics: List[Any] = field(default_factory=list)
    events: List[StepEvent] = field(default_factory=list)
    failures: int = 0
    promotions: int = 0
    restarts: int = 0
    ckpt_writes: int = 0
    rolled_back_steps: int = 0
    ckpt_s: float = 0.0
    restore_s: float = 0.0
    final_state: Any = None
    # the shared priced virtual-time ledger (repro.clock.TimeBreakdown —
    # the same class SimRuntime's RunResult.time carries): useful/rollback
    # from the step loop, ckpt_write/restore at the backend's priced cost,
    # repair from the recovery plans, comm from priced fan-out traffic
    time: TimeBreakdown = field(default_factory=TimeBreakdown)
    # observability (repro.obs, sessions built with obs=...): the run's
    # recorder and its end-of-run snapshot.  Deliberately NOT the
    # ``metrics`` field above — that list holds per-step workload scalars
    # (``.losses`` reads it); the obs registry is a separate surface
    obs: Any = None
    obs_metrics: Optional[dict] = None

    @property
    def losses(self) -> List[float]:
        """Scalar metrics as floats (train workloads emit the loss)."""
        return [float(m) for m in self.metrics if m is not None]

    @property
    def efficiency(self) -> float:
        """Useful fraction of the ledger (mirrors RunResult.efficiency)."""
        t = self.time.total
        return self.time.useful / t if t > 0 else 1.0


# Backwards-compatible alias: the old name for the train-specific report.
TrainReport = RunReport


class FTSession:
    """Drives any Workload under an FTStrategy with unified failure
    injection.

    On a real multi-pod mesh the replica slice is pod 1 and promotion is a
    VirtualMesh relabel; on this container both slices live on the same
    device and ``simulate_replica`` executes the replica step redundantly —
    preserving the exact semantics (bit-identical states, O(1) promotion)
    at 2x local cost, so FT-theorem tests can compare failure runs against
    failure-free runs for equality.
    """

    def __init__(self, *, ft: Optional[FTConfig] = None,
                 strategy: Optional[FTStrategy] = None,
                 injector=None,
                 ckpt_dir: Optional[str] = None,
                 n_logical_workers: int = 8,
                 workers_per_node: int = 4,
                 simulate_replica: bool = True,
                 step_time_s: float = 1.0,
                 allow_restart: bool = True,
                 replicable_ranks: Optional[int] = None,
                 obs=None):
        if strategy is None:
            strategy = make_strategy(ft or FTConfig())
        self.strategy = strategy.bind(self)
        self.ft = strategy.ft
        self.injector: FailureInjector = as_injector(injector)
        self.n_logical_workers = n_logical_workers
        self.workers_per_node = workers_per_node
        self.simulate_replica = simulate_replica and strategy.wants_replica
        self.step_time_s = step_time_s
        self.allow_restart = allow_restart
        # cap on how many logical ranks the replication degree applies to:
        # a workload with a placement-pinned unreplicated rank (the pool
        # master, serve's frontend) passes n-1 so replicas cover exactly
        # the worker ranks (replicas attach to ranks 0..m-1)
        self.replicable_ranks = replicable_ranks
        self.ckpt_dir = ckpt_dir
        self.ckpt = None
        # observability (repro.obs): obs=True builds a recorder, or pass
        # one in; obs=None (default) keeps every hook a falsy check
        self.obs = None
        if obs is not None:
            from repro.obs import ObsRecorder
            self.obs = ObsRecorder() if obs is True else obs
        self._init_fabric()

    def _init_fabric(self):
        n = self.n_logical_workers
        base = n if self.replicable_ranks is None \
            else max(0, min(self.replicable_ranks, n))
        m = self.strategy.n_replica_workers(base)
        self.rmap = ReplicaMap(n, m)
        self.topology = ClusterTopology(self.rmap.world_size,
                                        self.workers_per_node)
        self.coords = CoordinatorSet(self.topology, float("inf"))
        # cost-model injection (repro.clock.pricing): with
        # FTConfig.topology set, the checkpoint backend's transport prices
        # every push/fetch message, so C and R are measured, not assumed
        self.pricing = pricing_from_ft(self.ft, self.topology)
        self.clock = VirtualClock(cost_model=self.pricing.cost_model)

    def _start(self, workload, n_steps: int, rep: RunReport):
        """Everything before the loop: the fabric, the run's clock, the
        workload's first state, the strategy's start (its replica copy and
        checkpoint backend) and the injector's horizon."""
        self._init_fabric()                       # re-entrant sessions
        # the run's clock writes straight into the report's ledger
        clock = self.clock = VirtualClock(breakdown=rep.time,
                                          cost_model=self.pricing.cost_model)
        obs = self.obs
        if obs is not None:
            obs.bind_clock(clock)
            obs.set_world(self.rmap.n, self.rmap.m,
                          injector_kind=type(self.injector).__name__)
        # the strategy's on_start builds its CheckpointBackend
        # (repro.store.make_backend) and re-points the self.ckpt alias
        self.ckpt = None

        # session-aware workloads (repro.pool) build their transport over
        # this run's fabric before init_state constructs the world state
        bind = getattr(workload, "bind_session", None)
        if bind is not None:
            bind(self)
        state = workload.init_state()
        self.strategy.on_start(workload, state, rep)
        # horizon slack (shared formula, repro.clock.injection_horizon):
        # rollbacks extend virtual time past n_steps, so time-indexed
        # schedules get 2x headroom
        self.injector.prepare(
            injection_horizon(n_steps, self.step_time_s,
                              self.ft.ckpt_cost_s),
            self.rmap.alive())
        return state

    def _recover(self, workload, state, plan, step: int, fresh, rep):
        """Carry out one recovery plan; returns (state, step)."""
        obs = self.obs
        if obs is not None:
            obs.span(f"recovery.{plan.kind}", "recovery", step=step)
        rep.events.append(StepEvent(step, plan.kind,
                                    {"failed": list(fresh),
                                     "promotions": plan.promotions,
                                     "restore_backend":
                                         plan.restore_backend}))
        state, step = self.strategy.handle_plan(workload, state, plan, step,
                                                rep)
        # shrink + message recovery (paper Fig 9 'repair'); ledger-only:
        # the step-indexed schedule clock ignores it.  A workload that
        # repairs its own priced transport in apply_plan (repro.pool)
        # reports the measured per-message drain/replay traffic; everyone
        # else gets the planner's flat estimate
        repair_s = plan.repair_cost_s
        rtrans = getattr(workload, "repair_transport", None)
        if plan.kind == "promote" and rtrans is not None \
                and rtrans.cost_model is not None:
            repair_s = rtrans.take_comm_time()
        self.clock.charge("repair", repair_s, advance=False, label=plan.kind)
        if obs is not None:
            obs.end_span(resumed_step=step)
        return state, step

    # -- main loop -----------------------------------------------------------

    def run(self, workload, n_steps: int) -> RunReport:
        rep = RunReport()
        with span("repro.ft.start"):
            state = self._start(workload, n_steps, rep)
        clock, obs, strat = self.clock, self.obs, self.strategy

        step = 0
        done_through = 0                  # first step index not yet earned
        while step < n_steps:
            # --- failure intake (injector -> coordinators -> plan) ---------
            with span("repro.ft.intake"):
                events = self.injector.poll(step, clock.now)
            for ev in events:
                with span("repro.ft.intake"):
                    fresh = self.coords.intercept_failure(list(ev.workers))
                    fresh = [w for w in fresh if w not in self.rmap.dead]
                    if not fresh:
                        continue
                    rep.failures += len(fresh)
                    if obs is not None:
                        obs.metrics.inc("failures.kills.worker", len(fresh))
                        obs.mark("failure", "failure", workers=tuple(fresh),
                                 step=step)
                    # elastic-workload absorption: a task pool can take a
                    # fatal (unreplicated-cmp) death forward — retire the
                    # rank, reassign its work — instead of the world
                    # restart plan_recovery would be forced into
                    absorb = getattr(workload, "absorb_failures", None)
                    if absorb is not None:
                        state, fresh = absorb(state, list(fresh), step, rep)
                        if not fresh:
                            continue
                    self.rmap, plan = plan_recovery(
                        self.rmap, fresh,
                        last_ckpt_step=strat.last_ckpt_step,
                        current_step=step, store=strat.recovery_store())
                with span(f"repro.ft.recover.{plan.kind}"):
                    state, step = self._recover(workload, state, plan, step,
                                                fresh, rep)

            # --- one workload step (strategy may double-execute) -----------
            with span("repro.ft.step"):
                component = "rollback" if step < done_through else "useful"
                state, metrics = strat.step(workload, state, step)
                rep.metrics.append(metrics)
                if step >= done_through:
                    done_through = step + 1
                step += 1
                # the schedule clock advances by exactly step_time_s per
                # executed step (the pre-clock vtime trajectory, bitwise);
                # re-executed post-rollback steps are booked as 'rollback'
                clock.charge(component, self.step_time_s)
                # replica processor-seconds are an explicit ledger
                # component (the live replicated share of the machine, so
                # the charge tracks promotions/drops), not a folded
                # efficiency factor — fig10's overhead row and the Fig 9
                # split read it directly.  SimRuntime keeps its own
                # accounting; this is FTSession's.
                n_redundant = len(self.rmap.replicated_ranks())
                if n_redundant:
                    clock.charge("redundant",
                                 self.step_time_s * n_redundant / self.rmap.n,
                                 advance=False)
                rep.steps = step
                if obs is not None:
                    obs.on_step(step - 1, clock.now - self.step_time_s,
                                self.step_time_s, component == "rollback",
                                self.rmap.n)

                # --- coordinated checkpoint (primary timer) ----------------
                strat.maybe_checkpoint(workload, state, step, clock.now, rep)

        rep.final_state = state
        if obs is not None:
            store = strat.recovery_store()
            if store is not None:
                obs.sample_store(store)
                obs.sample_transport(store.transport)
            if obs.tracer is not None:
                obs.tracer.finish()
            rep.obs = obs
            rep.obs_metrics = obs.snapshot()
        return rep
