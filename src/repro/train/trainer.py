"""Training loop with fault tolerance and the resilience subsystem.

Features (DESIGN.md §5 + repro.resilience):
  * auto-resume: newest *verified* committed checkpoint + exact data-stream
    skip-ahead (corrupt/partial latest saves are skipped automatically)
  * periodic checkpointing (params + optimizer state + step) via atomic
    commit with per-leaf checksums
  * NaN/Inf guard: non-finite losses skip the update inside the jitted step
    (counted + logged) — rung 0 of the recovery ladder
  * health monitor (``resilience=...``): windowed loss-spike / blowup /
    dead-subspace detectors over in-jit signals, unified with the
    straggler :class:`StepTimeMonitor` into per-step
    :class:`~repro.resilience.health.HealthReport`s
  * recovery controller: skip → forced off-cycle projector refresh →
    rollback to an in-memory snapshot ring (params, optimizer state AND
    rank-policy controller extras, so floors/TTLs stay in sync) → restore
    of the last verified durable checkpoint; every event lands in
    :class:`TrainResult`
  * fault injection (``inject=...``): a seeded declarative
    :class:`~repro.resilience.inject.FaultPlan` arms gradient corruption,
    projector sabotage, checkpoint corruption and mid-save kills — every
    recovery path has a reproducible trigger
  * optional pjit over a mesh with the repo's sharding rules.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import statistics
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import RunConfig
from repro.core import OptimizerConfig, build_optimizer, resolve_rank_policy
from repro.core.rank_policy import RankPolicyController
from repro.data import DataConfig, build_stream
from repro.launch.steps import make_train_step
from repro.models.transformer import Model
from repro.sharding import named_sharding_tree, opt_state_sharding, use_mesh
from repro.telemetry import (
    JsonlSink,
    MemorySink,
    StdoutSink,
    Telemetry,
    TelemetryConfig,
)


class StepTimeMonitor:
    """Flags straggling steps: wall time > mean + z·std over a window."""

    def __init__(self, window: int = 50, z: float = 3.0, min_samples: int = 10):
        self.times = collections.deque(maxlen=window)
        self.z = z
        self.min_samples = min_samples
        self.flagged: list[tuple[int, float]] = []

    def record(self, step: int, dt: float) -> bool:
        is_straggler = False
        if len(self.times) >= self.min_samples:
            mu = statistics.fmean(self.times)
            sd = statistics.pstdev(self.times) or 1e-9
            if dt > mu + self.z * sd:
                is_straggler = True
                self.flagged.append((step, dt))
        self.times.append(dt)
        return is_straggler


@dataclasses.dataclass
class TrainResult:
    final_step: int
    losses: list[float]
    skipped_nonfinite: int
    straggler_steps: list[tuple[int, float]]
    resumed_from: Optional[int]
    # Resilience accounting (empty when the subsystem is off):
    health_events: list = dataclasses.field(default_factory=list)
    recovery_counts: dict = dataclasses.field(default_factory=dict)
    recovery_trace: list = dataclasses.field(default_factory=list)
    fault_log: list = dataclasses.field(default_factory=list)
    # Path of the run's events.jsonl (None when telemetry is off).
    events_path: Optional[str] = None
    # Wall seconds of each step run, device work included (the first holds
    # the step's trace and compile).
    step_times: list = dataclasses.field(default_factory=list)


class Trainer:
    def __init__(
        self,
        model: Model,
        opt_cfg: OptimizerConfig,
        run_cfg: RunConfig,
        data_cfg: DataConfig,
        mesh=None,
        microbatches: int = 1,
        optimizer=None,
        resilience=None,
        inject=None,
        telemetry=None,
        events_out: Optional[str] = None,
        profile_steps: Optional[str] = None,
    ):
        """``optimizer`` (a :class:`repro.core.api.Transform`) overrides the
        ``opt_cfg`` factory path — pass a hand-composed combinator chain
        (repro.core.combinators) to train with compositions the factory does
        not name, e.g. ``chain(combinators.clip_by_global_norm(1.0),
        lowrank(layerwise_unbias(scale_by_adam())), scale_by_lr(sched))``
        (the transform-valued clip lives in the combinators namespace; the
        same name in repro.core is the plain (grads, max_norm) function).

        ``resilience`` turns on the health monitor + recovery ladder: True
        or "" for defaults, a spec string ("ring=3,snapshot_every=5"), or a
        :class:`~repro.resilience.recovery.ResilienceConfig`.

        ``inject`` arms deterministic fault injection: a
        :class:`~repro.resilience.inject.FaultPlan` or its spec string
        ("grad_nan@5;refresh_zero@13;kill_save@20#3").

        ``telemetry`` turns on the run log (repro.telemetry): True or ""
        for defaults, a spec string ("every=10,stdout=0,memory=256"), or a
        :class:`~repro.telemetry.TelemetryConfig`.  One run then writes one
        schema-versioned ``events.jsonl`` (``events_out`` overrides the
        default ``<ckpt_dir>/events.jsonl``) holding step metrics, every
        health / recovery / fault / rank-policy / checkpoint event, and
        timing spans.  The console is always driven through the same bus —
        with telemetry off it degrades to the historical print lines.

        ``profile_steps="A:B"`` opens a ``jax.profiler`` trace window
        covering steps [A, B) (written under ``<ckpt_dir>/profile``)."""
        self.model = model
        self.opt_cfg = opt_cfg
        self.run = run_cfg
        self.data_cfg = data_cfg
        self.mesh = mesh
        self.microbatches = microbatches
        # ZeRO-style sharded projected state: family-stacked low-rank leaves
        # partition over the data axis (combinators.family_sharding routes
        # the projector refresh through the boundary all_gather).  Only
        # meaningful with a mesh and the fused family layout.
        self.shard_state = bool(
            getattr(opt_cfg, "shard_state", False)
            and opt_cfg.fuse_families and mesh is not None)
        self._family_axis = None
        if self.shard_state:
            names = mesh.axis_names
            self._family_axis = "data" if "data" in names else names[0]

        # --- telemetry bus (repro.telemetry) ---
        # The bus always exists: with telemetry off it carries only the
        # stdout pretty-printer (console output unchanged from the print()
        # era); enabling telemetry adds the JSONL sink — so console and
        # events.jsonl are two sinks of ONE record stream and can never
        # disagree.
        self.tele_cfg = TelemetryConfig.parse(telemetry)
        self.events_path = None
        sinks = []
        if self.tele_cfg is None or self.tele_cfg.stdout:
            sinks.append(StdoutSink())
        self.memory_sink = None
        if self.tele_cfg is not None:
            path = (events_out or self.tele_cfg.events
                    or os.path.join(run_cfg.ckpt_dir, "events.jsonl"))
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            sinks.append(JsonlSink(path))
            self.events_path = path
            if self.tele_cfg.memory:
                self.memory_sink = MemorySink(self.tele_cfg.memory)
                sinks.append(self.memory_sink)
        self.tele = Telemetry(sinks, run={
            "optimizer": opt_cfg.name, "rank": str(opt_cfg.rank),
            "period": opt_cfg.period, "seed": run_cfg.seed,
            "steps": run_cfg.steps, "telemetry": self.tele_cfg is not None,
        })
        self._profile_window = None
        self._profiling = False
        if profile_steps:
            a, _, b = str(profile_steps).partition(":")
            self._profile_window = (int(a), int(b))

        self.ckpt = CheckpointManager(run_cfg.ckpt_dir,
                                      keep=run_cfg.keep_ckpts,
                                      telemetry=self.tele)
        self.monitor = StepTimeMonitor()

        # --- resilience wiring (repro.resilience) ---
        from repro.resilience import FaultPlan, HealthMonitor
        from repro.resilience.recovery import ResilienceConfig

        if resilience is None or resilience is False:
            self.resilience = None
            self.health = None
        else:
            self.resilience = ResilienceConfig.parse(resilience)
            self.health = HealthMonitor(self.resilience,
                                        step_monitor=self.monitor)
        self.fault_plan = (FaultPlan.parse(inject) if isinstance(inject, str)
                           else inject)
        self._fault_gate = (self.fault_plan.gate()
                            if self.fault_plan is not None else None)
        self.recovery = None  # built per train() run

        # Rank policy (repro.core.rank_policy): rank is a shape in JAX, so a
        # policy-driven rank change is a host-side event between steps — the
        # controller migrates the optimizer state and we re-jit (bounded by
        # the policy ladder via the per-map jit cache below).  Only active on
        # the factory path; a hand-passed `optimizer` owns its own rank.
        self.rank_ctrl: Optional[RankPolicyController] = None
        if optimizer is None:
            policy = resolve_rank_policy(opt_cfg)
            if policy is not None:
                self.rank_ctrl = RankPolicyController(
                    policy,
                    lambda m: build_optimizer(opt_cfg, rank_map=m),
                    period=opt_cfg.period, default_rank=opt_cfg.rank,
                    # A rank migration changes state shapes, so the sharding
                    # must be re-derived from the MIGRATED state and
                    # re-applied — otherwise the first spectral decision
                    # silently de-shards (or mis-shards) the optimizer state
                    # under a mesh.
                    reshard=(self._reshard_opt_state
                             if mesh is not None else None),
                )
                optimizer = self.rank_ctrl.transform()
        self._jit_cache: dict = {}
        self._last_step_args = None  # for lower_step() after a run
        self._has_probes: Optional[bool] = None
        self._set_optimizer(
            optimizer if optimizer is not None else build_optimizer(opt_cfg)
        )

    # ------------------------------------------------------------- setup

    def _set_optimizer(self, optimizer):
        self.optimizer = optimizer
        step_fn = make_train_step(
            self.model, optimizer, grad_clip=self.run.grad_clip,
            microbatches=self.microbatches,
            fault_gate=self._fault_gate,
            extra_metrics=self.resilience is not None,
        )
        if self.shard_state:
            from repro.core.combinators import family_sharding

            mesh, axis = self.mesh, self._family_axis
            inner_step = step_fn

            def step_fn(*args, _inner=inner_step):
                # entered at TRACE time: the fused lowrank path sees the
                # context and emits the sharded (all_gather-at-boundary)
                # projector refresh for shardable families
                with family_sharding(mesh, axis):
                    return _inner(*args)

        self._step_fn = step_fn

    def init_state(self):
        key = jax.random.PRNGKey(self.run.seed)
        if self.mesh is None:
            params = self.model.init(key)
            return params, self.optimizer.init(params)
        # On a mesh, each device builds only its own shards: eager init
        # would first place the whole model and state on device 0.
        params_abs = jax.eval_shape(self.model.init, key)
        params = jax.jit(self.model.init, out_shardings=named_sharding_tree(
            params_abs, self.mesh))(key)
        opt_abs = jax.eval_shape(self.optimizer.init, params_abs)
        opt_state = jax.jit(self.optimizer.init, out_shardings=opt_state_sharding(
            opt_abs, self.mesh, family_axis=self._family_axis))(params)
        return params, opt_state

    def _jit_step(self, params, opt_state):
        # One jitted step per rank assignment; without a controller there is
        # exactly one entry, with one the cache is bounded by the ladder.
        key = self.rank_ctrl.current_map if self.rank_ctrl else None
        cached = self._jit_cache.get(key)
        if cached is not None:
            return cached
        n_in = 4 if self._fault_gate is not None else 3
        if self.mesh is None:
            jitted = jax.jit(self._step_fn, donate_argnums=(0, 1))
        else:
            psh = named_sharding_tree(params, self.mesh)
            osh = opt_state_sharding(opt_state, self.mesh,
                                     family_axis=self._family_axis)
            jitted = jax.jit(
                self._step_fn,
                in_shardings=(psh, osh) + (None,) * (n_in - 2),
                out_shardings=(psh, osh, None),
                donate_argnums=(0, 1),
            )
        self._jit_cache[key] = jitted
        return jitted

    def lower_step(self, params=None, opt_state=None):
        """Lower the current jitted step on abstract arguments.

        Without arguments they are shaped like those of the last step the
        run took, so ``.compile()`` returns the run's executable from JAX's
        in-memory cache and its HLO and memory can be read.  With ``params``
        (abstract or concrete) they are shaped like ``params``, ``opt_state``
        (default: its abstract init) and one batch.  Committed arrays keep
        their shardings: on what :meth:`init_state` returns, this is the
        program the run's first step will find compiled."""
        if params is None:
            args = self._last_step_args
        else:
            from repro.resilience.inject import FaultGate

            if opt_state is None:
                opt_state = jax.eval_shape(self.optimizer.init, params)
            batch = {"tokens": jax.ShapeDtypeStruct(
                (self.data_cfg.global_batch // max(self.data_cfg.num_hosts, 1),
                 self.data_cfg.seq_len), jnp.int32)}
            args = (params, opt_state, batch)
            if self._fault_gate is not None:
                args = args + (FaultGate.disarmed(),)
        args = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None)
            if isinstance(x, jax.Array) else x, args)
        with use_mesh(self.mesh):  # the context the run's calls trace in
            return self._jit_step(*args[:2]).lower(*args)

    # ------------------------------------------------------------- helpers

    def _profile(self, step: int) -> None:
        """Opt-in ``jax.profiler`` trace window: start at step A, stop at
        step B (``profile_steps="A:B"``).  Best-effort — profiler failures
        must never take down training."""
        a, b = self._profile_window
        try:
            if step == a and not self._profiling:
                trace_dir = os.path.join(self.run.ckpt_dir, "profile")
                jax.profiler.start_trace(trace_dir)
                self._profiling = True
                self.tele.event("profile", f"profiler: trace started -> "
                                f"{trace_dir}", step=step)
            elif step == b and self._profiling:
                jax.profiler.stop_trace()
                self._profiling = False
                self.tele.event("profile", "profiler: trace stopped",
                                step=step)
                self._profile_window = None
        except Exception as e:  # pragma: no cover - platform dependent
            self.tele.event("profile", f"profiler: unavailable "
                            f"({type(e).__name__}: {e})", step=step,
                            severity="warn")
            self._profile_window = None
            self._profiling = False

    def _stop_profile(self) -> None:
        """Close a still-open trace window (run ended before step B)."""
        if not self._profiling:
            return
        self._profiling = False
        try:
            jax.profiler.stop_trace()
            self.tele.event("profile", "profiler: trace stopped at run end")
        except Exception:  # pragma: no cover - never started
            pass

    def _reshard_opt_state(self, opt_state):
        """Re-derive the optimizer-state sharding from the live (possibly
        just-migrated) state and re-apply it — the mesh counterpart of
        ``opt_state_sharding`` at jit time.  No-op without a mesh."""
        if self.mesh is None:
            return opt_state
        return jax.device_put(
            opt_state,
            opt_state_sharding(opt_state, self.mesh,
                               family_axis=self._family_axis))

    def _restore_shardings(self, params, opt_state):
        """Shardings to re-apply on checkpoint restore (None off-mesh):
        checkpoints hold host-gathered full arrays, so the restore must put
        every leaf back on its derived sharding — including the family-
        stacked ZeRO layout — or the first step pays a full reshard."""
        if self.mesh is None:
            return None
        return (named_sharding_tree(params, self.mesh),
                opt_state_sharding(opt_state, self.mesh,
                                   family_axis=self._family_axis))

    def _ckpt_extra(self) -> Optional[dict]:
        if self.rank_ctrl is None:
            return None
        return {"rank_policy": self.rank_ctrl.state_dict()}

    def _save(self, step: int, params, opt_state) -> None:
        """Checkpoint save with the fault plan's kill hook and post-commit
        corruption events attached (no-ops without a plan)."""
        observer = (self.fault_plan.save_observer(step)
                    if self.fault_plan is not None else None)
        self.ckpt.save(step, (params, opt_state), extra=self._ckpt_extra(),
                       observer=observer)
        if self.fault_plan is not None:
            for ev in self.fault_plan.apply_ckpt_events(self.ckpt.dir, step):
                self.tele.event(
                    "fault", f"fault-injection: {ev.kind} on the "
                    f"step-{step} checkpoint", step=step, severity="warn",
                    kind=ev.kind)

    def _load_checkpoint(self, step: int):
        """Restore params/opt_state at ``step``, rebuilding the rank-policy
        controller (and therefore the state template's shapes) from the
        saved extras first — the restore rung of the recovery ladder."""
        if self.rank_ctrl is not None:
            extra = self.ckpt.read_extra(step)
            if "rank_policy" in extra:
                self.rank_ctrl.load_state_dict(extra["rank_policy"])
                self._set_optimizer(self.rank_ctrl.transform())
        params, opt_state = self.init_state()
        (params, opt_state), _ = self.ckpt.restore(
            step, (params, opt_state),
            shardings=self._restore_shardings(params, opt_state))
        return params, opt_state

    def _gather_probes(self, opt_state, step: int) -> Optional[dict]:
        """Spectrum probes for the health monitor's captured-energy floor —
        gathered only on refresh-cadence steps and only when the optimizer
        actually stores probes (zero cost otherwise)."""
        if (self.resilience is None or not self.resilience.probe_health
                or self.opt_cfg.period <= 0
                or step % self.opt_cfg.period != 0):
            return None
        from repro.core import find_lowrank_states
        from repro.core.rank_policy import gather_probes

        if self._has_probes is None:
            self._has_probes = any(
                st.probes is not None
                for st in find_lowrank_states(opt_state))
        return gather_probes(opt_state) if self._has_probes else None

    # ------------------------------------------------------------- loop

    def train(self, steps: Optional[int] = None) -> TrainResult:
        from repro.resilience import poison_projectors
        from repro.resilience.inject import FaultGate
        from repro.resilience.recovery import (
            RecoveryController,
            SnapshotRing,
            force_refresh,
        )

        steps = steps or self.run.steps
        stream = build_stream(self.data_cfg)
        res, plan, health = self.resilience, self.fault_plan, self.health
        ring = SnapshotRing(res.ring) if res is not None else None
        recov = RecoveryController(res) if res is not None else None
        self.recovery = recov

        start_step, resumed_from = 0, None
        latest = None
        if self.run.resume:
            latest = self.ckpt.latest_verified_step()
            newest = self.ckpt.latest_step()
            if newest is not None and newest != latest:
                self.tele.event(
                    "checkpoint",
                    f"checkpoint: newest committed step {newest} failed "
                    f"verification — resuming from last verified "
                    f"{latest}", severity="warn", action="resume_fallback")
        if latest is not None and self.rank_ctrl is not None:
            # The controller state determines the optimizer-state SHAPES, so
            # it must be rebuilt from the saved extras before the restore
            # template exists — this is what makes resume exact across a
            # rank change.
            extra = self.ckpt.read_extra(latest)
            if "rank_policy" in extra:
                self.rank_ctrl.load_state_dict(extra["rank_policy"])
                self._set_optimizer(self.rank_ctrl.transform())
        params, opt_state = self.init_state()
        try:
            # One-line static audit of the step we are about to jit:
            # launches/step, projected-state bytes, abstract signature hash.
            # Purely abstract (trace only) and best-effort — a failure here
            # must never block training.
            from repro.analysis import audit_summary

            self.tele.event("audit", audit_summary(self.optimizer, params,
                                                   name=self.opt_cfg.name))
            if self.tele_cfg is not None:
                # Runtime launch-counter cross-check against the PR 6
                # closed-form model — the RA-style assertion, as an event.
                from repro.telemetry.instrument import launch_crosscheck

                xc = launch_crosscheck(self.optimizer, params,
                                       name=self.opt_cfg.name)
                self.tele.event(
                    "launch_crosscheck",
                    f"audit[{self.opt_cfg.name}]: launch cross-check "
                    f"{'ok' if xc['ok'] else 'MISMATCH'} "
                    f"(traced {sum(xc['traced'].values())}/step)",
                    severity="info" if xc["ok"] else "warn",
                    expected=xc["expected"], traced=xc["traced"],
                    unmodeled=xc["unmodeled"])
                if xc["fallbacks"]:
                    # what kernel_fallback_ms times on the device: the ops
                    # whose shapes the kernels refuse, run as jnp instead
                    self.tele.event(
                        "dispatch_fallback",
                        f"audit[{self.opt_cfg.name}]: "
                        f"{sum(f['calls'] for f in xc['fallbacks'])} "
                        f"dispatched call(s)/step run jnp for a shape the "
                        f"kernels refuse: " + ", ".join(
                            f"{f['op']}{tuple(f['shape'])}"
                            for f in xc["fallbacks"]),
                        fallbacks=xc["fallbacks"])
            if self.mesh is not None:
                # Mesh run: also verify the jitted step's donation wiring on
                # the lowered module (donated params/opt_state must alias
                # outputs — losing it double-buffers the whole model).
                from repro.analysis import donation_findings, parse_main_args

                opt_state0 = jax.eval_shape(self.optimizer.init, params)
                infos = parse_main_args(self.lower_step(params).as_text())
                n_donate = (len(jax.tree_util.tree_leaves(params))
                            + len(jax.tree_util.tree_leaves(opt_state0)))
                self.tele.event(
                    "audit", f"audit[{self.opt_cfg.name}]: mesh donation "
                    f"{sum(a.aliased for a in infos)}/{n_donate} args "
                    f"alias outputs")
                for f in donation_findings(
                        infos, n_params=len(jax.tree_util.tree_leaves(params)),
                        n_opt=len(jax.tree_util.tree_leaves(opt_state0)),
                        where=self.opt_cfg.name):
                    self.tele.event("audit", "  " + f.format(),
                                    severity="warn")
        except Exception as e:  # pragma: no cover - diagnostics only
            self.tele.event("audit", f"audit[{self.opt_cfg.name}]: "
                            f"unavailable ({type(e).__name__}: {e})",
                            severity="warn")
        if latest is not None:
            (params, opt_state), _ = self.ckpt.restore(
                latest, (params, opt_state),
                shardings=self._restore_shardings(params, opt_state),
            )
            start_step, resumed_from = latest, latest
            stream.resume(start_step)  # exact skip-ahead

        step_jit = self._jit_step(params, opt_state)

        loss_by_step: dict[int, float] = {}
        step_times: list[float] = []
        skipped = 0
        step = start_step
        tele, tcfg = self.tele, self.tele_cfg
        gamma_tracker = None
        if tcfg is not None:
            from repro.telemetry.instrument import (
                GammaSlotTracker,
                lowrank_family_metrics,
            )

            gamma_tracker = GammaSlotTracker()
        with use_mesh(self.mesh):
            while step < steps:
                if self._profile_window is not None:
                    self._profile(step)
                refresh_step = (self.opt_cfg.period > 0
                                and step % self.opt_cfg.period == 0)
                t0 = time.time()
                # The host phases are profiler annotations only (no record):
                # the names a device trace's idle gaps are attributed to.
                with tele.span("step", step=step + 1,
                               kind="refresh" if refresh_step else "steady"):
                    if self.rank_ctrl is not None:
                        with tele.annotate("rank_migration"):
                            opt_state, changed = self.rank_ctrl.maybe_update(
                                opt_state, params
                            )
                            if changed:
                                self._set_optimizer(self.rank_ctrl.transform())
                                step_jit = self._jit_step(params, opt_state)
                        if changed:
                            tele.record_span("rank_migration",
                                             time.time() - t0, step=step)
                            tele.event("rank_policy",
                                       f"rank-policy -> "
                                       f"{self.rank_ctrl.current_map}",
                                       step=step,
                                       map=str(self.rank_ctrl.current_map))
                    if plan is not None:
                        for ev in plan.state_events(step):
                            opt_state = poison_projectors(opt_state, ev.kind)
                            tele.event("fault", f"fault-injection: {ev.kind}",
                                       step=step, severity="warn",
                                       kind=ev.kind)
                    with tele.annotate("feed"):
                        tokens = jnp.asarray(next(stream))
                    if self._fault_gate is not None:
                        ev = plan.grad_event(step)
                        if ev is not None:
                            tele.event("fault", f"fault-injection: {ev.kind}",
                                       step=step, severity="warn",
                                       kind=ev.kind)
                        fault = (FaultGate.armed(ev) if ev is not None
                                 else FaultGate.disarmed())
                        args = (params, opt_state, {"tokens": tokens}, fault)
                    else:
                        args = (params, opt_state, {"tokens": tokens})
                    self._last_step_args = args
                    with tele.annotate("dispatch"):
                        new_params, new_opt, metrics = step_jit(*args)
                    with tele.annotate("block"):
                        jax.block_until_ready((new_params, new_opt))
                    with tele.annotate("loss"):
                        loss = float(metrics["loss"])
                    params, opt_state = new_params, new_opt
                    applied = bool(metrics["update_applied"])
                    dt = time.time() - t0
                if applied:
                    loss_by_step[step] = loss
                else:
                    # the step itself zeroed the update (in-jit NaN guard)
                    skipped += 1
                step_times.append(dt)
                if tcfg is not None and (step + 1) % tcfg.every == 0:
                    tele.metric(step + 1, "loss", loss)
                    tele.metric(step + 1, "grad_norm",
                                float(metrics["grad_norm"]))
                if tcfg is not None and refresh_step:
                    for rec in lowrank_family_metrics(opt_state):
                        fam = rec["family"]
                        tele.metric(step + 1, "rank", rec["rank"], family=fam)
                        tele.metric(step + 1, "energy", rec["energy"],
                                    family=fam)
                        for k in ("drift", "bias"):
                            if k in rec:
                                tele.metric(step + 1, k, rec[k], family=fam)
                    slots = gamma_tracker.observe(opt_state)
                    if slots:
                        tele.event(
                            "gamma_slots",
                            f"gamma-slots: {len(slots)} leaves tracked",
                            step=step + 1, leaves=slots)

                if health is not None:
                    report = health.observe(
                        step, loss=loss, applied=applied,
                        grad_norm=float(metrics.get(
                            "grad_norm_raw", metrics["grad_norm"])),
                        # collapse detection watches the low-rank-leaf
                        # restricted norm: embeddings/norms keep updating
                        # through a dead subspace and would mask it globally
                        update_norm=(float(metrics["update_norm_lowrank"])
                                     if "update_norm_lowrank" in metrics
                                     else None),
                        dt=dt,
                        probes=self._gather_probes(opt_state, step),
                    )
                    for e in report.events:
                        tele.event("health",
                                   f"health[{e.severity}] "
                                   f"{e.kind}: {e.detail}", step=step,
                                   severity=e.severity, kind=e.kind)
                    action = recov.decide(report)
                    if action.kind == "refresh":
                        opt_state = force_refresh(opt_state,
                                                  self.opt_cfg.period)
                        recov.record(action, target=step + 1)
                        health.reset()
                        tele.event("recovery", "recovery: forced off-cycle "
                                   "projector refresh", step=step,
                                   severity="warn", action="refresh")
                    elif action.kind in ("rollback", "restore"):
                        target, kind = None, action.kind
                        if action.kind == "rollback":
                            snap = ring.pop_latest()
                            if snap is not None:
                                params, opt_state = ring.restore(snap)
                                if (self.rank_ctrl is not None and snap.extra
                                        and "rank_policy" in snap.extra):
                                    self.rank_ctrl.load_state_dict(
                                        snap.extra["rank_policy"])
                                    self._set_optimizer(
                                        self.rank_ctrl.transform())
                                target = snap.step
                        if target is None:
                            # no snapshot (or explicit restore rung): fall
                            # back to the last verified durable checkpoint
                            ck = self.ckpt.latest_verified_step()
                            if ck is not None:
                                params, opt_state = self._load_checkpoint(ck)
                                target, kind = ck, "restore"
                        recov.record(dataclasses.replace(action, kind=kind)
                                     if kind != action.kind else action,
                                     target=target)
                        if target is not None:
                            tele.event("recovery",
                                       f"recovery: {kind} -> step {target}",
                                       step=step, severity="warn",
                                       action=kind, target=target)
                            stream.resume(target)
                            loss_by_step = {k: v for k, v in
                                            loss_by_step.items()
                                            if k < target}
                            step = target
                            step_jit = self._jit_step(params, opt_state)
                            health.reset()
                            continue
                        tele.event("recovery",
                                   f"recovery: {action.kind} requested but "
                                   f"nothing restorable — continuing",
                                   step=step, severity="warn",
                                   action=action.kind)
                else:
                    self.monitor.record(step, dt)

                if (res is not None and res.snapshot_every
                        and (step + 1) % res.snapshot_every == 0
                        and (health is None or report.status == "ok")):
                    ring.add(step + 1, params, opt_state,
                             extra=self._ckpt_extra())

                if self.run.ckpt_every and (step + 1) % self.run.ckpt_every == 0:
                    with tele.span("ckpt_save", step=step + 1):
                        self._save(step + 1, params, opt_state)
                if self.run.log_every and (step + 1) % self.run.log_every == 0:
                    tele.event("log", f"loss {loss:.4f}", step=step + 1)
                step += 1

        # Final save — unless the loop's periodic save already committed
        # this exact step (a duplicate would also clobber any post-commit
        # state, e.g. injected corruption under test).
        if not (self.run.ckpt_every and steps % self.run.ckpt_every == 0
                and steps > start_step):
            with self.tele.span("ckpt_save", step=steps):
                self._save(steps, params, opt_state)
        self._stop_profile()
        if self.tele_cfg is not None:
            self.tele.emit_counters(steps)
        return TrainResult(
            final_step=steps,
            losses=[v for _, v in sorted(loss_by_step.items())],
            skipped_nonfinite=skipped,
            straggler_steps=self.monitor.flagged,
            resumed_from=resumed_from,
            health_events=([e.to_json() for e in health.events]
                           if health is not None else []),
            recovery_counts=dict(recov.counts) if recov is not None else {},
            recovery_trace=list(recov.trace) if recov is not None else [],
            fault_log=list(plan.log) if plan is not None else [],
            events_path=self.events_path,
            step_times=step_times,
        )
