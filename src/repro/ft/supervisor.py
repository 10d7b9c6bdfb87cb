"""Fault-tolerant elastic training supervisor.

The repo has had every *piece* of the paper's reconfigurability story —
straggler detection (ft/straggler.py), rate-weighted stage re-cutting
(core/scheduler.rebalance -> core/partition.partition_layers), cheap
uneven cuts at runtime (dist/pipeline.pad_pipeline_params), elastic
mesh reformation + re-sharded restore (ft/elastic.py), and async atomic
checkpoints (ft/checkpoint.py) — but nothing that CLOSED the loop.
:class:`TrainSupervisor` is that loop:

    step -> per-stage heartbeats -> HeartbeatMonitor -> HealthEvents
         -> "slow"?         re-cut boundaries with the rate-weighted
            DP, re-pad the LIVE state (pure gathers, no checkpoint
            round-trip), re-jit, continue — zero steps lost
         -> "device_loss"?  reform the mesh from the survivors, restore
            the latest checkpoint re-sharded onto the new topology,
            recompute the batch schedule from the restored step,
            resume — at most ``ckpt_every`` steps lost
         -> "nan"?          roll back to the last checkpoint and SKIP
            the poisoned batch on replay
         -> checkpoint write died?  the atomic-rename design means
            nothing on disk is corrupt: sweep the torn .tmp and retry

Detection is observation-driven (PR 9): after each step the supervisor
emits one heartbeat per pipeline stage into a
:class:`repro.ft.health.HeartbeatMonitor` — carrying the stage's
service time, the step's device enumeration and a loss-finiteness flag
— and reacts to the typed ``HealthEvent``s that come back.  The fault
plan now poisons what the beats REPORT (``FaultPlan.devices_visible``
shrinks the enumeration, ``nan_at`` poisons the loss) rather than
steering the supervisor directly, so the detect half of the loop is
the code a real deployment would run.  The one exception is
``ckpt_crash``, which arms a write-path hook: its detection was always
the save exception, recorded as a ``ckpt_retry`` event.

Checkpoints are written in the CANONICAL (unpadded) layer layout, so a
restore can target any later boundary vector or stage count — the
padded stage layout is a property of the current plan, not of the
weights.  Faults come from a seeded :class:`repro.ft.faults.FaultPlan`
(or from reality); per-stage service times are modelled as the measured
lockstep step time apportioned by the planner's per-stage cost shares,
with injected slowdowns both recorded into the monitor and *slept*, so
recovery metrics are real wall-clock quantities.

Data replay is exact: batches are a pure function of (seed, data
index), the supervisor tracks skipped indices, so a run recovered from
step N consumes exactly the batches the fault-free run would — which is
what makes "recovered final loss == fault-free final loss" a testable
gate (benchmarks/ft_bench.py).
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.pipeline import SyntheticLM
from repro.dist.sharding import param_specs
from repro.ft import checkpoint as ckpt_mod
from repro.ft.elastic import make_mesh_for
from repro.ft.faults import one_shot_write_fault
from repro.ft.health import HeartbeatMonitor
from repro.ft.straggler import StragglerMonitor
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig, OptState
from repro.train.step import (
    init_pipeline_state,
    init_state,
    make_pipeline_train_step,
    make_train_step,
    pad_pipeline_state,
    repad_pipeline_state,
    unpad_pipeline_state,
)


@dataclasses.dataclass
class RecoveryEvent:
    """One supervisor reaction, with its real cost."""

    kind: str  # "recut" | "rescale" | "rollback" | "ckpt_retry"
    step: int  # opt step at which the reaction happened
    steps_lost: int = 0  # opt steps re-run because of the fault
    recovery_s: float = 0.0  # wall-clock from detection to resumed
    detail: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SupervisorResult:
    losses: list  # per-step loss of the final (recovered) trajectory
    step_times: list  # effective per-step seconds (faults included)
    events: list  # RecoveryEvents in order
    boundaries_history: list  # pipeline cut vectors over the run
    final_loss: float = float("nan")

    def events_of(self, kind: str) -> list:
        return [e for e in self.events if e.kind == kind]


class TrainSupervisor:
    """Closed-loop fault-tolerant trainer (see module docstring).

    ``strategy='pipeline'`` (the full story: per-stage monitoring and
    straggler-driven live re-cuts on a ``(1, stages)`` mesh, one stage
    per device) or any SPMD strategy (``fused``/...), where the
    checkpointed recovery paths still apply but re-cutting does not —
    for SPMD the elastic restart IS the mitigation, as ft/straggler.py
    documents.
    """

    def __init__(self, cfg, opt_cfg: AdamWConfig | None = None, *,
                 steps: int, seq: int = 32, batch: int = 8,
                 strategy: str = "pipeline", schedule: str = "1f1b",
                 microbatches: int = 0, grad_accum: int = 1,
                 ckpt_dir: str | None = None, ckpt_every: int = 0,
                 keep: int = 2, fault_plan=None, devices=None, data=None,
                 monitor: StragglerMonitor | None = None,
                 recut_cooldown: int | None = None,
                 dtype=jnp.float32, seed: int = 0,
                 max_inject_sleep_s: float = 1.0, max_rollbacks: int = 8,
                 verbose: bool = False):
        self.cfg = cfg
        self.opt_cfg = opt_cfg or AdamWConfig(lr=1e-3, warmup_steps=5,
                                              total_steps=steps)
        self.steps = steps
        self.seq, self.batch = seq, batch
        self.strategy, self.schedule = strategy, schedule
        self._mb_arg, self.grad_accum = microbatches, grad_accum
        self.plan = fault_plan
        self.devices = list(devices if devices is not None else jax.devices())
        self.data = data or SyntheticLM(cfg.vocab, seq, batch, seed=seed)
        self.monitor = monitor or StragglerMonitor(window=8, threshold=1.3,
                                                   min_samples=4)
        # detection runs through heartbeats: each stage beats once per
        # step and the monitor's typed events drive the handlers below
        self.health = HeartbeatMonitor(straggler=self.monitor)
        self.recut_cooldown = (recut_cooldown if recut_cooldown is not None
                               else self.monitor.min_samples)
        self.dtype, self.seed = dtype, seed
        self.max_inject_sleep_s = max_inject_sleep_s
        self.max_rollbacks = max_rollbacks
        self.verbose = verbose

        self.ckpt = (ckpt_mod.AsyncCheckpointer(ckpt_dir, keep=keep)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        # canonical (unpadded) state template for topology-free restore
        self._like = jax.eval_shape(
            lambda k: init_state(k, cfg, dtype), jax.random.PRNGKey(seed)
        )
        self.events: list[RecoveryEvent] = []
        self.boundaries = None
        self.boundaries_history: list = []
        self.skipped: set[int] = set()  # poisoned data indices
        self._losses: dict[int, float] = {}
        self._times: dict[int, float] = {}
        self._recut_ready = 0
        self._unit_costs = None
        self._setup()

    # -- build / rebuild ----------------------------------------------------

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(f"[supervisor] {msg}", flush=True)

    def _setup(self, canonical=None, padded=None) -> None:
        """(Re)build mesh, step function, shardings and state for the
        CURRENT device set + boundaries.  ``canonical`` installs a
        restored unpadded state; ``padded`` installs an already-padded
        live state (the re-cut path); neither -> fresh init."""
        cfg = self.cfg
        if self.strategy == "pipeline":
            from repro.core.autotune import tune_microbatches
            from repro.core.graph import config_graph
            from repro.core.partition import layer_costs, stage_costs
            from repro.core.placement import pipeline_boundaries
            from repro.dist.pipeline import pipeline_units

            self.stages = len(self.devices)
            units = pipeline_units(cfg)
            if self.stages > units:
                raise ValueError(
                    f"{self.stages} devices > {units} cut units; shrink the "
                    "device set or deepen the model")
            if self.boundaries is None:
                self.boundaries = pipeline_boundaries(cfg, self.seq,
                                                      self.stages)
            self.microbatches = self._mb_arg or tune_microbatches(
                self.stages, self.batch, self.schedule)
            if self.batch % self.microbatches:
                raise ValueError(f"batch {self.batch} % microbatches "
                                 f"{self.microbatches} != 0")
            self.mesh = make_mesh((1, self.stages), ("data", "model"),
                                  devices=self.devices)
            step_fn = make_pipeline_train_step(
                cfg, self.opt_cfg, self.mesh,
                num_microbatches=self.microbatches,
                boundaries=self.boundaries, schedule=self.schedule,
            )
            if padded is None:
                if canonical is None:
                    padded = init_pipeline_state(
                        jax.random.PRNGKey(self.seed), cfg, self.boundaries,
                        self.dtype)
                else:
                    padded = pad_pipeline_state(canonical, cfg,
                                                self.boundaries)
            state = padded
            if self._unit_costs is None:
                self._unit_costs = layer_costs(config_graph(cfg, self.seq))
            if len(self._unit_costs) == self.boundaries[-1]:
                costs = stage_costs(self._unit_costs, self.boundaries)
            else:  # hybrid cut units (groups): shares by unit count
                b = self.boundaries
                costs = [float(b[k + 1] - b[k]) for k in range(self.stages)]
            total = sum(costs) or 1.0
            self._stage_shares = tuple(c / total for c in costs)
            self.boundaries_history.append(tuple(self.boundaries))
        else:
            self.stages = 1
            self.mesh = make_mesh_for(self.devices)
            step_fn = make_train_step(cfg, self.opt_cfg,
                                      grad_accum=self.grad_accum)
            state = (canonical if canonical is not None
                     else init_state(jax.random.PRNGKey(self.seed), cfg,
                                     self.dtype))
            self._stage_shares = (1.0,)

        pspecs = param_specs(state["params"], self.mesh, self.strategy)
        sspecs = {"params": pspecs,
                  "opt": OptState(mu=pspecs, nu=pspecs, step=P()),
                  "step": P()}
        self.sshard = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), sspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.state = jax.tree.map(jax.device_put, state, self.sshard)
        self.jitted = jax.jit(step_fn, in_shardings=(self.sshard, None),
                              out_shardings=(self.sshard, None))
        # warm the compile cache so fault timing and the monitor never
        # see compilation time as a (gigantic, spurious) straggler
        with self.mesh:
            _, warm = self.jitted(self.state, self.data.batch(0))
        jax.block_until_ready(warm["loss"])
        self._reset_health()

    def _reset_health(self) -> None:
        """Post-reconfiguration amnesia: stale intervals/timings must not
        describe the new plan, and the shrunken device enumeration must
        not read as a SECOND loss on the next beat."""
        self.health.reset()
        self.health.expect_devices(0, len(self.devices))

    def _install_state(self, canonical) -> None:
        """Pad (if pipelined) + device_put a canonical state without
        rebuilding the step function (topology unchanged)."""
        if self.strategy == "pipeline":
            canonical = pad_pipeline_state(canonical, self.cfg,
                                           self.boundaries)
        self.state = jax.tree.map(jax.device_put, canonical, self.sshard)

    # -- checkpointing ------------------------------------------------------

    def _canonical_state(self):
        if self.strategy == "pipeline":
            return unpad_pipeline_state(self.state, self.cfg,
                                        self.boundaries)
        return self.state

    def _save(self, step: int) -> None:
        if self.ckpt is None:
            return
        st = self._canonical_state()
        try:
            self.ckpt.save(st, step)
        except Exception as e:
            # a previous background write died; atomic rename means the
            # on-disk latest is still intact — sweep the torn .tmp,
            # record it, and retry this save
            swept = ckpt_mod.sweep_tmp(self.ckpt.root)
            self.events.append(RecoveryEvent(
                "ckpt_retry", step,
                detail={"error": repr(e), "swept": swept}))
            self._log(f"checkpoint write failed ({e!r}); swept {swept}, "
                      "retrying")
            self.ckpt.save(st, step)

    def _load_latest(self):
        """(canonical_state, step) from the newest complete checkpoint,
        or None.  A pending failed write surfaces here and is recorded —
        it cannot have produced a corrupt checkpoint."""
        if self.ckpt is None:
            return None
        try:
            self.ckpt.wait()
        except Exception as e:
            ckpt_mod.sweep_tmp(self.ckpt.root)
            self._log(f"pending checkpoint write had failed: {e!r}")
        step = ckpt_mod.latest_step(self.ckpt.root)
        if step is None:
            return None
        import os

        state = ckpt_mod.restore(
            os.path.join(self.ckpt.root, f"step_{step}"), self._like)
        return state, step

    # -- fault handling -----------------------------------------------------

    def _handle_kill(self, lost: int, t: int) -> int:
        """Device loss (a ``device_loss`` HealthEvent): reform the mesh
        from the survivors, restore the latest checkpoint re-sharded
        onto it, resume from its step."""
        t0 = time.perf_counter()
        if len(self.devices) - lost < 1:
            raise RuntimeError("device loss removed the last device")
        before = len(self.devices)
        self.devices = self.devices[: before - lost]
        loaded = self._load_latest()
        canonical, rstep = loaded if loaded else (None, 0)
        self.boundaries = None  # re-cut for the shrunken stage count
        self._setup(canonical=canonical)
        self.events.append(RecoveryEvent(
            "rescale", t, steps_lost=t - rstep,
            recovery_s=time.perf_counter() - t0,
            detail={"devices": f"{before}->{len(self.devices)}",
                    "restored_step": rstep, "stages": self.stages,
                    "boundaries": tuple(self.boundaries or ())}))
        self._log(f"device loss at step {t}: {before}->{len(self.devices)} "
                  f"devices, resumed from step {rstep}")
        return rstep

    def _handle_rollback(self, t: int, data_index: int) -> int:
        """Non-finite loss: back to the last checkpoint, skip the batch."""
        t0 = time.perf_counter()
        self.skipped.add(data_index)
        loaded = self._load_latest()
        if loaded:
            canonical, rstep = loaded
            self._install_state(canonical)
        else:  # no checkpoint yet: restart from initialization
            rstep = 0
            self._setup()
        self._reset_health()
        self.events.append(RecoveryEvent(
            "rollback", t, steps_lost=t - rstep,
            recovery_s=time.perf_counter() - t0,
            detail={"skipped_data_index": data_index,
                    "restored_step": rstep}))
        self._log(f"non-finite loss at step {t}: rolled back to {rstep}, "
                  f"skipping batch {data_index}")
        return rstep

    def _maybe_recut(self, t: int, stragglers: list, rates: dict) -> None:
        """Persistent straggler (a ``slow`` HealthEvent) -> rate-weighted
        DP re-cut of the LIVE pipeline (no rollback: the re-pad is a
        pure gather).  ``stragglers``/``rates`` come from the event's
        detail — the monitor's verdict over the beats it has seen."""
        if self.strategy != "pipeline" or self.stages < 2:
            return
        if t < self._recut_ready:
            return
        from repro.core.scheduler import recut_boundaries

        t0 = time.perf_counter()
        new = tuple(recut_boundaries(self.cfg, self.seq, self.stages,
                                     rates))
        old = tuple(self.boundaries)
        if new == old:
            # plan already compensates the observed rates (or the rates
            # are still averaging in pre-fault history): check again
            # next step rather than thrash
            self._recut_ready = t + 1
            return
        live = repad_pipeline_state(self.state, self.cfg, old, new)
        self.boundaries = new
        self._setup(padded=live)
        self._recut_ready = t + self.recut_cooldown
        self.events.append(RecoveryEvent(
            "recut", t, steps_lost=0,
            recovery_s=time.perf_counter() - t0,
            detail={"stragglers": stragglers,
                    "rates": {n: round(r, 3) for n, r in rates.items()},
                    "old": old, "new": new}))
        self._log(f"straggler(s) {stragglers} at step {t}: re-cut "
                  f"{old} -> {new}")

    def _observe(self, t: int, t_compute: float, loss: float) -> list:
        """Emit one heartbeat per pipeline stage for step ``t`` and
        return the monitor's HealthEvents.  The fault plan poisons the
        observations here — slowdown factors scale the reported service
        time, pending kills shrink the reported device enumeration, a
        poisoned batch shows up as a non-finite loss flag — and the
        monitor, not the plan, decides what they mean.

        Per-unit-work service time: a slow BOARD is slow regardless of
        how many layers it holds, so the beat carries t * factor —
        cut-imbalance never masquerades as a straggler."""
        now = time.monotonic()
        factors = self.plan.slowdowns_at(t) if self.plan else {}
        visible = (self.plan.devices_visible(self.devices, t)
                   if self.plan else self.devices)
        bad = not math.isfinite(loss)
        events = []
        for s in range(self.stages):
            events += self.health.beat(
                s, t, now=now,
                step_s=t_compute * factors.get(s, 1.0),
                # stage 0 is the coordinator's view of the cluster; the
                # loss is a collective output, so one stage flags it
                devices=len(visible) if s == 0 else None,
                nan=bad if s == 0 else False)
        return events

    def _inject_sleep(self, t: int, t_compute: float) -> float:
        """Sleep the wall-clock surcharge an active slowdown would cost
        the lockstep pipe, so recovery metrics stay real wall-clock
        quantities.  Returns the effective step seconds."""
        factors = self.plan.slowdowns_at(t) if self.plan else {}
        if not factors:
            return t_compute
        shares = self._stage_shares
        base = max(shares) * self.stages * t_compute
        slow = max(
            shares[s] * self.stages * t_compute * factors.get(s, 1.0)
            for s in range(self.stages)
        )
        extra = min(max(0.0, slow - base), self.max_inject_sleep_s)
        if extra > 0:
            time.sleep(extra)
        return t_compute + extra

    # -- the loop -----------------------------------------------------------

    def _data_index(self, t: int) -> int:
        d = t
        for s in sorted(self.skipped):
            if s <= d:
                d += 1
        return d

    def run(self) -> SupervisorResult:
        t = int(self.state["step"])
        if self.ckpt is not None and ckpt_mod.latest_step(self.ckpt.root) is None:
            self._save(t)  # step-0 anchor so the first rollback has a target
        rollbacks = 0
        while t < self.steps:
            if self.plan is not None and self.ckpt is not None:
                # write-path injection (detection is the save exception
                # itself, recorded as a ckpt_retry event in _save)
                cev = self.plan.take_ckpt_crash(t)
                if cev is not None:
                    n_leaves = len(jax.tree.leaves(self._like))
                    one_shot_write_fault(self.plan.crash_leaf_index(n_leaves))
                    self._log(f"armed checkpoint-write crash at step {t}")

            d_idx = self._data_index(t)
            batch = self.data.batch(d_idx)
            t0 = time.perf_counter()
            with self.mesh:
                new_state, metrics = self.jitted(self.state, batch)
            loss = float(metrics["loss"])  # blocks until the step is done
            t_compute = time.perf_counter() - t0
            if self.plan is not None and self.plan.nan_at(d_idx):
                loss = float("nan")  # injected numerically-poisoned batch

            # observation, then reaction: the step's heartbeats report
            # what happened and the monitor's events say what it means
            events = self._observe(t, t_compute, loss)
            lost = sum(e.detail["lost"] for e in events
                       if e.kind == "device_loss")
            if lost:
                # the step's output ran on the pre-loss topology —
                # discard it and restore from the checkpoint
                t = self._handle_kill(lost, t)
                continue
            if any(e.kind == "nan" for e in events):
                rollbacks += 1
                if rollbacks > self.max_rollbacks:
                    raise RuntimeError(
                        f"{rollbacks} rollbacks: loss is persistently "
                        "non-finite, refusing to loop forever")
                t = self._handle_rollback(t, d_idx)
                continue

            self.state = new_state
            t_eff = self._inject_sleep(t, t_compute)
            self._losses[t] = loss
            self._times[t] = t_eff
            t += 1
            slow = [e for e in events if e.kind == "slow"]
            if slow:
                # the step's LAST slow event carries the freshest rates
                # (every stage's sample for this step is in by then)
                self._maybe_recut(t - 1, slow[-1].detail["stragglers"],
                                  slow[-1].detail["rates"])
            if (self.ckpt is not None and self.ckpt_every
                    and t % self.ckpt_every == 0):
                self._save(t)

        if self.ckpt is not None:
            try:
                self.ckpt.wait()
            except Exception as e:
                ckpt_mod.sweep_tmp(self.ckpt.root)
                self.events.append(RecoveryEvent(
                    "ckpt_retry", t, detail={"error": repr(e)}))
        losses = [self._losses[i] for i in range(self.steps)]
        times = [self._times[i] for i in range(self.steps)]
        return SupervisorResult(
            losses=losses, step_times=times, events=self.events,
            boundaries_history=self.boundaries_history,
            final_loss=losses[-1] if losses else float("nan"),
        )
