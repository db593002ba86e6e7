"""Fault-aware training runner: the Oobleck methodology applied to a
training step (detection -> quarantine -> reroute -> continue).

Per step:
  * the executable for the current FaultSignature comes from the
    Dispatcher (compile-per-signature, LRU; the no-fault program is fully
    fused — the paper's queue bypass);
  * StepGuard checks loss/grad finiteness; a trip restores the last
    checkpoint and re-runs (transient) or quarantines a stage (persistent,
    two consecutive trips);
  * CanaryChecker sweeps each Viscosity stage's HW path against its SW
    oracle every ``canary_every`` steps (cheap; catches silent wrong-value
    faults that never produce NaNs);
  * StragglerWatchdog tracks step times (multi-replica deployments feed
    per-replica times; single-process runs feed synthetic replica ids).

Checkpoints are async + checksummed; restore is elastic (any mesh).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp

from repro import optim
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core.fault import (CanaryChecker, FaultClassifier,
                              FaultSignature, FaultState, ProbationPolicy,
                              StepGuard, StragglerWatchdog)
from repro.core.oobleck import Dispatcher
from repro.core.routing import FleetPlan, RoutingPlan
from repro.core.stage import Stage
from repro.data.pipeline import SyntheticLM
from repro.launch.distributed import (FleetEvent, HostTopology, HostView,
                                      fleet_fingerprint)
from repro.launch.sharding import shard_bounds
from repro.models import build_model
from repro.obs import metrics as obs_metrics
from repro.viscosity import INTERPRET, REGISTRY, SW, lanefault

PyTree = Any


def model_stage_names(cfg: ModelConfig) -> List[str]:
    """The Viscosity stages this architecture actually exercises."""
    names = []
    if not cfg.attn_free or cfg.shared_attn_every:
        names.append("flash_attention")
    if cfg.gated_mlp and cfg.moe is None:
        names.append("swiglu_mlp")
    if cfg.family == "hybrid":
        names.append("mamba2_ssd")
    if cfg.family == "ssm" and cfg.layer_pattern and cfg.layer_pattern[0] == 3:
        names.append("rwkv6_wkv")
    return names


def canary_stages(cfg: ModelConfig, hw_route: str = INTERPRET
                  ) -> List[Stage]:
    """Small-port canary stages for the arch's Viscosity ops."""
    hd = 32
    ports = {
        "flash_attention": (jax.ShapeDtypeStruct((2, 64, 4, hd), jnp.float32),
                            jax.ShapeDtypeStruct((2, 64, 2, hd), jnp.float32),
                            jax.ShapeDtypeStruct((2, 64, 2, hd), jnp.float32)),
        "swiglu_mlp": (jax.ShapeDtypeStruct((64, 64), jnp.float32),
                       jax.ShapeDtypeStruct((64, 128), jnp.float32),
                       jax.ShapeDtypeStruct((64, 128), jnp.float32),
                       jax.ShapeDtypeStruct((128, 64), jnp.float32)),
        "mamba2_ssd": (jax.ShapeDtypeStruct((2, 64, 2, 16), jnp.float32),
                       jax.ShapeDtypeStruct((2, 64, 2), jnp.float32),
                       jax.ShapeDtypeStruct((2,), jnp.float32),
                       jax.ShapeDtypeStruct((2, 64, 8), jnp.float32),
                       jax.ShapeDtypeStruct((2, 64, 8), jnp.float32)),
        "rwkv6_wkv": (jax.ShapeDtypeStruct((2, 32, 2, 16), jnp.float32),
                      jax.ShapeDtypeStruct((2, 32, 2, 16), jnp.float32),
                      jax.ShapeDtypeStruct((2, 32, 2, 16), jnp.float32),
                      jax.ShapeDtypeStruct((2, 32, 2, 16), jnp.float32),
                      jax.ShapeDtypeStruct((2, 16), jnp.float32)),
    }
    stages = []
    for name in model_stage_names(cfg):
        spec = REGISTRY.get(name)
        stages.append(Stage(name=name, spec=spec, ports=ports[name],
                            tol=max(spec.tol, 1e-3)))
    return stages


@dataclass
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    canary_every: int = 0          # 0 = disabled
    canary_localize: bool = False  # lane-localize canary faults (DEGRADED)
    ckpt_dir: Optional[str] = None
    compression: bool = False      # int8 EF gradient compression
    hw_route: str = SW             # production: HW; CPU tests: SW/INTERPRET
    seed: int = 0
    # Probation (transient-vs-persistent classification): a detection
    # re-executes under backoff before any capacity is surrendered.
    # 0 retries = disabled (every detection is persistent, the
    # pre-probation behavior).
    probation_retries: int = 0
    probation_backoff_s: float = 0.0

    def probation_policy(self) -> Optional[ProbationPolicy]:
        if self.probation_retries <= 0:
            return None
        return ProbationPolicy(retries=self.probation_retries,
                               backoff_base_s=self.probation_backoff_s)


class TrainRunner:
    def __init__(self, cfg: ModelConfig, opt_cfg: optim.AdamWConfig,
                 tcfg: TrainConfig, data: SyntheticLM):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.data = data
        self.fault_state = FaultState()
        self.stage_names = model_stage_names(cfg)
        self.dispatcher = Dispatcher(self._build)
        self.guard_trips = 0
        self.watchdog = StragglerWatchdog()
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir)
                     if tcfg.ckpt_dir else None)
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------ build
    def _build(self, plan: RoutingPlan) -> Callable:
        model = build_model(self.cfg, routes=plan)
        use_comp = self.tcfg.compression

        def step(params, opt_state, err, batch):
            (loss, metrics), grads = jax.value_and_grad(
                model.forward, has_aux=True)(params, batch)
            if use_comp:
                grads, err = optim.compress_tree(grads, err)
            params, opt_state, om = optim.update(self.opt_cfg, grads,
                                                 opt_state, params)
            return params, opt_state, err, {**metrics, **om}

        return jax.jit(step, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------ state
    def init_state(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(self.tcfg.seed)
        model = build_model(self.cfg)
        params = model.init(key)
        opt_state = optim.init(params)
        err = optim.init_error(params) if self.tcfg.compression else \
            jnp.zeros(())
        return params, opt_state, err

    def signature(self) -> FaultSignature:
        return self.fault_state.signature(self.stage_names)

    def plan(self) -> RoutingPlan:
        """The RoutingPlan for the current fault state: healthy stages take
        the deployment's optimized target; quarantined ones walk the
        degradation ladder when a lane map is localized (remap -> reduced
        width -> SW), or drop straight to the SW oracle without one.
        Hashable — it is the Dispatcher cache key."""
        base = RoutingPlan.from_signature(
            self.signature(), healthy=self.tcfg.hw_route)
        return lanefault.degraded_plan(
            base, self.fault_state.counts(self.stage_names)).validate(
                registry=REGISTRY)

    def inject_fault(self, stage: str, kind: str = "injected"):
        if stage not in self.stage_names:
            raise ValueError(f"unknown stage {stage!r}; this model's stages:"
                             f" {self.stage_names}")
        self.fault_state.mark(stage, 0, kind=kind)

    # -------------------------------------------------------------- run
    def run(self, params, opt_state, err, *, start_step: int = 0,
            steps: Optional[int] = None,
            on_step: Optional[Callable[[int, dict], None]] = None):
        tcfg = self.tcfg
        steps = steps if steps is not None else tcfg.steps
        step_i = start_step
        last_good = start_step - 1
        while step_i < start_step + steps:
            batch = self.data.device_batch(step_i)
            fn = self.dispatcher.get(self.plan())
            t0 = time.perf_counter()
            new = fn(params, opt_state, err, batch)
            new[-1]["loss"].block_until_ready()
            dt = time.perf_counter() - t0
            obs_metrics.observe("train_step_seconds", dt)
            self.watchdog.record(0, dt)
            params2, opt2, err2, metrics = new
            if not StepGuard.ok({"loss": metrics["loss"],
                                 "grad_norm": metrics["grad_norm"]}):
                self.guard_trips += 1
                # Logical (step, origin, seq) stamp — never wall clock:
                # the fault log is a deterministic function of the run.
                self.fault_state.note("<step>", kind="nan_guard",
                                      step=step_i)
                if self.ckpt and last_good >= 0 and self.ckpt.steps():
                    s = self.ckpt.latest_step()
                    self.ckpt.wait()
                    like = {"params": jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        params),
                        "opt": jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        opt_state)}
                    r0 = time.perf_counter()
                    restored = self.ckpt.restore(s, like)
                    obs_metrics.observe("ckpt_restore_seconds",
                                        time.perf_counter() - r0)
                    params, opt_state = restored["params"], restored["opt"]
                    # inputs of the failed call were donated; rebuild err
                    err = (optim.init_error(params)
                           if self.tcfg.compression else jnp.zeros(()))
                    step_i = s
                    continue
                raise FloatingPointError("non-finite step with no checkpoint")
            params, opt_state, err = params2, opt2, err2
            row = {k: float(v) for k, v in metrics.items()
                   if jnp.ndim(v) == 0}
            row.update(step=step_i, dt=dt,
                       n_faults=self.signature().n_faults(),
                       compiles=self.dispatcher.compiles)
            self.history.append(row)
            if on_step:
                on_step(step_i, row)
            if tcfg.canary_every and (step_i + 1) % tcfg.canary_every == 0:
                chk = CanaryChecker(canary_stages(self.cfg),
                                    route_hw=tcfg.hw_route,
                                    localize=tcfg.canary_localize)
                found = chk.sweep(self.fault_state, step=step_i)
                policy = tcfg.probation_policy()
                if found and policy is not None:
                    # Probation: re-canary each detection under backoff.
                    # Transient (clean re-run) clears the quarantine — the
                    # next plan() restores the HW route; persistent walks
                    # the ladder exactly as before.
                    clf = FaultClassifier(chk, policy)
                    for name in found:
                        res = clf.classify(name, step=step_i,
                                           state=self.fault_state)
                        if res.transient:
                            self.fault_state.clear(name, step=step_i)
            if self.ckpt and (step_i + 1) % tcfg.ckpt_every == 0:
                s0 = time.perf_counter()
                self.ckpt.save_async(step_i + 1,
                                     {"params": params, "opt": opt_state},
                                     extra={"data_step": step_i + 1})
                obs_metrics.observe("ckpt_save_seconds",
                                    time.perf_counter() - s0)
                last_good = step_i + 1
            step_i += 1
        if self.ckpt:
            self.ckpt.wait()
        return params, opt_state, err


# ==========================================================================
# Fleet layer (paper §II Fig. 2, §V Fig. 8): data-parallel steps where each
# shard consults its own RoutingPlan out of a shared FleetPlan.
# ==========================================================================
@dataclass
class FleetTrainConfig:
    n_devices: int = 2
    n_spares: int = 0
    # Host axis (multi-host fleets): devices partition into contiguous
    # per-host blocks; a host loss quarantines the whole block in one
    # FleetPlan transition and the survivors re-fold the mesh.
    topology: Optional[HostTopology] = None


class FleetTrainRunner:
    """Data-parallel training across a device-indexed fleet.

    Per step the global batch shards across the FleetPlan's *serving*
    devices (``launch.sharding.shard_bounds`` — quarantined devices and
    idle spares get no slice); each shard's gradients come from an
    executable keyed by that shard's own ``RoutingPlan`` in one shared
    Dispatcher, so devices with equal routing share a single compile.
    Detection follows the Oobleck loop per shard: a non-finite shard loss
    quarantines that device (detect), its work migrates to a hot spare
    when one is free (Fig. 8) or its slice redistributes over the
    survivors (quarantine -> migrate-or-reroute), and the step re-runs
    (continue).  Stage-level faults reroute only the faulted device's
    plan — the other shards keep their fully-fused fast path.
    """

    def __init__(self, cfg: ModelConfig, opt_cfg: optim.AdamWConfig,
                 tcfg: TrainConfig, data: SyntheticLM,
                 fcfg: FleetTrainConfig):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.data = data
        self.fcfg = fcfg
        if fcfg.topology is not None and \
                fcfg.topology.n_devices != fcfg.n_devices:
            raise ValueError(
                f"topology covers {fcfg.topology.n_devices} device(s), "
                f"fleet has {fcfg.n_devices}")
        self.stage_names = model_stage_names(cfg)
        self.fleet = FleetPlan.healthy(fcfg.n_devices, self.stage_names,
                                       target=tcfg.hw_route,
                                       n_spares=fcfg.n_spares)
        self.dispatcher = Dispatcher(self._build_grads)
        self.guard_trips = 0
        self.history: List[Dict[str, float]] = []
        # Ordered transition log (the multi-host runtime replays this):
        # every quarantine/migration the runner performs is one event.
        self.fleet_log: List[FleetEvent] = []
        # Probation bookkeeping rides the same logical-stamp log dialect
        # as the single-device runner.
        self.fault_state = FaultState()
        self.classifier: Optional[FaultClassifier] = None
        policy = tcfg.probation_policy()
        if policy is not None:
            self.classifier = FaultClassifier(
                CanaryChecker(canary_stages(cfg), route_hw=tcfg.hw_route),
                policy)
        # Fleet-owned checkpoints: checksummed async saves on the
        # ckpt_every cadence; host-fault recovery restores the latest
        # onto the survivor mesh (restore-then-continue).
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir)
                     if tcfg.ckpt_dir else None)
        self._update = jax.jit(
            lambda grads, opt_state, params: optim.update(
                self.opt_cfg, grads, opt_state, params))

    # ------------------------------------------------------------ build
    def _build_grads(self, plan: RoutingPlan) -> Callable:
        model = build_model(self.cfg, routes=plan)

        def grads_fn(params, batch):
            (loss, metrics), grads = jax.value_and_grad(
                model.forward, has_aux=True)(params, batch)
            return grads, loss, metrics

        return jax.jit(grads_fn)

    # ------------------------------------------------------------ state
    def init_state(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(self.tcfg.seed)
        params = build_model(self.cfg).init(key)
        return params, optim.init(params)

    def _log_event(self, step: int, kind: str, device: int,
                   stage: str = ""):
        topo = self.fcfg.topology
        origin = 0 if topo is None or topo.host_id is None else topo.host_id
        self.fleet_log.append(FleetEvent(step=step, origin=origin,
                                         seq=len(self.fleet_log),
                                         kind=kind, device=device,
                                         stage=stage))

    def inject_stage_fault(self, device: int, stage: str, *,
                           step: int = -1):
        if stage not in self.stage_names:
            raise ValueError(f"unknown stage {stage!r}; this model's stages:"
                             f" {self.stage_names}")
        self.fleet = self.fleet.with_stage_fault(device, stage)
        self._log_event(step, "stage", device, stage)

    def inject_device_fault(self, device: int, *, step: int = -1):
        self.fleet = self.fleet.with_device_fault(device)
        self._log_event(step, "device", device)

    def inject_host_fault(self, host: int, *, step: int = -1):
        """A whole host drops out: quarantine its device block in ONE
        FleetPlan transition (spares outside the block absorb what they
        can); the next step re-folds the mesh over the survivors."""
        if self.fcfg.topology is None:
            raise ValueError("host faults need FleetTrainConfig.topology")
        self.fleet = self.fleet.with_host_fault(
            self.fcfg.topology.devices_of(host))
        self._log_event(step, "host", host)

    def host_view(self) -> HostView:
        """The fleet's health projected onto the host partition."""
        if self.fcfg.topology is None:
            raise ValueError("host_view needs FleetTrainConfig.topology")
        return HostView.of(self.fleet, self.fcfg.topology)

    # -------------------------------------------------------------- run
    def _shard_step(self, params, batch, poison_device: Optional[int]):
        """Grads per serving shard; returns (avg_grads, metrics, tripped)
        where ``tripped`` is the first device whose shard failed the
        StepGuard (None when the step is clean)."""
        B = batch["tokens"].shape[0]
        bounds = shard_bounds(B, self.fleet.device_mask())
        total = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, n_rows = [], 0
        for d, (lo, hi) in bounds.items():
            if hi == lo:
                continue
            shard = {k: v[lo:hi] for k, v in batch.items()}
            fn = self.dispatcher.get(self.fleet.plan_for(d))
            grads, loss, metrics = fn(params, shard)
            if d == poison_device:       # emulated datapath blowup
                loss = loss * jnp.nan
            if not StepGuard.ok({"loss": loss, "grads": grads}):
                return None, {"device": d}, d
            w = float(hi - lo)
            total = jax.tree_util.tree_map(
                lambda t, g: t + w * g, total, grads)
            losses.append(w * float(loss))
            n_rows += hi - lo
        avg = jax.tree_util.tree_map(lambda t: t / n_rows, total)
        return avg, {"loss": sum(losses) / n_rows}, None

    def _probe_shard(self, params, batch, device: int,
                     poison_device: Optional[int]) -> bool:
        """Probation probe: re-execute just ``device``'s shard and guard
        the result (RedMulE-FT re-execution-on-demand).  True = clean."""
        B = batch["tokens"].shape[0]
        bounds = shard_bounds(B, self.fleet.device_mask())
        lo, hi = bounds.get(device, (0, 0))
        if hi == lo:
            return True
        shard = {k: v[lo:hi] for k, v in batch.items()}
        fn = self.dispatcher.get(self.fleet.plan_for(device))
        grads, loss, _metrics = fn(params, shard)
        if device == poison_device:
            loss = loss * jnp.nan
        return StepGuard.ok({"loss": loss, "grads": grads})

    def _restore_latest(self, params, opt_state, step_i: int):
        """Host-fault recovery: restore the latest checksummed checkpoint
        onto whatever mesh survives (restore is elastic — params are
        replicated, so the shard re-fold is just shard_bounds following
        the new mask).  Returns (params, opt_state, resume_step)."""
        self.ckpt.wait()
        s = self.ckpt.latest_step()
        like = {"params": jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
            "opt": jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), opt_state)}
        r0 = time.perf_counter()
        restored = self.ckpt.restore(s, like)
        obs_metrics.observe("ckpt_restore_seconds",
                            time.perf_counter() - r0)
        self.fault_state.note("<ckpt>", kind="checkpoint_restored",
                              step=step_i)
        return restored["params"], restored["opt"], s

    def run(self, params, opt_state, *, steps: Optional[int] = None,
            poison: Optional[Mapping[int, int]] = None,
            transient: Optional[Mapping[int, int]] = None,
            host_loss: Optional[Mapping[int, int]] = None):
        """``poison[step] = device`` injects a non-finite shard loss at
        that step (the detect -> quarantine -> migrate loop, test-drivable
        without real broken silicon).  ``transient[step] = device`` is the
        single-upset variant: it poisons only the *first* execution of
        that step, so with probation enabled (``TrainConfig
        .probation_retries > 0``) the re-executed shard comes back clean
        and the fleet keeps its capacity — logged ``transient_recovered``,
        zero quarantines.  ``host_loss[step] = host`` drops a whole host
        just before that step: its device block quarantines in one
        transition and the surviving hosts' shards absorb the batch (the
        mesh re-fold is automatic — shard_bounds follows the mask); with
        a CheckpointManager attached, the latest checkpoint restores onto
        the survivor mesh first (restore-then-continue).
        """
        steps = steps if steps is not None else self.tcfg.steps
        poison = dict(poison or {})
        transient = dict(transient or {})
        host_loss = dict(host_loss or {})
        step_i = 0
        while step_i < steps:
            if step_i in host_loss:
                self.inject_host_fault(host_loss.pop(step_i), step=step_i)
                if self.ckpt:
                    self.ckpt.wait()     # a save still in flight counts
                if self.ckpt and self.ckpt.steps():
                    params, opt_state, step_i = self._restore_latest(
                        params, opt_state, step_i)
                    continue
            batch = self.data.device_batch(step_i)
            t0 = time.perf_counter()
            pd = poison.get(step_i)
            if pd is None and step_i in transient:
                pd = transient.pop(step_i)   # upset hits one execution only
            grads, metrics, tripped = self._shard_step(params, batch, pd)
            if tripped is not None:
                # detect -> probate -> quarantine-or-recover; a transient
                # verdict re-runs the step with no capacity surrendered,
                # persistent migrates to a spare / reroutes the survivors.
                self.guard_trips += 1
                if self.classifier is not None:
                    res = self.classifier.probate(
                        lambda: self._probe_shard(params, batch, tripped,
                                                  poison.get(step_i)),
                        stage="<step>", replica=tripped, step=step_i,
                        state=self.fault_state)
                    if res.transient:
                        continue
                poison.pop(step_i, None)     # the bad device is now gone
                self.fleet = self.fleet.with_device_fault(tripped)
                self._log_event(step_i, "device", tripped)
                continue
            params, opt_state, om = self._update(grads, opt_state, params)
            fleet_dt = time.perf_counter() - t0
            obs_metrics.observe("train_step_seconds", fleet_dt)
            row = {
                "step": step_i, "loss": metrics["loss"],
                "dt": fleet_dt,
                "n_serving": len(self.fleet.serving()),
                "n_quarantined": len(self.fleet.quarantined),
                "compiles": self.dispatcher.compiles}
            if self.fcfg.topology is not None:
                row["hosts_serving"] = len(self.host_view().hosts_serving())
            self.history.append(row)
            step_i += 1
            if self.ckpt and step_i % self.tcfg.ckpt_every == 0:
                s0 = time.perf_counter()
                self.ckpt.save_async(
                    step_i, {"params": params, "opt": opt_state},
                    extra={"data_step": step_i,
                           "fingerprint": fleet_fingerprint(self.fleet)})
                obs_metrics.observe("ckpt_save_seconds",
                                    time.perf_counter() - s0)
        if self.ckpt:
            self.ckpt.wait()
        return params, opt_state
