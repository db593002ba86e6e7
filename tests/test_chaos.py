"""Chaos campaign layer (repro.chaos): schedule generator, value-level
canary injection, probation classification, coordinator stall drills,
checkpoint restore-then-continue, and the campaign smokes.

The schedule/replay tests are pure plan algebra (fast); the campaign
smokes drive real engines at small sizing — they are the tier-1 slice
of what CI's chaos-smoke job soaks at full sizing.
"""
import time
import types

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import optim
from repro.chaos import (DEVICE_LOSS, LANE_FAULT, PERSISTENT_STAGE,
                         SPARE_EXHAUSTION, TRANSIENT_STAGE, ChaosEvent,
                         draw_schedule)
from repro.chaos.campaign import (ChaosCanary, StallingKVClient,
                                  closure_scenario, coordinator_campaign,
                                  serve_campaign, train_campaign)
from repro.chaos.schedule import COORD_STALL, SERVE_KINDS, TRAIN_KINDS, \
    horizon_of
from repro.configs import get_config
from repro.core.fault import (INTERMITTENT_PROMOTED, PERSISTENT,
                              TRANSIENT_RECOVERED, FaultClassifier,
                              FaultState, IntermittentPolicy,
                              ProbationPolicy)
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.core.routing import FleetPlan
from repro.data import DataConfig, SyntheticLM
from repro.launch.distributed import (FleetEvent, HostTimeoutError,
                                      HostTopology, KVCoordinator,
                                      fleet_fingerprint, merge_event_logs,
                                      replay_log)
from repro.models import build_model
from repro.train import TrainConfig
from repro.train.runner import FleetTrainConfig, FleetTrainRunner
from repro.viscosity import INTERPRET, lanefault
from repro.viscosity.lanefault import STUCK, LaneFault
from repro.viscosity.lang import SW

ARCH = "qwen1.5-4b"
STAGES = ["flash_attention", "swiglu_mlp"]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config(ARCH).reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------------------- schedule
def test_draw_schedule_deterministic():
    kw = dict(n_events=8, n_devices=4, stage_names=STAGES, n_spares=2)
    a = draw_schedule(3, **kw)
    b = draw_schedule(3, **kw)
    assert a == b
    assert a != draw_schedule(4, **kw)
    steps = [e.step for e in a]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    assert horizon_of(a, settle=5) == a[-1].step + 5


def test_draw_schedule_transient_persistent_stages_disjoint():
    """A probation episode's probes drain the armed-fault queue in
    order, so a stage must never carry both a transient and a
    persistent spec (the episode would cross into the hard fault and
    earn a spurious persistent verdict)."""
    for seed in range(12):
        sched = draw_schedule(seed, n_events=7, n_devices=4,
                              stage_names=STAGES, n_spares=2)
        trans = {e.stage for e in sched if e.kind == TRANSIENT_STAGE}
        hard = {e.stage for e in sched
                if e.kind in (PERSISTENT_STAGE, LANE_FAULT)}
        assert not trans & hard, (seed, trans, hard)


def test_draw_schedule_validates():
    with pytest.raises(ValueError):
        draw_schedule(0, n_events=-1, n_devices=2, stage_names=STAGES)
    with pytest.raises(ValueError):
        draw_schedule(0, n_events=1, n_devices=2, stage_names=[])
    with pytest.raises(ValueError):
        ChaosEvent(step=0, kind="meteor_strike")


def _wire_events(sched):
    """The engine-level wire events a campaign applies for ``sched`` —
    a transient is a net-zero (stage, recover) pair."""
    wires = []
    for ev in sched:
        if ev.kind == TRANSIENT_STAGE:
            wires += [("stage", ev.device, ev.stage),
                      ("recover", ev.device, ev.stage)]
        elif ev.kind in (PERSISTENT_STAGE, LANE_FAULT):
            wires.append(("stage", ev.device, ev.stage))
        elif ev.kind == DEVICE_LOSS:
            wires.append(("device", ev.device))
        elif ev.kind == SPARE_EXHAUSTION:
            wires += [("device", d) for d in ev.devices]
    return wires


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 500), cut=st.integers(0, 14))
def test_property_schedule_events_applicable_any_interleaving(seed, cut):
    """Every drawn schedule replays onto the healthy plan with zero
    dropped transitions, and any split of the wire events across two
    host logs merges to the same final FleetPlan (the multi-host
    agreement property, over *randomized* chaos schedules)."""
    sched = draw_schedule(seed, n_events=6, n_devices=5,
                          stage_names=STAGES, n_spares=2, min_serving=1)
    evs = [FleetEvent.from_engine(i, 0, i, w)
           for i, w in enumerate(_wire_events(sched))]
    base = FleetPlan.healthy(5, STAGES, target=INTERPRET, n_spares=2)
    ref, ref_dropped = replay_log(base, evs, STAGES, target=INTERPRET)
    assert not ref_dropped
    cut = min(cut, len(evs))
    merged = merge_event_logs(evs[:cut], evs[cut:])
    plan, dropped = replay_log(base, merged, STAGES, target=INTERPRET)
    assert fleet_fingerprint(plan) == fleet_fingerprint(ref)
    assert dropped == ref_dropped
    assert len(plan.serving()) >= 1


# ----------------------------------------------- ChaosCanary injection
class _SpyChecker:
    """Reports a stage clean exactly when no injection is armed during
    the probe — what the real canary does, minus the kernels."""

    def __init__(self, names):
        self.stages = [types.SimpleNamespace(name=n) for n in names]
        self.seen = []

    def check_stage(self, stage):
        f = lanefault.injection(stage.name)
        self.seen.append((stage.name, f is not None))
        return f is None


def _fault(width=8):
    return LaneFault(kind=STUCK, lanes=(1,), width=width, value=3.0)


def test_chaos_canary_arms_only_around_probe():
    lanefault.reset()
    spy = _SpyChecker(["s0"])
    canary = ChaosCanary(spy)
    canary.arm("s0", _fault(), fails=1)
    stage = spy.stages[0]
    assert canary.check_stage(stage) is False      # armed during probe
    assert lanefault.injection("s0") is None       # never armed outside
    assert canary.check_stage(stage) is True       # transient: consumed
    assert canary.armed() == []
    canary.arm("s0", _fault(), fails=None)         # hard fault
    assert not canary.check_stage(stage)
    assert not canary.check_stage(stage)           # still failing
    canary.disarm("s0")
    assert canary.check_stage(stage) is True
    assert lanefault.injection("s0") is None


# ------------------------------------------------------------ probation
def test_probation_transient_and_persistent_verdicts():
    waits = []
    clf = FaultClassifier(None, ProbationPolicy(retries=3,
                                                backoff_base_s=0.0),
                          sleep=waits.append)
    state = FaultState()
    flaky = iter([False, True])
    res = clf.probate(lambda: next(flaky), stage="x", replica=1, step=5,
                      state=state)
    assert res.transient and res.attempts == 2
    assert res.verdict == TRANSIENT_RECOVERED
    assert [e["kind"] for e in state.log] == \
        ["probation_retry", "probation_retry", TRANSIENT_RECOVERED]

    res = clf.probate(lambda: False, stage="x", state=state)
    assert not res.transient and res.attempts == 3
    assert res.verdict == PERSISTENT
    assert [e["kind"] for e in state.log].count(PERSISTENT) == 1
    assert waits == []                             # zero-base never sleeps


def test_intermittent_flapping_promoted_to_persistent():
    """A (stage, replica) that keeps earning transient verdicts inside
    the frequency window gets its next clean probe overridden to
    persistent (wear-out signature), with the promotion in the fault
    log; a different replica on the same stage is unaffected."""
    clf = FaultClassifier(None, ProbationPolicy(retries=3,
                                                backoff_base_s=0.0),
                          sleep=lambda _s: None,
                          intermittent=IntermittentPolicy(threshold=2,
                                                          window_steps=5))
    state = FaultState()
    res = clf.probate(lambda: True, stage="x", replica=1, step=0,
                      state=state)
    assert res.transient and res.verdict == TRANSIENT_RECOVERED
    res = clf.probate(lambda: True, stage="x", replica=1, step=3,
                      state=state)
    assert not res.transient and res.verdict == INTERMITTENT_PROMOTED
    assert INTERMITTENT_PROMOTED in [e["kind"] for e in state.log]
    # other replicas keep their own window
    res = clf.probate(lambda: True, stage="x", replica=2, step=3,
                      state=state)
    assert res.transient


def test_intermittent_window_expires():
    """Transient verdicts outside the trailing window do not count
    toward promotion — sparse upsets stay transient."""
    clf = FaultClassifier(None, ProbationPolicy(retries=3,
                                                backoff_base_s=0.0),
                          sleep=lambda _s: None,
                          intermittent=IntermittentPolicy(threshold=2,
                                                          window_steps=3))
    for step in (0, 10, 20):
        res = clf.probate(lambda: True, stage="x", replica=0, step=step)
        assert res.transient, step


def test_intermittent_promotion_under_chaos_schedule():
    """Chaos-schedule shape: repeated transient upsets on one stage
    within the window promote on the threshold'th episode, and the
    verdict counters land in telemetry."""
    pol = IntermittentPolicy(threshold=3, window_steps=10)
    clf = FaultClassifier(None, ProbationPolicy(retries=2,
                                                backoff_base_s=0.0),
                          sleep=lambda _s: None, intermittent=pol)
    sched = [ChaosEvent(step=s, kind=TRANSIENT_STAGE, device=0,
                        stage="flash_attention") for s in (2, 5, 8)]
    reg = obs_metrics.Registry()
    verdicts = []
    with obs_metrics.use(reg):
        for ev in sched:
            res = clf.probate(lambda: True, stage=ev.stage,
                              replica=ev.device, step=ev.step)
            verdicts.append(res.verdict)
    assert verdicts == [TRANSIENT_RECOVERED, TRANSIENT_RECOVERED,
                        INTERMITTENT_PROMOTED]
    snap = reg.snapshot()
    assert obs_report.counter_value(
        snap, "probation_verdicts_total",
        verdict=INTERMITTENT_PROMOTED) == 1
    assert obs_report.counter_value(
        snap, "probation_transients_total",
        stage="flash_attention") == 3


def test_probation_backoff_schedule_capped():
    pol = ProbationPolicy(retries=4, backoff_base_s=0.25,
                          backoff_factor=2.0, max_backoff_s=0.6)
    assert pol.backoff_schedule() == (0.25, 0.5, 0.6, 0.6)
    waits = []
    clf = FaultClassifier(None, pol, sleep=waits.append)
    clf.probate(lambda: False, stage="x")
    assert waits == [0.25, 0.5, 0.6, 0.6]


# ---------------------------------------------------------- coordinator
def test_coordinator_stalled_peer_typed_timeout_bounded():
    client = StallingKVClient(stalled=[1])
    coord = KVCoordinator(num_hosts=2, host_id=0, client=client,
                          timeout_ms=5_000, attempt_timeout_ms=10,
                          max_attempts=3, backoff_base_s=0.001)
    t0 = time.perf_counter()
    with pytest.raises(HostTimeoutError) as ei:
        coord.exchange("payload")
    wall = time.perf_counter() - t0
    assert ei.value.host_id == 1
    assert client.gets <= 3                        # bounded retry budget
    assert wall < 5.0                              # nowhere near 120 s

    coord.mark_dead(1)
    client.gets = 0
    assert coord.exchange("again") == ["again", None]
    assert client.gets == 0                        # dead peer not polled


# -------------------------------------------------- train runner drills
def _train_runner(cfg, tcfg, *, n_devices=4, n_spares=1, topo=None):
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, batch=8,
                                  seq_len=16))
    return FleetTrainRunner(
        cfg, optim.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        tcfg, data, FleetTrainConfig(n_devices=n_devices,
                                     n_spares=n_spares, topology=topo))


def test_fleet_train_transient_probation_keeps_capacity(setup):
    cfg, _ = setup
    r = _train_runner(cfg, TrainConfig(steps=3, hw_route=SW,
                                       probation_retries=2))
    params, opt = r.init_state()
    r.run(params, opt, steps=3, transient={1: 0})
    kinds = [e["kind"] for e in r.fault_state.log]
    assert r.guard_trips == 1
    assert not r.fleet.quarantined                 # capacity kept
    assert TRANSIENT_RECOVERED in kinds
    assert all(np.isfinite(h["loss"]) for h in r.history)


def test_fleet_train_ckpt_cadence_and_host_restore(setup, tmp_path):
    cfg, _ = setup
    topo = HostTopology(num_hosts=2, devices_per_host=2)
    r = _train_runner(cfg, TrainConfig(steps=6, hw_route=SW,
                                       ckpt_every=2,
                                       ckpt_dir=str(tmp_path)),
                      topo=topo)
    params, opt = r.init_state()
    r.run(params, opt, steps=6, host_loss={3: 1})
    kinds = [e["kind"] for e in r.fault_state.log]
    assert "checkpoint_restored" in kinds          # restore-then-continue
    assert {2, 3} <= set(r.fleet.quarantined)      # host 1's block gone
    assert r.ckpt.steps() and r.ckpt.steps()[0] == 2   # cadence saves
    assert all(np.isfinite(h["loss"]) for h in r.history)
    # the restore rewinds: some step index re-runs after the host loss
    steps = [h["step"] for h in r.history]
    assert len(steps) > len(set(steps))


def test_fleet_train_host_restore_waits_for_the_save_in_flight(
        setup, tmp_path, monkeypatch):
    """A host lost while the latest checkpoint is still being written
    restores from it once it lands, instead of running on unrestored."""
    from repro.checkpoint.manager import CheckpointManager
    write = CheckpointManager._write

    def slow_write(self, *args):
        time.sleep(1.0)
        return write(self, *args)

    monkeypatch.setattr(CheckpointManager, "_write", slow_write)
    cfg, _ = setup
    topo = HostTopology(num_hosts=2, devices_per_host=2)
    r = _train_runner(cfg, TrainConfig(steps=4, hw_route=SW, ckpt_every=2,
                                       ckpt_dir=str(tmp_path)), topo=topo)
    params, opt = r.init_state()
    r.run(params, opt, steps=4, host_loss={3: 1})
    kinds = [e["kind"] for e in r.fault_state.log]
    assert "checkpoint_restored" in kinds
    assert [h["step"] for h in r.history].count(2) == 2


# ------------------------------------------------------ campaign smokes
def test_serve_campaign_smoke_invariants_green(setup):
    """Invariants green at small sizing, and the run's telemetry
    snapshot reproduces the campaign's own MTTR/goodput summaries
    exactly (the obs.metrics exact-stats contract).  Seed 2's schedule
    draws a coord_stall, so the drill's bounded KV retries must show as
    a counter spike in the same snapshot."""
    cfg, params = setup
    reg = obs_metrics.Registry()
    with obs_metrics.use(reg), \
            obs_metrics.label_scope(section="serve_resident"):
        r = serve_campaign(2, n_events=2, n_requests=10, params=params,
                           cfg=cfg)
    assert r["invariants"]["ok"], r["invariants"]["reports"]
    assert r["traffic"]["completed"] == r["traffic"]["requests"]
    assert r["mttr_summary"]["n"] == r["n_events"]
    assert lanefault.injection("flash_attention") is None  # cleaned up

    snap = reg.snapshot()
    assert obs_report.mttr_summary(snap, section="serve_resident") \
        == r["mttr_summary"]
    g = obs_report.goodput_summary(snap, section="serve_resident")
    assert g["completed"] == r["traffic"]["completed"]
    assert g["expired"] == r["traffic"]["expired"]
    assert round(g["throughput_tok_s"], 2) == \
        r["traffic"]["throughput_tok_s"]
    assert round(g["virtual_time_s"], 2) == r["traffic"]["virtual_time_s"]
    # the scheduled coord_stall surfaced as bounded KV retries
    assert any(e["kind"] == COORD_STALL for e in r["schedule"])
    assert obs_report.counter_value(snap, "kv_retries_total", op="get") > 0
    assert obs_report.counter_value(snap, "coord_timeouts_total",
                                    host="1") > 0


def test_train_campaign_smoke_invariants_green(tmp_path):
    r = train_campaign(0, n_events=2, ckpt_dir=str(tmp_path))
    assert r["invariants"]["ok"], r["invariants"]["reports"]
    assert r["n_events"] == 2 and r["steps"] > 0


def test_coordinator_campaign_fast_typed_mttr():
    r = coordinator_campaign(1)
    assert r["invariants"]["ok"], r["invariants"]["reports"]
    assert r["mttr_summary"]["max_s"] < 5.0


def test_closure_scenario_tracks_degradation_model(setup):
    cfg, params = setup
    rep = closure_scenario(0, n_requests=24, params=params, cfg=cfg)
    assert rep["ok"], rep
    assert rep["rel_err"] <= 0.15 and not rep["dropped"]
