"""Partial degradation: lane faults, DEGRADED lowerings, deterministic logs.

The value-level fault path end to end, per kernel family: an injected
``LaneFault`` corrupts ONLY its mapped lanes of the kernel's output
(healthy lanes bit-identical), the DEGRADED remap lowering heals the
corruption exactly (bit-identity across injection under the same plan),
reduced-width execution stays within the stage tolerance, and routing /
validation / the capacity model all consult the same lane-map registry.
Plus the two satellite bug classes: wall-clock-free fault logs that merge
identically under any interleaving, and injection no-ops on zero-heavy
inputs failing loudly instead of passing vacuously.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CanaryChecker, FaultState, RoutingPlan, Stage
from repro.core.datacenter import DegradationModel
from repro.core.fault import (EXPECTED_STAGE_ERRORS, FaultInjector,
                              InjectionNoOpError)
from repro.kernels.flash_attention import ops as _fa_ops  # noqa: F401
from repro.kernels.mamba2_scan import ops as _m2_ops      # noqa: F401
from repro.kernels.rwkv6_scan import ops as _rk_ops       # noqa: F401
from repro.kernels.swiglu import ops as _sg_ops           # noqa: F401
from repro.launch import sharding
from repro.viscosity import (DEGRADED_REDUCED, DEGRADED_REMAP, INTERPRET,
                             REGISTRY, SW, lanefault)
from repro.viscosity.lanefault import LaneFault


@pytest.fixture(autouse=True)
def _clean_registries():
    lanefault.reset()
    yield
    lanefault.reset()


# Small canary ports per kernel family (the runner's shapes) + the output
# lane width each family's fault map refers to.
PORTS = {
    "flash_attention": ((2, 64, 4, 32), (2, 64, 2, 32), (2, 64, 2, 32)),
    "swiglu_mlp": ((64, 64), (64, 128), (64, 128), (128, 64)),
    "mamba2_ssd": ((2, 64, 2, 16), (2, 64, 2), (2,), (2, 64, 8),
                   (2, 64, 8)),
    "rwkv6_wkv": ((2, 32, 2, 16), (2, 32, 2, 16), (2, 32, 2, 16),
                  (2, 32, 2, 16), (2, 16)),
}
WIDTH = {"flash_attention": 32, "swiglu_mlp": 64, "mamba2_ssd": 16,
         "rwkv6_wkv": 16}
FAMILIES = sorted(PORTS)


def _stage(name: str) -> Stage:
    spec = REGISTRY.get(name)
    ports = tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                  for s in PORTS[name])
    return Stage(name=name, spec=spec, ports=ports,
                 tol=max(spec.tol, 1e-3))


def _changed_lanes(a: np.ndarray, b: np.ndarray):
    """Lane (minor-axis) indices where two outputs differ at all."""
    d = (a != b).reshape(-1, a.shape[-1])
    return tuple(int(i) for i in np.flatnonzero(d.any(axis=0)))


# ------------------------------------------------------------- descriptor
def test_lane_fault_validation():
    with pytest.raises(ValueError):
        LaneFault(kind="melted", lanes=(0,), width=8)
    with pytest.raises(ValueError):
        LaneFault(kind=lanefault.STUCK, lanes=(), width=8)
    with pytest.raises(ValueError):
        LaneFault(kind=lanefault.STUCK, lanes=(8,), width=8)
    with pytest.raises(ValueError):                  # every lane dead
        LaneFault(kind=lanefault.STUCK, lanes=tuple(range(8)), width=8)
    with pytest.raises(ValueError):
        LaneFault(kind=lanefault.STUCK, lanes=(0,), width=1)
    f = LaneFault(kind=lanefault.GAIN, lanes=(5, 1, 5), width=8)
    assert f.lanes == (1, 5)                         # sorted, deduped
    assert f.survivors() == (0, 2, 3, 4, 6, 7)


def test_lane_fault_apply_is_shape_aware():
    f = LaneFault(kind=lanefault.STUCK, lanes=(1,), width=4, value=9.0)
    x = jnp.ones((3, 4))
    out = np.asarray(f.apply(x))
    assert (out[:, 1] == 9.0).all() and (out[:, [0, 2, 3]] == 1.0).all()
    # wrong minor width, integer dtype, scalar: all untouched
    assert f.apply(jnp.ones((3, 5))) is not None
    np.testing.assert_array_equal(np.asarray(f.apply(jnp.ones((3, 5)))),
                                  np.ones((3, 5)))
    ints = jnp.ones((3, 4), jnp.int32)
    np.testing.assert_array_equal(np.asarray(f.apply(ints)), np.ones((3, 4)))
    assert f.apply(3.0) == 3.0
    # kind semantics on the mapped lane
    z = jnp.full((2, 4), 2.0)
    drop = LaneFault(kind=lanefault.DROPPED_MAC, lanes=(0,), width=4)
    assert np.asarray(drop.apply(z))[0, 0] == 0.0
    gain = LaneFault(kind=lanefault.GAIN, lanes=(0,), width=4, gain=1.5)
    assert np.asarray(gain.apply(z))[0, 0] == 3.0


# ------------------------------------------------- kernel-level injection
@pytest.mark.parametrize("name", FAMILIES)
def test_injection_corrupts_only_mapped_lanes(name):
    """The fault threads into the kernel body: the HW output differs from
    clean ONLY on the mapped lanes, and clearing the injection restores
    bit-identical output (healthy paths compile identically)."""
    stage = _stage(name)
    x = stage.canary_inputs(seed=3)
    w = WIDTH[name]
    fault = LaneFault(kind=lanefault.STUCK, lanes=(1, w - 2), width=w)
    clean = np.asarray(stage.run(*x, route=INTERPRET))
    with lanefault.inject(name, fault):
        bad = np.asarray(stage.run(*x, route=INTERPRET))
    changed = _changed_lanes(bad, clean)
    assert changed, "injection was a silent no-op"
    assert set(changed) <= set(fault.lanes)
    again = np.asarray(stage.run(*x, route=INTERPRET))
    np.testing.assert_array_equal(again, clean)


@pytest.mark.parametrize("name", FAMILIES)
def test_degraded_remap_heals_bit_identically(name):
    """DEGRADED remap under injection == DEGRADED remap without injection,
    bit for bit: corruption confined to mapped lanes is recomputed via the
    oracle and scattered in exactly."""
    stage = _stage(name)
    spec = REGISTRY.get(name)
    x = stage.canary_inputs(seed=3)
    w = WIDTH[name]
    fault = LaneFault(kind=lanefault.DROPPED_MAC, lanes=(0, 3), width=w)
    ref = np.asarray(spec.ref(*x))
    with lanefault.known_map(name, fault, base=INTERPRET):
        fn = spec.lower(DEGRADED_REMAP)
        healed_clean = np.asarray(fn(*x))
        with lanefault.inject(name, fault):
            healed_inj = np.asarray(fn(*x))
    np.testing.assert_array_equal(healed_inj, healed_clean)
    # dead lanes are exactly the oracle; the rest within the contract tol
    np.testing.assert_array_equal(healed_inj[..., list(fault.lanes)],
                                  ref[..., list(fault.lanes)])
    assert np.abs(healed_inj - ref).max() <= stage.tol


@pytest.mark.parametrize("name", FAMILIES)
def test_degraded_reduced_width_matches_oracle(name):
    """Reduced-width execution (kernel on the surviving-lane operand
    window, oracle on the dead lanes) stays within the stage tolerance and
    is insensitive to the full-width injection (the narrow tile no longer
    matches the fault's width — the defect is routed around)."""
    stage = _stage(name)
    spec = REGISTRY.get(name)
    x = stage.canary_inputs(seed=3)
    w = WIDTH[name]
    fault = LaneFault(kind=lanefault.STUCK, lanes=(2, w - 1), width=w)
    ref = np.asarray(spec.ref(*x))
    with lanefault.known_map(name, fault, base=INTERPRET):
        fn = spec.lower(DEGRADED_REDUCED)
        out_clean = np.asarray(fn(*x))
        with lanefault.inject(name, fault):
            out_inj = np.asarray(fn(*x))
    np.testing.assert_array_equal(out_inj, out_clean)
    np.testing.assert_array_equal(out_inj[..., list(fault.lanes)],
                                  ref[..., list(fault.lanes)])
    assert np.abs(out_inj - ref).max() <= stage.tol


# ----------------------------------------------------- routing and ladder
def test_validate_rejects_degraded_without_map():
    plan = RoutingPlan.make({"swiglu_mlp": DEGRADED_REMAP})
    with pytest.raises(ValueError, match="no lane map"):
        plan.validate(registry=REGISTRY)
    f = LaneFault(kind=lanefault.STUCK, lanes=(1,), width=64)
    with lanefault.known_map("swiglu_mlp", f, base=INTERPRET):
        assert plan.validate(registry=REGISTRY) is plan


def test_rung_ladder_and_degraded_plan():
    assert [lanefault.rung_for(n) for n in (1, 2, 3, 7)] == [
        DEGRADED_REMAP, DEGRADED_REDUCED, SW, SW]
    with pytest.raises(ValueError):
        lanefault.rung_for(0)
    base = RoutingPlan.make({"a": INTERPRET, "b": INTERPRET})
    f = LaneFault(kind=lanefault.STUCK, lanes=(1,), width=8)
    with lanefault.known_map("a", f, base=INTERPRET):
        # mapped stage walks the ladder; unmapped keeps its binary route
        p1 = lanefault.degraded_plan(base, {"a": 1, "b": 1})
        assert p1.target_for("a") == DEGRADED_REMAP
        assert p1.target_for("b") == INTERPRET
        p2 = lanefault.degraded_plan(base, {"a": 2})
        assert p2.target_for("a") == DEGRADED_REDUCED
        p3 = lanefault.degraded_plan(base, {"a": 3})
        assert p3.target_for("a") == SW
    assert lanefault.degraded_plan(base, {"a": 1}) == base  # map cleared


def test_set_map_rejects_degraded_base():
    f = LaneFault(kind=lanefault.STUCK, lanes=(1,), width=8)
    with pytest.raises(ValueError):
        lanefault.set_map("s", f, base=DEGRADED_REMAP)


# ------------------------------------------------------- capacity model
def test_degradation_model_legacy_equivalence_and_partials():
    m = DegradationModel(curve=(1.0, 0.38, 0.19))
    # no rungs: exactly the legacy scalar curve (Fig. 2 unchanged)
    assert [m.factor(k) for k in (0, 1, 2, 5)] == [1.0, 0.38, 0.19, 0.19]
    # one remapped fault: absorbed off the curve, charged its partial
    assert m.factor(1, (("s", DEGRADED_REMAP),)) == pytest.approx(0.85)
    assert m.factor(1, (("s", DEGRADED_REDUCED),)) == pytest.approx(0.6)
    # reduced absorbs TWO faults (its ladder position)
    assert m.factor(2, (("s", DEGRADED_REDUCED),)) == pytest.approx(0.6)
    # a third fault on top bottoms out at SW: curve step re-applies
    assert m.factor(3, (("s", DEGRADED_REDUCED),)) == pytest.approx(
        0.38 * 0.6)
    # per-(stage, rung) override wins over the default
    m2 = DegradationModel(partial=((("s", DEGRADED_REMAP), 0.9),))
    assert m2.factor(1, (("s", DEGRADED_REMAP),)) == pytest.approx(0.9)
    assert m2.factor(1, (("t", DEGRADED_REMAP),)) == pytest.approx(0.85)
    with pytest.raises(ValueError):
        DegradationModel(partial=((("s", SW), 0.5),))
    assert m.slot_cap(6, 1, (("s", DEGRADED_REMAP),)) == 5   # round(5.1)


def test_degradation_model_rungs_of_reads_plan():
    f = LaneFault(kind=lanefault.STUCK, lanes=(1,), width=64)
    with lanefault.known_map("swiglu_mlp", f, base=INTERPRET):
        plan = RoutingPlan.make({"swiglu_mlp": DEGRADED_REMAP,
                                 "flash_attention": SW})
        assert DegradationModel.rungs_of(plan) == (
            ("swiglu_mlp", DEGRADED_REMAP),)


# ----------------------------------------------- deterministic fault logs
def test_fault_log_interleavings_merge_identically():
    """Two replicas' events arrive in different cross-origin
    interleavings (each origin's own emission order is what the seq stamp
    encodes, so it stays fixed — exactly FleetEvent's semantics); the
    merged logs are identical lists (the logical-stamp satellite)."""
    def run(order):
        h0, h1 = FaultState(origin="h0"), FaultState(origin="h1")
        events = {
            "a": lambda: h0.mark("flash_attention", step=2, kind="canary"),
            "b": lambda: h1.mark("swiglu_mlp", step=2, kind="injected"),
            "c": lambda: h0.note("<step>", step=3, kind="nan_guard"),
            "d": lambda: h1.mark("flash_attention", step=4),
        }
        for k in order:
            events[k]()
        # cross-observe the other replica's entries (any order)
        for e in list(h1.log):
            h0.observe(e)
        for e in list(h0.log):
            if e["origin"] == "h0":
                h1.observe(e)
        return h0, h1
    h0a, h1a = run("abcd")       # h0 emits a then c; h1 emits b then d
    h0b, h1b = run("badc")       # cross-origin order shuffled
    merged = FaultState.merge_logs(h0a.log, h1a.log)
    assert merged == FaultState.merge_logs(h0b.log, h1b.log)
    assert merged == FaultState.merge_logs(h1b.log, h0b.log)  # arg order
    # no wall-clock anywhere; stamps are exactly (step, origin, seq)
    for e in merged:
        assert set(e) == {"stage", "replica", "kind", "step", "origin",
                          "seq"}
    # observe folds counts identically on both sides
    assert h0a.count("flash_attention") == h0b.count("flash_attention") == 2


def test_fault_counts_drive_ladder_input():
    st = FaultState()
    st.mark("a", step=1)
    st.mark("a", step=2)
    st.mark("b", step=2)
    st.note("a", step=3)                       # log-only: no count
    assert st.counts(["a", "b", "c"]) == {"a": 2, "b": 1, "c": 0}
    assert st.count("a") == 2 and st.n_faults() == 2


# ------------------------------------------------- injection no-op guard
def test_injector_bitflip_corrupts_zero_heavy_input():
    inj = FaultInjector(kind="bitflip", magnitude=0.25)
    bad = inj.wrap(lambda: jnp.zeros((4, 4)))
    out = np.asarray(bad())                    # must not raise: zeros flip
    assert np.count_nonzero(out) == 1 and out.reshape(-1)[8] == 0.25


@pytest.mark.parametrize("kind", ["stuck_zero", "gain"])
def test_injector_noop_on_zeros_fails_loudly(kind):
    inj = FaultInjector(kind=kind)
    with pytest.raises(InjectionNoOpError):
        inj.wrap(lambda: jnp.zeros((4, 4)))()


@pytest.mark.parametrize("kind", ["bitflip", "stuck_zero", "gain"])
def test_injector_corrupts_nonzero_input(kind):
    inj = FaultInjector(kind=kind)
    clean = jnp.arange(1.0, 17.0).reshape(4, 4)
    out = np.asarray(inj.wrap(lambda: clean)())
    assert not np.array_equal(out, np.asarray(clean))


# --------------------------------------------------- narrowed fail-opens
def test_canary_expected_errors_flag_fault_and_log(caplog):
    def boom(x):
        raise FloatingPointError("invalid value (nan) in the datapath")
    stage = Stage(name="s", hw=boom, sw=lambda x: x,
                  ports=(jax.ShapeDtypeStruct((4,), jnp.float32),),
                  tol=0.0)
    chk = CanaryChecker([stage])
    with caplog.at_level(logging.WARNING, logger="repro.core.fault"):
        assert chk.check_stage(stage) is False
    assert any("treating as a fault" in r.message for r in caplog.records)


def test_canary_unexpected_errors_propagate():
    def bug(x):
        raise RuntimeError("a genuine bug, not a fault signal")
    stage = Stage(name="s", hw=bug, sw=lambda x: x,
                  ports=(jax.ShapeDtypeStruct((4,), jnp.float32),),
                  tol=0.0)
    chk = CanaryChecker([stage])
    with pytest.raises(RuntimeError, match="genuine bug"):
        chk.check_stage(stage)
    assert RuntimeError not in EXPECTED_STAGE_ERRORS


def test_canary_lowering_refusal_raises_instead_of_quarantining():
    """A kernel the compiler refuses (Mosaic raises ValueError) is a
    program error, not a fault: the canary re-raises, nothing is marked,
    and the stage is never quietly rerouted onto its oracle."""
    def refused(x):
        raise ValueError("The Pallas TPU lowering currently requires that "
                         "the last two dimensions of your block shape are "
                         "divisible by 8 and 128 respectively")
    stage = Stage(name="s", hw=refused, sw=lambda x: x,
                  ports=(jax.ShapeDtypeStruct((4,), jnp.float32),),
                  tol=1e-3)
    chk = CanaryChecker([stage], localize=True)
    state = FaultState()
    with pytest.raises(ValueError, match="block shape"):
        chk.sweep(state, step=1)
    assert state.log == [] and not state.is_faulty("s")
    assert ValueError not in EXPECTED_STAGE_ERRORS


def test_sharding_constrain_narrow_except(monkeypatch):
    x = jnp.ones((4, 4))
    assert sharding.constrain(x, "batch") is x     # no rules: no-op
    with sharding.axis_rules({"batch": None}):
        def spec_error(*a, **k):
            raise ValueError("rank mismatch")
        monkeypatch.setattr(jax.lax, "with_sharding_constraint", spec_error)
        assert sharding.constrain(x, "batch") is x  # expected: swallowed

        def bug(*a, **k):
            raise RuntimeError("not a spec error")
        monkeypatch.setattr(jax.lax, "with_sharding_constraint", bug)
        with pytest.raises(RuntimeError, match="not a spec error"):
            sharding.constrain(x, "batch")


# ------------------------------------------------------ lane localization
@pytest.mark.parametrize("kind,expect", [
    ("stuck", lanefault.STUCK),
    ("dropped", lanefault.DROPPED_MAC),
    ("gain", lanefault.GAIN),
])
def test_canary_localizes_each_fault_kind(kind, expect):
    """An injected lane fault of each kind is detected, localized to the
    right lanes, classified, and registered as a map (unlocking DEGRADED
    routing instead of a binary SW drop)."""
    name = "swiglu_mlp"
    stage = _stage(name)
    lanes, w = (2, 9), WIDTH[name]
    fault = LaneFault(kind={"stuck": lanefault.STUCK,
                            "dropped": lanefault.DROPPED_MAC,
                            "gain": lanefault.GAIN}[kind],
                      lanes=lanes, width=w, value=2.5, gain=3.0)
    state = FaultState()
    chk = CanaryChecker([stage], route_hw=INTERPRET, localize=True)
    with lanefault.inject(name, fault):
        found = chk.sweep(state, step=5)
    assert found == [name]
    assert state.log[-1]["kind"] == "canary_localized"
    assert state.log[-1]["step"] == 5
    located = lanefault.fault_map(name)
    assert located is not None and located.lanes == lanes
    assert located.kind == expect
    assert lanefault.map_base(name) == INTERPRET
def test_canary_whole_tile_breakage_stays_binary():
    """A defect touching EVERY output lane is not lane-shaped: localize
    returns no map and the stage takes the binary SW quarantine."""
    st2 = Stage(name="whole", hw=lambda x: x + 1.0, sw=lambda x: x,
                ports=(jax.ShapeDtypeStruct((4, 8), jnp.float32),),
                tol=1e-3)
    chk = CanaryChecker([st2], localize=True)
    state = FaultState()
    found = chk.sweep(state, step=6)
    assert found == ["whole"]
    assert state.log[-1]["kind"] == "canary"
    assert lanefault.fault_map("whole") is None
