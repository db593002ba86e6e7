"""Autotuner subsystem: cache lifecycle, search behavior, admissibility.

Everything here runs with a **synthetic** measure callable and a tmp-dir
cache: no kernel compiles, no wall-clock flakiness.  The real-workload
end of the tuner (jit + time) is exercised by ``benchmarks/kernel_micro``.
"""
import json

import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.oobleck import Dispatcher
from repro.kernels import tuning
from repro.kernels.tuning import tuner
from repro.kernels.tuning.cache import TuningCache, plan_digest
from repro.kernels.tuning.space import (MXU_LANE, SPACES, SUBLANE_F32,
                                        VMEM_BUDGET_BYTES, space_for)

SWIGLU_SHAPE = (256, 128, 1024)    # (M, D, F)
FLASH_SHAPE = (2, 128, 128, 8, 2, 64)   # (B, Sq, Skv, H, Hkv, D)


@pytest.fixture
def cache(tmp_path):
    """Process tuning cache pointed at a tmp dir with a pinned
    fingerprint (tests never touch the real artifacts/ cache)."""
    tuning.reset()
    c = TuningCache(str(tmp_path), fingerprint="jax-test/cpu/TestCpu")
    tuning.set_cache(c)
    yield c
    tuning.reset()


def _swiglu_cost(cfg):
    """Synthetic convex-ish surface with the optimum away from defaults."""
    return (abs(cfg["bm"] - 64) + abs(cfg["bf"] - 256) / 8
            + abs(cfg["bs"] - 128) / 16 + 1.0)


# --------------------------------------------------------- cache lifecycle
def test_cold_miss_then_tune_then_warm_hit(cache, tmp_path):
    # cold: no entry anywhere
    assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                         jnp.float32) is None
    assert tuning.stats()["misses"] == 1 and tuning.stats()["hits"] == 0

    cfg, us = tuning.tune_kernel("swiglu_mlp", "hw", SWIGLU_SHAPE,
                                 jnp.float32, measure=_swiglu_cost,
                                 budget=200)
    assert cfg == {"bm": 64, "bf": 256, "bs": 128}
    assert us == pytest.approx(_swiglu_cost(cfg))

    # warm: the same process hits
    assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                         jnp.float32) == cfg
    assert tuning.stats()["hits"] == 1 and tuning.stats()["tuned"] == 1

    # persisted: a brand-new cache object on the same dir + fingerprint
    # (a later process) reloads the entry from disk
    fresh = TuningCache(str(tmp_path), fingerprint=cache.fingerprint)
    assert fresh.get("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32) == cfg
    doc = json.load(open(cache.path))
    assert cache.fingerprint in doc["by_backend"]


def test_fingerprint_partitions_the_cache(cache, tmp_path):
    cfg = {"bm": 64, "bf": 256, "bs": 128}
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32, cfg, us=1.0)
    other = TuningCache(str(tmp_path), fingerprint="jax-other/tpu/v5e")
    # same file, different backend: cold miss, never a cross-backend leak
    assert other.get("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32) is None
    same = TuningCache(str(tmp_path), fingerprint=cache.fingerprint)
    assert same.get("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32) == cfg


def test_corrupt_cache_fails_open(cache, tmp_path):
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32,
              {"bm": 64, "bf": 256, "bs": 128})
    with open(cache.path, "w") as f:
        f.write("{ not json")
    cache.invalidate()
    # corrupt file == empty cache: lookup is None, nothing raises
    assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                         jnp.float32) is None
    # and a put over the corrupt file recovers it
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32,
              {"bm": 32, "bf": 512, "bs": 256})
    assert json.load(open(cache.path))["schema"] == 1


def test_stale_inadmissible_entry_is_ignored(cache):
    # an entry persisted under an older search space that today's kernel
    # would reject (bm=12 breaks M % bm) must be filtered by lookup
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32,
              {"bm": 12, "bf": 256, "bs": 128})
    assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                         jnp.float32) is None


def test_plan_scoped_lookup_prefers_plan_entry(cache):
    plan_key = ("stage0:sw", "stage1:hw")
    default_cfg = {"bm": 128, "bf": 512, "bs": 128}
    plan_cfg = {"bm": 64, "bf": 256, "bs": 128}
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32, default_cfg)
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32, plan_cfg,
              plan=plan_digest(plan_key))
    assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                         jnp.float32) == default_cfg
    with tuning.plan_scope(plan_key):
        assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                             jnp.float32) == plan_cfg
    # a plan with no dedicated entry falls back to the default entry
    with tuning.plan_scope(("some", "other", "plan")):
        assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                             jnp.float32) == default_cfg


def test_disabled_by_env(cache, monkeypatch):
    cache.put("swiglu_mlp", "hw", SWIGLU_SHAPE, jnp.float32,
              {"bm": 64, "bf": 256, "bs": 128})
    monkeypatch.setenv("REPRO_TUNER", "off")
    assert tuning.lookup("swiglu_mlp", "hw", SWIGLU_SHAPE,
                         jnp.float32) is None


def test_dispatcher_threads_plan_scope_to_lookups(cache):
    seen = {}

    def build(key):
        seen["build"] = tuning.current_plan_key()

        def fn(x):
            seen["call"] = tuning.current_plan_key()
            return x

        return fn

    d = Dispatcher(build)
    assert d(("planA",), 1) == 1
    assert seen == {"build": ("planA",), "call": ("planA",)}
    assert tuning.current_plan_key() is None   # scope did not leak


# ------------------------------------------------------------- the search
def test_tuner_sweeps_and_hillclimbs_to_optimum(cache):
    cfg, us, evals = tuner.tune("swiglu_mlp", "hw", SWIGLU_SHAPE,
                                measure=_swiglu_cost, budget=500)
    assert cfg == {"bm": 64, "bf": 256, "bs": 128}
    assert evals <= 500


def test_tuner_respects_budget(cache):
    calls = []

    def measure(cfg):
        calls.append(dict(cfg))
        return float(len(calls))

    _, _, evals = tuner.tune("swiglu_mlp", "hw", SWIGLU_SHAPE,
                             measure=measure, budget=5)
    assert evals == 5 and len(calls) == 5


def test_crashing_config_never_aborts_search(cache):
    def measure(cfg):
        if cfg["bm"] != 64:
            raise RuntimeError("simulated tile crash")
        return float(cfg["bf"])

    cfg, us, _ = tuner.tune("swiglu_mlp", "hw", SWIGLU_SHAPE,
                            measure=measure, budget=500)
    assert cfg["bm"] == 64 and cfg["bf"] == 128


def test_tuner_raises_when_nothing_measures(cache):
    def measure(cfg):
        raise RuntimeError("all tiles crash")

    with pytest.raises(RuntimeError, match="no admissible config"):
        tuner.tune("swiglu_mlp", "hw", SWIGLU_SHAPE, measure=measure,
                   budget=10)


def test_seeded_default_bounds_the_result(cache):
    # the kernel default is always in the sweep, so the tuned config can
    # never score worse than it on the same surface
    default = dict(SPACES[("swiglu_mlp", "hw")].defaults)
    _, us, _ = tuner.tune("swiglu_mlp", "hw", SWIGLU_SHAPE,
                          measure=_swiglu_cost, budget=500)
    assert us <= _swiglu_cost(default)


# --------------------------------------------- admissibility (properties)
@settings(max_examples=30, deadline=None)
@given(mi=st.sampled_from([8, 16, 64, 256, 1024]),
       fi=st.sampled_from([128, 256, 1024, 4096]))
def test_swiglu_sweep_configs_are_admissible(mi, fi):
    shape = (mi, 128, fi)
    space = space_for("swiglu_mlp", "hw")
    cfgs = list(space.configs(shape))
    assert cfgs, f"empty sweep for {shape}"
    for cfg in cfgs:
        assert space.admissible(cfg, shape)
        bm, bf = min(cfg["bm"], mi), min(cfg["bf"], fi)
        assert mi % bm == 0 and fi % bf == 0   # grid divisibility
        assert bf % min(cfg["bs"], bf) == 0    # hidden sub-tile streams
        assert space.vmem(cfg, shape) <= space.vmem_budget


@settings(max_examples=30, deadline=None)
@given(sq=st.sampled_from([8, 32, 128, 512, 2048]),
       skv=st.sampled_from([8, 32, 128, 512, 2048]),
       d=st.sampled_from([64, 128]))
def test_flash_sweep_configs_are_admissible(sq, skv, d):
    shape = (2, sq, skv, 8, 2, d)
    space = space_for("flash_attention", "hw")
    cfgs = list(space.configs(shape))
    assert cfgs, f"empty sweep for {shape}"
    for cfg in cfgs:
        # MXU geometry: sublane-aligned score tiles, VMEM under budget
        assert cfg["bq"] % SUBLANE_F32 == 0
        assert cfg["bk"] % SUBLANE_F32 == 0
        assert cfg["bq"] <= -(-max(sq, 8) // 8) * 8
        assert cfg["bk"] <= -(-max(skv, 8) // 8) * 8
        assert space.vmem(cfg, shape) <= VMEM_BUDGET_BYTES
    assert MXU_LANE % SUBLANE_F32 == 0   # geometry sanity


@settings(max_examples=20, deadline=None)
@given(key=st.sampled_from(sorted(SPACES)),
       i=st.integers(0, 10 ** 6))
def test_neighbors_stay_admissible(key, i):
    space = SPACES[key]
    shape = {"flash_attention": FLASH_SHAPE,
             "swiglu_mlp": SWIGLU_SHAPE,
             "mamba2_ssd": (2, 512, 4, 32, 16),
             "rwkv6_wkv": (2, 256, 4, 16, 16)}[key[0]]
    cfgs = list(space.configs(shape))
    cfg = cfgs[i % len(cfgs)]
    for cand in space.neighbors(cfg, shape):
        assert space.admissible(cand, shape)
        # a neighbor changes exactly one knob by one choice index
        diff = [n for n in space.params if cand[n] != cfg[n]]
        assert len(diff) == 1
        choices = space.params[diff[0]]
        assert abs(choices.index(cand[diff[0]])
                   - choices.index(cfg[diff[0]])) == 1
