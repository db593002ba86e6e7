"""Compiles for a described TPU v5e: the served path's kernels and programs.

Nothing here runs on a chip.  The TPU compiler is installed with jaxlib,
and it compiles for a topology that is described but not attached, so
these tests catch what interpret mode cannot: block shapes Mosaic refuses,
VMEM overruns, programs that do not fit.  Shapes are qwen1.5-4b's published
widths (d_model 2560, d_ff 6912, 20 heads x 128); the whole-program cases
cut depth to one layer and keep every width.

The topology is described inside a module fixture — never at import time:
only one process may load the TPU library, and it holds it until it exits.
The persistent compilation cache is off for the module (an entry written
for a described chip cannot be read back without one).
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.routing import RoutingPlan
from repro.kernels.checksum import checksum
from repro.kernels.flash_attention import attention
from repro.kernels.mamba2_scan import ssd
from repro.kernels.rwkv6_scan import wkv6
from repro.kernels.swiglu import swiglu
from repro.launch.serve import served_config
from repro.models import build_model
from repro.models.model import decode_state_specs, params_specs
from repro.serve.engine import RECOMPILE, RESIDENT, build_decode, \
    build_prefill
from repro.train.runner import model_stage_names
from repro.viscosity import HW

D, F, H, HD = 2560, 6912, 20, 128     # qwen1.5-4b widths
MAX_LEN = 256
SLOTS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def tree(t):
        return jax.tree_util.tree_map(lambda s: make(s.shape, s.dtype), t)

    make.tree = tree
    return make


def _compile_text(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_described_chip_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


# ------------------------------------------------------------- kernels
@pytest.mark.parametrize("M", [1, 4, 12, 13, 128, 200])
def test_swiglu_kernel_compiles(sds, M):
    """Row counts that are no sublane multiple (a 12- or 13-token
    prefill, a one-token decode) are padded to an aligned row tile."""
    fn = functools.partial(swiglu, route=HW)
    txt = _compile_text(fn, sds((M, D)), sds((D, F)), sds((D, F)),
                        sds((F, D)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("S", [12, 200, 512])
def test_flash_attention_kernel_compiles(sds, S):
    fn = functools.partial(attention, causal=True, route=HW)
    q = sds((1, S, H, HD))
    txt = _compile_text(fn, q, q, q)
    assert "tpu_custom_call" in txt


# Canary shapes (train/runner.canary_stages), f32 as the canaries draw.
SCAN_CASES = {
    "mamba2_ssd": (ssd, ((2, 64, 2, 16), (2, 64, 2), (2,), (2, 64, 8),
                         (2, 64, 8))),
    "rwkv6_wkv": (wkv6, ((2, 32, 2, 16), (2, 32, 2, 16), (2, 32, 2, 16),
                         (2, 32, 2, 16), (2, 16))),
    "checksum": (checksum, ((64, 64),)),
}


@pytest.mark.parametrize("name", sorted(SCAN_CASES))
def test_canary_kernels_compile(sds, name):
    op, shapes = SCAN_CASES[name]
    fn = functools.partial(op, route=HW)
    txt = _compile_text(fn, *(sds(s, jnp.float32) for s in shapes))
    assert "tpu_custom_call" in txt


# ----------------------------------------------------- served programs
def _served(sds, num_layers):
    """qwen1.5-4b at published widths, cut to ``num_layers``, as the server
    holds it (bf16 weights): the config, the all-HW plan, placed shapes."""
    cfg = dataclasses.replace(served_config("qwen1.5-4b", full=True),
                              num_layers=num_layers)
    plan = RoutingPlan.for_stages(model_stage_names(cfg), target=HW)
    model = build_model(cfg)
    params = sds.tree(params_specs(model))
    return cfg, plan, model, params


@pytest.fixture(scope="module")
def one_layer(sds):
    return _served(sds, 1)


@pytest.fixture(scope="module")
def two_layers(sds):
    """Two layers: the smallest stack whose programs lay the KV pool and
    the stacked weights out as the full model does (one layer does not)."""
    return _served(sds, 2)


def _mask(sds, cfg):
    return sds((len(model_stage_names(cfg)),), jnp.bool_)


@pytest.mark.parametrize("failover,P", [(RECOMPILE, 13), (RECOMPILE, 37),
                                        (RECOMPILE, 200), (RESIDENT, 37)])
def test_prefill_program_compiles(sds, one_layer, failover, P):
    cfg, plan, model, params = one_layer
    cache = sds.tree(jax.eval_shape(lambda: model.init_cache(1, MAX_LEN)))
    batch = {"tokens": sds((1, P), jnp.int32), "cache": cache}
    args = (params, batch) + ((_mask(sds, cfg),) if failover == RESIDENT
                              else ())
    compiled = build_prefill(cfg, plan, failover).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decode_compiled(sds, served, failover):
    """The slot-vmapped decode step the engine runs each tick, compiled,
    and the shape of its KV pool's k and v."""
    cfg, plan, model, params = served
    state, tok, t = decode_state_specs(cfg, model, 1, MAX_LEN)
    slots = jax.tree_util.tree_map(
        lambda s: sds((SLOTS,) + s.shape, s.dtype), (state, tok, t))
    args = (params,) + slots + ((_mask(sds, cfg),) if failover == RESIDENT
                                else ())
    compiled = build_decode(cfg, plan, failover).lower(*args).compile()
    pool = slots[0]["grp"][0]["k"].shape
    return compiled, pool


@pytest.mark.parametrize("failover", [RECOMPILE, RESIDENT])
def test_decode_program_compiles(sds, one_layer, failover):
    compiled, _ = _decode_compiled(sds, one_layer, failover)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("failover", [RECOMPILE, RESIDENT])
def test_decode_updates_kv_pool_in_place(sds, two_layers, failover):
    """The tick writes each slot's new k/v row into the donated pool and
    reads the pool where it lies: no copy of the whole pool's k or v.
    With (Smax, Hkv) as the minor dims the TPU lays the pool out
    seq-major, the vmapped per-slot write runs row-major, and the program
    copies the pool into that layout and back every tick."""
    compiled, pool = _decode_compiled(sds, two_layers, failover)
    shape = ",".join(str(d) for d in pool)
    copies = re.findall(rf"= bf16\[{shape}\]\S* copy\(", compiled.as_text())
    assert not copies, f"{len(copies)} copies of the KV pool {pool}"


@pytest.mark.parametrize("failover", [RECOMPILE, RESIDENT])
def test_prefill_keeps_stacked_wv_in_place(sds, two_layers, failover):
    """Writing the heads-major cache in prefill leaves the stacked value
    projection as it lies: no copy of ``wv`` re-laid out for the layer
    loop (524 MB of prefill temp at 40 layers).  The benchmark cell's
    longest prompt and cache length."""
    cfg, plan, model, params = two_layers
    cache = sds.tree(jax.eval_shape(lambda: model.init_cache(1, 1024)))
    batch = {"tokens": sds((1, 640), jnp.int32), "cache": cache}
    args = (params, batch) + ((_mask(sds, cfg),) if failover == RESIDENT
                              else ())
    txt = build_prefill(cfg, plan, failover).lower(*args).compile().as_text()
    wv = [line for line in txt.splitlines()
          if " copy(" in line and re.search(r"\[\\?'wv\\?'\]", line)]
    assert not wv, wv[0][:200]
