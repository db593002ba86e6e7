"""Compiles for a described TPU v5e: the served path's kernels and programs.

Nothing here runs on a chip.  The TPU compiler is installed with jaxlib,
and it compiles for a topology that is described but not attached, so
these tests catch what interpret mode cannot: block shapes Mosaic refuses,
VMEM overruns, programs that do not fit.  Shapes are qwen1.5-4b's published
widths (d_model 2560, d_ff 6912, 20 heads x 128) and, where a case says so,
mistral-nemo-12b's (d_model 5120, d_ff 14336, 32 query and 8 kv heads x
128); the whole-program cases cut depth and keep every width.

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
from repro.kernels.swiglu.kernel import swiglu_pallas
from repro.kernels.tuning import space
from repro.launch.serve import served_config
from repro.models import build_model
from repro.models.model import decode_state_specs, params_specs
from repro.serve.engine import RECOMPILE, RESIDENT, build_decode, \
    build_prefill
from repro.train.runner import model_stage_names
from repro.viscosity import HW

D, F, H, HD = 2560, 6912, 20, 128     # qwen1.5-4b widths
NEMO_D, NEMO_F = 5120, 14336          # mistral-nemo-12b widths
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
@pytest.mark.parametrize("M,d,f", [
    *(pytest.param(m, D, F, id=str(m)) for m in (1, 4, 12, 13, 128, 200)),
    *(pytest.param(m, NEMO_D, NEMO_F, id=f"nemo-{m}")
      for m in (1, 8, 12, 4096))])
def test_swiglu_kernel_compiles(sds, M, d, f):
    """Row counts that are no sublane multiple (a 12- or 13-token
    prefill, a one-token decode) are padded to an aligned row tile.  At
    mistral-nemo widths the default tiles' blocks outgrow Mosaic's 16 MiB
    scoped-VMEM default, and the call asks for what they need."""
    fn = functools.partial(swiglu, route=HW)
    txt = _compile_text(fn, sds((M, d)), sds((d, f)), sds((d, f)),
                        sds((f, d)))
    assert "tpu_custom_call" in txt


def _scoped_vmem_figure(sds, M, d, f, bm, bf):
    """What the compiler reports when these tiles overrun Mosaic's default
    scoped-VMEM limit ("Scoped allocation with size ..."), in MiB."""
    fn = functools.partial(swiglu_pallas, bm=bm, bf=bf, bs=128)
    with pytest.raises(Exception, match="Scoped allocation") as e:
        _compile_text(fn, sds((M, d)), sds((d, f)), sds((d, f)),
                      sds((f, d)))
    return float(re.search(r"Scoped allocation with size ([\d.]+)M",
                           str(e.value)).group(1))


@pytest.mark.parametrize("M,bm,bf,figure,rel", [
    # the blocks alone overrun the limit: the figure is the blocks
    (4096, 128, 512, 37.50, 0.05), (4096, 128, 256, 22.50, 0.05),
    (8, 8, 512, 30.31, 0.05),
    # the blocks (15.00M) fit and Mosaic's own scratch overruns it: the
    # figure is the blocks and that scratch
    (4096, 128, 128, 16.13, 0.075)])
def test_swiglu_vmem_estimate_matches_compiler(sds, M, bm, bf, figure,
                                               rel):
    """``space._swiglu_hw_vmem`` (what ``_hw`` and the tuner read) against
    the compiler's own figures at mistral-nemo widths, and the limit a
    call asks for (the estimate plus headroom) above each of them."""
    shape = (M, NEMO_D, NEMO_F)
    need = space.SPACES[("swiglu_mlp", "hw")].vmem({"bm": bm, "bf": bf},
                                                   shape)
    assert _scoped_vmem_figure(sds, M, NEMO_D, NEMO_F, bm, bf) == figure
    assert need / space.MIB == pytest.approx(figure, rel=rel)
    assert space.vmem_limit(need) >= figure * space.MIB
    assert need <= space.SPACES[("swiglu_mlp", "hw")].vmem_budget


def _custom_calls(txt):
    """The Mosaic kernels of a compiled program: each custom call's
    operand and result shapes and its backend config (the scoped-VMEM
    limit it asks for and the serialized kernel)."""
    txt = re.sub(r", metadata=\{[^}]*\}", "", txt)
    return sorted(re.findall(r"(\S+ custom-call\(.*?custom_call_target="
                             r"\"tpu_custom_call\".*?backend_config=\S+)",
                             txt))


def _swiglu_hw(x, w1, w3, w2, **tiles):
    return swiglu(x, w1, w3, w2, route=HW, **tiles)


@pytest.mark.parametrize("shape", [(1, D), (13, D), (200, D), (640, D),
                                   (6, 1, D)])
def test_qwen_swiglu_compiles_unchanged(sds, shape):
    """At qwen1.5-4b widths the wrapper picks the tiles it always has (bm
    128, or the rows rounded up to 8; bf 128; bs 128) and asks for no
    scoped-VMEM limit: its kernels are those of the same call with those
    tiles given, byte for byte, with an empty ``scoped_memory_configs``.
    (6, 1, D) is the slot-vmapped decode call."""
    M = shape[-2]
    tiles = {"bm": min(128, -(-M // 8) * 8), "bf": 128, "bs": 128}
    auto = _swiglu_hw
    given = functools.partial(_swiglu_hw, **tiles)
    if len(shape) == 3:
        auto = jax.vmap(auto, in_axes=(0, None, None, None))
        given = jax.vmap(given, in_axes=(0, None, None, None))
    args = (sds(shape), sds((D, F)), sds((D, F)), sds((F, D)))
    # one call site for both: a kernel's locations name its callers' lines
    got, want = (_custom_calls(_compile_text(fn, *args))
                 for fn in (auto, given))
    assert got and got == want
    assert all('"scoped_memory_configs":[]' in c for c in got)


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
def _served(sds, num_layers, arch="qwen1.5-4b"):
    """``arch`` at published widths, cut to ``num_layers``, as the server
    holds it (bf16 weights): the config, the all-HW plan, placed shapes."""
    cfg = dataclasses.replace(served_config(arch, full=True),
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


@pytest.fixture(scope="module")
def nemo_two_layers(sds):
    """mistral-nemo-12b's widths, two layers: grouped-query attention
    (32 query heads on 8 kv heads), a 4096-wide query, d_model 5120."""
    return _served(sds, 2, "mistral-nemo-12b")


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


@pytest.mark.parametrize("served,failover", [
    pytest.param("two_layers", RECOMPILE, id=RECOMPILE),
    pytest.param("two_layers", RESIDENT, id=RESIDENT),
    pytest.param("nemo_two_layers", RESIDENT, id=f"nemo-{RESIDENT}")])
def test_decode_updates_kv_pool_in_place(sds, request, served, failover):
    """The tick writes each slot's new k/v row into the donated pool and
    reads the pool where it lies: no copy of the whole pool's k or v.
    With (Smax, Hkv) as the minor dims the TPU lays the pool out
    seq-major, the vmapped per-slot write runs row-major, and the program
    copies the pool into that layout and back every tick.  With grouped
    queries (nemo: 4 query heads a kv head) a grouped product makes the
    compiler lay the pool out layer-major, two copies a layer."""
    compiled, pool = _decode_compiled(sds, request.getfixturevalue(served),
                                      failover)
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


# ------------------------------------------------- the decode readers' ops
def _events(compiled):
    """The compiled program's device ops as a trace names them (``%name =
    shape opcode(operands...``), each with the ``op_name`` it came from:
    the instructions of every computation that is not a fusion's body.  A
    multi-output fusion carries no ``op_name`` of its own and takes its
    body's; an op with none at all (a copy) takes its operands'."""
    text = compiled.as_text()
    bodies = {}
    for block in text.split("\n\n"):
        head = block.strip().split(" ", 1)[0]
        bodies[head] = " ".join(re.findall(r'op_name="([^"]*)"', block))
    out, origin = [], {}
    for block in text.split("\n\n"):
        lines = block.strip().splitlines()
        if not lines or "fused_computation" in lines[0].split("(")[0]:
            continue
        for line in lines[1:]:
            line = line.strip().removeprefix("ROOT ")
            m = re.match(r"(%\S+) = .*?[\]})] ([a-z][a-z0-9_-]*)\((.*)",
                         line)
            if not m:
                continue
            name = re.search(r'op_name="([^"]*)"', line)
            calls = re.search(r"calls=(%[\w.-]+)", line)
            src = (name.group(1) if name else
                   bodies.get(calls.group(1), "") if calls else
                   " ".join(origin.get(o, "") for o in
                            re.findall(r"%[\w.-]+", m.group(3))))
            origin[m.group(1)] = src
            if m.group(2) not in ("parameter", "get-tuple-element",
                                  "tuple", "bitcast", "constant"):
                out.append((line, src))
    return out


def _reader(name):
    from bench import spec
    return spec.metric_reader(name).__globals__


@pytest.fixture(scope="module")
def nemo_decode_events(sds, nemo_two_layers):
    cfg = nemo_two_layers[0]
    compiled, _ = _decode_compiled(sds, nemo_two_layers, RESIDENT)
    s = {"kv_heads": cfg.num_kv_heads, "heads": cfg.num_heads,
         "head_dim": cfg.head_dim}
    return _events(compiled), s, cfg


def test_decode_attn_reader_hits_attention_ops(nemo_decode_events):
    """``decode_attn_roofline``'s pattern takes the decode attention's ops
    (named ``attention_decode`` in the program) and nothing else: every
    float op over the pool's positions and every product with v, no
    weight slice, no projection.  (The mask, a few bytes a position, is
    left out.)"""
    events, s, cfg = nemo_decode_events
    rx = re.compile(_reader("decode_attn_roofline")["pattern"](s))
    hits = [(e, n) for e, n in events if rx.search(e)]
    assert hits and all("attention_decode" in n for _, n in hits), \
        [e[:120] for e, n in hits if "attention_decode" not in n]
    heavy = [e for e, n in events if "attention_decode" in n
             and (re.match(rf"\(?(f32|bf16)\[[\d,]*,{MAX_LEN}\]",
                           e.split(" = ", 1)[1]) or "bhk,bhkd->bhd" in n)]
    assert len(heavy) >= 3 * cfg.num_layers
    assert set(heavy) <= {e for e, _ in hits}


def test_decode_swiglu_reader_hits_swiglu_calls(nemo_decode_events):
    """``swiglu_roofline.decode``'s pattern takes the swiglu kernel's own
    calls, one a layer, and no op that merely reads one's result or
    slices its weights."""
    events, _, cfg = nemo_decode_events
    rx = re.compile(_reader("swiglu_roofline.decode")["PATTERN"])
    hits = [e for e, _ in events if rx.search(e)]
    assert len(hits) == cfg.num_layers
    assert all(" custom-call(" in e and "tpu_custom_call" in e
               for e in hits)
    assert any("%swiglu" in e for e, _ in events if e not in hits)
