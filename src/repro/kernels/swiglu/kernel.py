"""Pallas TPU kernel for the fused gated MLP.

Fuses both matmuls of the gated MLP so the (M, F) hidden activations never
round-trip to HBM — and, inside each grid step, streams the hidden tile in
``bs``-column sub-tiles so the gate product ``act(x@w1) * (x@w3)`` is never
materialized wider than (bm, bs): each sub-tile is activated, gated, and
immediately contracted against its w2 rows in a **single pass over the
hidden dim**.  Grid (nM, nF), F minor-most; the (BM, D) output accumulator
persists in VMEM scratch across the F loop and is flushed once per M block.

Arithmetic-intensity argument: the unfused pair reads/writes 2*M*F hidden
values through HBM; fusion removes that traffic entirely, and the sub-tile
pass caps the live gate intermediate at bm*bs values, which is what lets
the tuner push ``bf`` up (weight-reuse) without blowing the VMEM budget.

Tile knobs (bm, bf, bs) are swept by ``kernels/tuning`` — see
``space.py`` for the admissibility rules this kernel asserts.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.swiglu.ref import gate
from repro.viscosity.lanefault import apply_fault


def _swiglu_kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_scr, *,
                   nf: int, bs: int, act: str, lane_fault=None):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)         # (BM, D)
    bf = w1_ref.shape[1]
    # Single pass over this grid step's hidden tile: activate, gate, and
    # contract one (BM, bs) sub-tile at a time (static unroll, bf/bs small).
    for j in range(bf // bs):
        cols = slice(j * bs, (j + 1) * bs)
        w1 = w1_ref[:, cols].astype(jnp.float32)   # (D, bs)
        w3 = w3_ref[:, cols].astype(jnp.float32)
        w2 = w2_ref[cols, :].astype(jnp.float32)   # (bs, D)
        h1 = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        h3 = jax.lax.dot_general(x, w3, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        g = gate(h1, act) * h3                     # (BM, bs): never wider
        acc_scr[...] += jax.lax.dot_general(
            g, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(fi == nf - 1)
    def _flush():
        # Value-level fault injection (lanefault): a static LaneFault
        # corrupts the output tile's lane axis at the single flush point —
        # the masked-where only exists in the trace when a fault is
        # registered, so healthy builds are byte-identical.
        o_ref[...] = apply_fault(acc_scr[...],
                                 lane_fault).astype(o_ref.dtype)


def swiglu_pallas(x, w1, w3, w2, *, act: str = "silu", bm: int = 128,
                  bf: int = 512, bs: int = 128, interpret: bool = False,
                  lane_fault=None, vmem_limit_bytes: Optional[int] = None):
    """x (M, D); w1/w3 (D, F); w2 (F, Do). M % bm == 0, F % bf == 0,
    bf % bs == 0 (after clamping each knob to its dim).  The output width
    is ``w2.shape[1]`` — normally D, narrower under DEGRADED_REDUCED
    (reduced-width execution slices w2 to the surviving lanes).
    ``vmem_limit_bytes`` raises Mosaic's scoped-VMEM limit for tiles
    whose blocks outgrow its default (None keeps the default)."""
    M, D = x.shape
    F = w1.shape[1]
    Do = w2.shape[1]
    bm = min(bm, M)
    bf = min(bf, F)
    bs = min(bs, bf)
    assert M % bm == 0 and F % bf == 0, (M, bm, F, bf)
    assert bf % bs == 0, (bf, bs)
    grid = (M // bm, F // bf)
    extra = {} if vmem_limit_bytes is None else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_limit_bytes))}
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, nf=F // bf, bs=bs, act=act,
                          lane_fault=lane_fault),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, D), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((D, bf), lambda mi, fi: (0, fi)),
            pl.BlockSpec((D, bf), lambda mi, fi: (0, fi)),
            pl.BlockSpec((bf, Do), lambda mi, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((bm, Do), lambda mi, fi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((M, Do), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, Do), jnp.float32)],
        interpret=interpret,
        name="swiglu",
        **extra,
    )(x, w1, w3, w2)
