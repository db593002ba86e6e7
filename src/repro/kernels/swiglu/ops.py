"""Jit'd wrapper + Viscosity registration for the fused gated-MLP stage."""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro import viscosity
from repro.kernels import tuning
from repro.kernels.swiglu import ref as _ref
from repro.kernels.swiglu.kernel import swiglu_pallas
from repro.kernels.tuning import space as _space
from repro.obs import metrics
from repro.viscosity import lanefault

SPACE = _space.space_for("swiglu_mlp", "hw")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _hw(x, w1, w3, w2, *, act: str = "silu", interpret: bool = False,
        bm=None, bf=None, bs=None):
    M, D = x.shape
    F = w1.shape[1]
    # Tuned tiles when the cache has an entry for this (shape, dtype,
    # active routing plan); explicit knobs always win; no entry -> the
    # historical hardcoded defaults.  Never fails: tuning.lookup is
    # fail-open by construction.
    if bm is None and bf is None and bs is None:
        cfg = tuning.lookup("swiglu_mlp", "hw", (M, D, F), x.dtype) or {}
    else:
        cfg = {}
    # Row tile: one 128-row MXU tile, or M rounded up to the f32 sublane
    # (8) when M is smaller.  M is padded to a whole number of row tiles
    # and the result sliced back — rows are independent, so the pad rows
    # never touch the real ones (a 13-token prefill, a 1-token decode).
    bm = min(bm or cfg.get("bm") or 128, _round_up(M, 8))
    if bf is None:
        bf = cfg.get("bf") or (512 if F % 512 == 0 else
                               (128 if F % 128 == 0 else F))
    if bs is None:
        bs = cfg.get("bs") or (128 if min(bf, F) % 128 == 0 else bf)
    # Scoped VMEM: tiles whose blocks pass the cap halve bf (while it
    # stays a multiple of 128), then bm; a call whose blocks outgrow
    # Mosaic's default asks for them plus headroom (space.py).
    itemsize = jnp.dtype(x.dtype).itemsize

    def need():       # of the rows as padded to whole row tiles
        return SPACE.vmem({"bm": bm, "bf": bf}, (_round_up(M, bm), D, F),
                          itemsize)

    while need() > SPACE.vmem_budget and min(bf, F) % 256 == 0:
        bf = min(bf, F) // 2
    while need() > SPACE.vmem_budget and bm > 8:
        bm = _round_up(bm // 2, 8)
    bf = min(bf, F)
    bs = min(bs, bf)
    metrics.inc("swiglu_tiles_traced_total", d=D, f=F, bm=bm, bf=bf, bs=bs,
                vmem_mib=f"{need() / _space.MIB:.2f}")
    Mp = _round_up(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    out = swiglu_pallas(x, w1, w3, w2, act=act, bm=bm, bf=bf, bs=bs,
                        interpret=interpret,
                        lane_fault=lanefault.injection("swiglu_mlp"),
                        vmem_limit_bytes=_space.vmem_limit(need()))
    return out[:M]


def _lane_slicer(args, kw, keep):
    # Output lane j depends only on w2[:, j]: slicing w2's columns to the
    # surviving lanes is exact reduced-width execution.
    x, w1, w3, w2 = args
    return (x, w1, w3, w2[:, jnp.asarray(keep, jnp.int32)]), kw


SWIGLU = viscosity.defop(
    "swiglu_mlp",
    ref=_ref.swiglu_ref,
    kernel=_hw,
    interpret=functools.partial(_hw, interpret=True),
    valid=viscosity.finite_valid,
    tol=2e-2,
    flops=lambda x, w1, *a, **kw: _ref.swiglu_flops(
        x.shape[0], x.shape[1], w1.shape[1]),
    lane_slicer=_lane_slicer,
)


def swiglu(x, w1, w3, w2, *, route: str = viscosity.SW, **kw):
    return SWIGLU(x, w1, w3, w2, route=route, **kw)
