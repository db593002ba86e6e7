"""Jit'd wrapper + Viscosity registration for the Mamba2 SSD stage."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import viscosity
from repro.kernels import tuning
from repro.kernels.mamba2_scan import ref as _ref
from repro.kernels.mamba2_scan.kernel import ssd_chunked_pallas
from repro.viscosity import lanefault


def _tuned_chunk(kind, x, B_, default):
    cfg = tuning.lookup(
        "mamba2_ssd", kind,
        (x.shape[0], x.shape[1], x.shape[2], x.shape[3], B_.shape[-1]),
        x.dtype) or {}
    return cfg.get("chunk") or default


def _sw(x, dt, A, B_, C, *, chunk=None):
    chunk = chunk or _tuned_chunk("sw", x, B_, 128)
    y, _ = _ref.ssd_chunked(x, dt, A, B_, C, chunk=chunk)
    return y


def _hw(x, dt, A, B_, C, *, chunk=None, interpret: bool = False):
    chunk = chunk or _tuned_chunk("hw", x, B_, 128)
    S = x.shape[1]
    L = min(chunk, S)
    if S % L:
        pad = L - S % L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y = ssd_chunked_pallas(x, dt, A, B_, C, chunk=L, interpret=interpret,
                           lane_fault=lanefault.injection("mamba2_ssd"))
    return y[:, :S]


def _lane_slicer(args, kw, keep):
    # y's head-channel lane j depends only on x[..., j] (the SSD mixes over
    # sequence/state, never across P): slicing x is exact reduced width.
    x, dt, A, B_, C = args
    return (x[..., jnp.asarray(keep, jnp.int32)], dt, A, B_, C), kw


def _canary_domain(x, dt, A, B_, C):
    # The model feeds dt = softplus(.) > 0 and A = -exp(A_log) < 0, so
    # every decay exp(dt * A) is <= 1 and the state stays bounded.  Raw
    # N(0, 1) draws give dt * A > 0 half the time, and the scan then grows
    # without bound (kernel and oracle agree only in relative terms).
    return x, jax.nn.softplus(dt), -jnp.exp(A), B_, C


SSD = viscosity.defop(
    "mamba2_ssd",
    ref=_sw,
    kernel=_hw,
    interpret=functools.partial(_hw, interpret=True),
    valid=viscosity.finite_valid,
    tol=2e-2,
    flops=lambda x, dt, A, B_, C, **kw: _ref.ssd_flops(
        x.shape[0], x.shape[1], x.shape[2], x.shape[3], B_.shape[-1]),
    lane_slicer=_lane_slicer,
    canary_domain=_canary_domain,
)


def ssd(x, dt, A, B_, C, *, route: str = viscosity.SW, **kw):
    return SSD(x, dt, A, B_, C, route=route, **kw)
