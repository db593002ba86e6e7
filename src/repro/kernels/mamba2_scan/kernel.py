"""Pallas TPU kernel for the chunked Mamba2 SSD scan.

TPU-native design: grid = (B, H, n_chunks) with the chunk axis minor-most;
the (N, P) SSM state lives in VMEM scratch and persists across sequential
chunk iterations (the recurrent carry).  All per-chunk work is expressed as
MXU matmuls on (L, N)/(L, P) tiles:

    CB     = C @ B^T                      (L, L)  MXU
    y_in   = (CB * decay * mask) @ xdt    (L, L)@(L, P)  MXU
    y_st   = (C @ state) * exp(cum)       (L, N)@(N, P)  MXU
    state' = exp(tot) * state + (B*scale)^T @ xdt  (N, L)@(L, P)  MXU

Inputs are pre-scaled outside the kernel: ``xdt = x * dt`` and the
within-chunk decay prefix sums ``cum = cumsum(dt * A)`` (as a column and
as a row, plus the chunk total), so the kernel touches only dense operands
and no scan.  Operands are laid out head-major and chunked,
``(B, H, n_chunks, L, ·)``, so the last two dims of every block are whole
array dims — the Mosaic tiling rule holds for any chunk length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.viscosity.lanefault import apply_fault


def _ssd_kernel(xdt_ref, cum_col_ref, cum_row_ref, tot_ref, b_ref, c_ref,
                y_ref, state_scr, *, L: int, lane_fault=None):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[...]                                 # (L, P) f32
    cum_col = cum_col_ref[...]                         # (L, 1)
    cum_row = cum_row_ref[...]                         # (1, L)
    tot = tot_ref[...]                                 # (1, 1)
    b = b_ref[...].astype(jnp.float32)                 # (L, N)
    c = c_ref[...].astype(jnp.float32)                 # (L, N)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    dec = jnp.exp(cum_col - cum_row)
    w = jnp.where(rows >= cols, cb * dec, 0.0)
    y_intra = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state = state_scr[...]                             # (N, P)
    y_state = jax.lax.dot_general(c, state, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y_intra + y_state * jnp.exp(cum_col)
    # Value-level fault injection (lanefault): masked corruption of the
    # chunk's head-channel lane axis; absent from the trace when healthy.
    y_ref[...] = apply_fault(y, lane_fault).astype(y_ref.dtype)

    bscale = b * jnp.exp(tot - cum_col)                # (L, N)
    upd = jax.lax.dot_general(bscale, xdt, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(tot) + upd


def ssd_chunked_pallas(x, dt, A, B_, C, *, chunk: int = 128,
                       interpret: bool = False, lane_fault=None):
    """x (B,S,H,P), dt (B,S,H), A (H,), B_/C (B,S,N) -> y (B,S,H,P).

    S must be a multiple of ``chunk`` (ops wrapper pads).  Final state is
    not returned by the kernel path (training does not need it; decode uses
    ``ssd_step``).
    """
    Bt, S, H, P = x.shape
    N = B_.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L
    f32 = jnp.float32

    xdt = x.astype(f32) * dt.astype(f32)[..., None]
    xdt = xdt.reshape(Bt, nc, L, H, P).transpose(0, 3, 1, 2, 4)
    da = (dt.astype(f32) * A.astype(f32)[None, None, :])
    cum = jnp.cumsum(da.reshape(Bt, nc, L, H).transpose(0, 3, 1, 2), -1)
    tot = cum[..., -1:]                                # (B, H, nc, 1)

    def per_chunk(*tail):     # block of one (b, h, chunk), whole last dims
        return pl.BlockSpec((None, None, None) + tail,
                            lambda b, h, ci: (b, h, ci, 0, 0))

    def per_seq(*tail):       # B_/C: shared across heads
        return pl.BlockSpec((None, None) + tail,
                            lambda b, h, ci: (b, ci, 0, 0))

    kernel = functools.partial(_ssd_kernel, L=L, lane_fault=lane_fault)
    y = pl.pallas_call(
        kernel,
        grid=(Bt, H, nc),
        in_specs=[per_chunk(L, P), per_chunk(L, 1), per_chunk(1, L),
                  per_chunk(1, 1), per_seq(L, N), per_seq(L, N)],
        out_specs=per_chunk(L, P),
        out_shape=jax.ShapeDtypeStruct((Bt, H, nc, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), f32)],
        interpret=interpret,
    )(xdt, cum[..., None], cum[..., None, :], tot[..., None],
      B_.reshape(Bt, nc, L, N), C.reshape(Bt, nc, L, N))
    return y.transpose(0, 2, 3, 1, 4).reshape(Bt, S, H, P)
