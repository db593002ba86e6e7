"""Pallas TPU kernel for the chunked RWKV-6 WKV recurrence.

Same TPU pattern as the SSD kernel: grid (B, H, n_chunks), chunk axis
minor-most, (K, V) state in VMEM scratch carried across chunk iterations.
Matmul (MXU) form with per-channel decays factored into q/k:

    qexp = r * exp(la_prev),  kexp = k * exp(-la)
    o    = mask(qexp @ kexp^T) @ v  +  qexp @ S  +  bonus * v
    S'   = exp(la_L) * S + (k * exp(la_L - la))^T @ v

The within-chunk log-decay prefix sums ``la`` (and the chunk total
``la_L``, as a row and as a column) are computed outside the kernel, and
operands are laid out head-major and chunked, ``(B, H, n_chunks, L, ·)``,
so the last two dims of every block are whole array dims.

f32-range analysis: |la| <= chunk * max|lw|; the model clamps lw >= -4 and
the default chunk is 16, so exp(-la) <= e^64 < f32 max (e^~88) and the
masked upper-triangle garbage stays finite.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.viscosity.lanefault import apply_fault


def _wkv_kernel(r_ref, k_ref, v_ref, la_ref, lw_ref, tot_row_ref,
                tot_col_ref, u_ref, o_ref, state_scr, *, L: int,
                lane_fault=None):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[...].astype(jnp.float32)            # (L, K)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)            # (L, V)
    la = la_ref[...]                              # (L, K) f32
    u = u_ref[...].astype(jnp.float32)            # (1, K)

    qexp = r * jnp.exp(la - lw_ref[...])
    kexp = k * jnp.exp(-la)
    scores = jax.lax.dot_general(qexp, kexp, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L,L)
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    scores = jnp.where(rows > cols, scores, 0.0)
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)                 # (L,1)

    state = state_scr[...]                         # (K, V)
    o = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o += jax.lax.dot_general(qexp, state, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o += bonus * v
    # Value-level fault injection (lanefault): masked corruption of the
    # chunk's value-lane axis; absent from the trace when healthy.
    o_ref[...] = apply_fault(o, lane_fault).astype(o_ref.dtype)

    kscale = k * jnp.exp(tot_row_ref[...] - la)
    upd = jax.lax.dot_general(kscale, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_scr[...] = jnp.exp(tot_col_ref[...]) * state + upd


def wkv6_chunked_pallas(r, k, v, lw, u, *, chunk: int = 16,
                        interpret: bool = False, lane_fault=None):
    """r/k/lw (B,S,H,K); v (B,S,H,V); u (H,K). S % chunk == 0."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    def chunked(a):           # (B, S, H, X) -> (B, H, nc, L, X)
        return a.reshape(B, nc, L, H, a.shape[-1]).transpose(0, 3, 1, 2, 4)

    lw_c = chunked(lw.astype(jnp.float32))
    la = jnp.cumsum(lw_c, axis=3)
    tot = la[:, :, :, L - 1]                       # (B, H, nc, K)

    def per_chunk(*tail):     # block of one (b, h, chunk), whole last dims
        return pl.BlockSpec((None, None, None) + tail,
                            lambda b, h, ci: (b, h, ci, 0, 0))

    kernel = functools.partial(_wkv_kernel, L=L, lane_fault=lane_fault)
    o = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[per_chunk(L, K), per_chunk(L, K), per_chunk(L, V),
                  per_chunk(L, K), per_chunk(L, K), per_chunk(1, K),
                  per_chunk(K, 1),
                  pl.BlockSpec((None, 1, K), lambda b, h, ci: (h, 0, 0))],
        out_specs=per_chunk(L, V),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, L, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(chunked(r), chunked(k), chunked(v), la, lw_c, tot[..., None, :],
      tot[..., None], u.reshape(H, 1, K))
    return o.transpose(0, 2, 3, 1, 4).reshape(B, S, H, V)
