"""Pallas TPU flash-attention kernel (the Viscosity "hardware" lowering).

TPU-native design notes (vs. the usual CUDA flash kernels):
  * grid = (B, H, nQ, nK) with nK minor-most: TPU grids execute
    sequentially minor-to-major, so the online-softmax running state
    (m, l, acc) lives in VMEM scratch and persists across the nK loop —
    the analogue of a warp-resident accumulator on GPU.
  * blocks are MXU-aligned (128x128 score tiles); both dot products use
    ``preferred_element_type=f32`` so the MXU accumulates in f32.
  * causal / sliding-window block skipping via ``pl.when`` on grid indices:
    skipped blocks issue no MXU work (the structural analogue of warp
    early-exit).
  * GQA is resolved in the k/v BlockSpec index maps (q head h reads kv head
    h * Hkv // H) — no materialized head repetition in HBM.

Supports: causal, sliding window, logit softcap, GQA, tail padding via a
static ``kv_len``.  Layout inside the kernel: (B, H, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.viscosity.lanefault import apply_fault

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 bq: int, bk: int, nk: int, kv_len: int, lane_fault=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level admissibility (static grid indices -> cheap scalar preds).
    run = k_start < kv_len
    if causal:
        run &= k_start <= q_start + bq - 1
    if window and window > 0:
        run &= (k_start + bk - 1) > (q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale              # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                      # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                      # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap and softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kp < kv_len
        if causal:
            mask &= kp <= qp
        if window and window > 0:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                       # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                    # (bq, bk)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        # Value-level fault injection (lanefault): masked corruption of the
        # normalized output tile's head_dim lanes, only present in the
        # trace when a fault is registered.
        o_ref[0, 0] = apply_fault(acc_scr[...] / l,
                                  lane_fault).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, scale: float = 0.0,
                         kv_len: int = 0, bq: int = 128, bk: int = 128,
                         interpret: bool = False, lane_fault=None):
    """q: (B, H, Sq, D); k: (B, Hkv, Skv, D); v: (B, Hkv, Skv, Dv).
    Sq % bq == Skv % bk == 0.  The output head_dim is ``v.shape[3]`` —
    normally D, narrower under DEGRADED_REDUCED (reduced-width execution
    slices v to the surviving lanes)."""
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[3]
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    assert H % Hkv == 0
    nq, nk = Sq // bq, Skv // bk
    sc = scale or (1.0 / D ** 0.5)
    kv_len = kv_len or Skv

    kernel = functools.partial(
        _attn_kernel, scale=sc, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk, kv_len=kv_len,
        lane_fault=lane_fault)

    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, Hkv=Hkv, H=H: (b, h * Hkv // H, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, qi, ki, Hkv=Hkv, H=H: (b, h * Hkv // H, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
