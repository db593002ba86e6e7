"""Jit'd wrapper + Viscosity registration for the attention stage.

``attention(...)`` is the stage entry point used by the models: the route
argument selects the lowering (paper: per-sub-accelerator queue config):
  * HW        -> Pallas flash kernel (TPU)
  * INTERPRET -> same kernel body, interpreter mode (CPU validation)
  * SW        -> chunked online-softmax jnp fallback (production software)
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro import viscosity
from repro.kernels import tuning
from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.obs import metrics
from repro.viscosity import lanefault


def _pad_to(x, m, axis):
    s = x.shape[axis]
    if s % m == 0:
        return x, s
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - s % m)
    return jnp.pad(x, pad), s


def _kernel_path(q, k, v, *, causal=True, window=0, softcap=0.0, scale=0.0,
                 q_offset=None, kv_len=None, kv_chunk=0, bq=None, bk=None,
                 interpret=False):
    fault = lanefault.injection("flash_attention")
    if q_offset is not None or kv_len is not None:
        # Decode-style calls carry dynamic positions, and there is no
        # decode kernel yet (ROADMAP 1.2): until there is, this chunked jnp
        # lowering IS the HW route's decode path, and it is counted as
        # such.  An active lane fault corrupts it too (wrapper-level: same
        # masked-where).
        metrics.inc("attention_lowerings_traced_total", phase="decode",
                    lowering="jnp_chunked")
        out = _ref.attention_chunked(q, k, v, causal=causal, window=window,
                                     softcap=softcap, scale=scale,
                                     q_offset=q_offset, kv_len=kv_len)
        return fault.corrupt_tree(out) if fault is not None else out
    metrics.inc("attention_lowerings_traced_total", phase="prefill",
                lowering="pallas_interpret" if interpret else "pallas")
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    # Tuned score-tile (bq, bk) for this (shape, dtype, active routing
    # plan) when cached; explicit knobs win; no entry -> the historical
    # 128x128 MXU tile.  tuning.lookup is fail-open by construction.
    if bq is None and bk is None:
        cfg = tuning.lookup("flash_attention", "hw",
                            (B, Sq, Skv, H, k.shape[2], D), q.dtype) or {}
    else:
        cfg = {}
    bq = bq or cfg.get("bq") or 128
    bk = bk or cfg.get("bk") or 128
    bq = min(bq, max(8, Sq))
    bk = min(bk, max(8, Skv))
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qt, _ = _pad_to(qt, bq, 2)
    kt, real_kv = _pad_to(kt, bk, 2)
    vt, _ = _pad_to(vt, bk, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               softcap=softcap, scale=scale, kv_len=real_kv,
                               bq=bq, bk=bk, interpret=interpret,
                               lane_fault=fault)
    return out[:, :, :Sq, :].transpose(0, 2, 1, 3)


def _lane_slicer(args, kw, keep):
    # attention output lane j depends only on v[..., j] (softmax weights
    # come from q@k): slicing v's head_dim is exact reduced-width execution.
    q, k, v = args
    return (q, k, v[..., jnp.asarray(keep, jnp.int32)]), kw


def _sw_path(q, k, v, *, kv_chunk=None, bq=128, bk=128, interpret=False,
             **kw):
    metrics.inc("attention_lowerings_traced_total",
                phase="decode" if kw.get("q_offset") is not None
                else "prefill", lowering="jnp_chunked")
    if not kv_chunk:
        B, Sq, H, D = q.shape
        cfg = tuning.lookup("flash_attention", "sw",
                            (B, Sq, k.shape[1], H, k.shape[2], D),
                            q.dtype) or {}
        kv_chunk = cfg.get("kv_chunk") or 512
    return _ref.attention_chunked(q, k, v, kv_chunk=kv_chunk, **kw)


ATTENTION = viscosity.defop(
    "flash_attention",
    ref=_sw_path,
    kernel=_kernel_path,
    interpret=functools.partial(_kernel_path, interpret=True),
    valid=viscosity.finite_valid,
    tol=2e-2,
    flops=lambda q, k, *a, **kw: _ref.attention_flops(
        q.shape[0], q.shape[1], k.shape[1], q.shape[2], q.shape[3]),
    lane_slicer=_lane_slicer,
)


def attention(q, k, v, *, route: str = viscosity.SW, **kw):
    return ATTENTION(q, k, v, route=route, **kw)
