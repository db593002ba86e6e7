"""Attention layer: GQA + RoPE/M-RoPE + local/global windows + softcap.

Stage-wrapped: the score/softmax/PV core routes through the Viscosity
``flash_attention`` op (HW = Pallas kernel, SW = chunked-jnp fallback).

Cache layout (decode): k/v heads-major (B, Hkv, Smax, Dh) plus an explicit
per-slot position array ``pos`` (B, Smax) initialized to -1.  Sliding-window
archs allocate Smax = window and write slots round-robin (ring buffer); the
position array makes masking uniform across both cases.  Heads-major keeps
(Smax, Dh) as the two minor dims: the TPU tiles them unpadded, so the
per-slot cache write and the attention read share the pool's default
layout and the decode program never re-lays the pool out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import viscosity
from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.flash_attention import ref as attn_ref
from repro.launch.sharding import constrain
from repro.models import rope as rope_mod
from repro.models.layers import _he, rms_norm_simple
from repro.obs import metrics


def init_attention(key, d_model, n_heads, n_kv, head_dim, dtype, *,
                   qkv_bias=False, qk_norm=False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": _he(ks[0], (d_model, n_heads * head_dim), d_model, dtype),
        "wk": _he(ks[1], (d_model, n_kv * head_dim), d_model, dtype),
        "wv": _he(ks[2], (d_model, n_kv * head_dim), d_model, dtype),
        "wo": _he(ks[3], (n_heads * head_dim, d_model),
                  n_heads * head_dim, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def _project_q_only(p, x, n_heads, head_dim):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, n_heads, head_dim)
    if "q_norm" in p:
        q = rms_norm_simple(q) * p["q_norm"].astype(x.dtype)
    return constrain(q, "batch", "seq", "heads", "head_dim")


def project_kv(p, x, n_kv, head_dim):
    B, S, _ = x.shape
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(B, S, n_kv, head_dim)
    v = v.reshape(B, S, n_kv, head_dim)
    if "k_norm" in p:
        k = rms_norm_simple(k) * p["k_norm"].astype(x.dtype)
    return (constrain(k, "batch", "kv_seq", "kv_heads", "head_dim"),
            constrain(v, "batch", "kv_seq", "kv_heads", "head_dim"))


def _project_qkv(p, x, n_heads, n_kv, head_dim, *, qk_norm_eps=1e-6):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv, head_dim)
    v = v.reshape(B, S, n_kv, head_dim)
    if "q_norm" in p:
        q = rms_norm_simple(q, eps=qk_norm_eps) * p["q_norm"].astype(x.dtype)
        k = rms_norm_simple(k, eps=qk_norm_eps) * p["k_norm"].astype(x.dtype)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def attn_full(p, x, cos, sin, *, n_heads, n_kv, head_dim, causal=True,
              window=0, softcap=0.0, scale=0.0, route=viscosity.SW,
              kv_out=False, cross_kv=None, precomputed_kv=None,
              kv_chunk=0):
    """Full-sequence attention (train / prefill).

    ``cross_kv``: encoder output (B, S_enc, D) — keys/values are projected
    from it instead of ``x`` (whisper cross-attention).
    ``precomputed_kv``: (k, v) already projected (cached cross-KV during
    serving; avoids re-projecting the encoder output every decode step).
    """
    if precomputed_kv is not None:
        q = _project_q_only(p, x, n_heads, head_dim)
        k, v = precomputed_kv
    elif cross_kv is not None:
        q = _project_q_only(p, x, n_heads, head_dim)
        k, v = project_kv(p, cross_kv.astype(x.dtype), n_kv, head_dim)
    else:
        q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)
    if cos is not None and cross_kv is None:
        q = rope_mod.apply_rope(q, cos, sin)
        k = rope_mod.apply_rope(k, cos, sin)
    o = attn_ops.attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale, route=route,
                           kv_chunk=kv_chunk)
    o = constrain(o, "batch", "seq", "heads", "head_dim")
    B, S = x.shape[:2]
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1),
                     p["wo"].astype(x.dtype))
    out = constrain(out, "batch", "seq", "embed")
    return (out, (k, v)) if kv_out else out


def init_kv_cache(B, smax, n_kv, head_dim, dtype):
    return {
        "k": jnp.zeros((B, n_kv, smax, head_dim), dtype),
        "v": jnp.zeros((B, n_kv, smax, head_dim), dtype),
        "pos": jnp.full((B, smax), -1, jnp.int32),
    }


def cache_write_prefill(cache, k, v):
    """Write a prefill's k/v (B, S, Hkv, Dh) into the heads-major cache.

    S <= Smax: plain write into slots [0, S).  S > Smax (ring buffer,
    windowed attention): keep the last Smax tokens, placed cyclically at
    slot = position % Smax so subsequent decode writes stay consistent.
    Flash attention keeps its (B, S, H, D) operands; each layer's new rows
    are transposed once, on their way into the cache.
    """
    B, S = k.shape[:2]
    smax = cache["k"].shape[2]
    c = dict(cache)
    if S <= smax:
        def put(full, new):
            # Through a one-layer (1, B, Hkv, S, Dh) view: the projection
            # then has two readers of different shapes, and XLA's TPU
            # compiler copies these rows per layer instead of re-laying
            # the whole stacked weight out before the layer loop (for
            # wv: 524 MB more prefill temp at qwen1.5-4b widths).
            rows = jnp.swapaxes(new[None], 2, 3).astype(full.dtype)
            return jax.lax.dynamic_update_slice(
                full[None], rows, (0, 0, 0, 0, 0))[0]
        c["k"] = put(cache["k"], k)
        c["v"] = put(cache["v"], v)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        c["pos"] = jax.lax.dynamic_update_slice(cache["pos"], pos, (0, 0))
        return c
    p0 = S - smax                       # first kept absolute position
    idx = (jnp.arange(smax, dtype=jnp.int32) - p0) % smax  # keep-row per slot
    c["k"] = jnp.swapaxes(k[:, p0:][:, idx], 1, 2).astype(cache["k"].dtype)
    c["v"] = jnp.swapaxes(v[:, p0:][:, idx], 1, 2).astype(cache["v"].dtype)
    pos = jnp.broadcast_to((p0 + idx)[None], (B, smax))
    c["pos"] = pos
    return c


def attn_decode(p, x, cache, t, *, n_heads, n_kv, head_dim, window=0,
                softcap=0.0, scale=0.0, rope_theta=0.0, mrope=None,
                positions3=None, route=viscosity.SW, layer=None):
    """One decode step. x (B,1,D); t: scalar int32 absolute position.

    Writes slot ``t % Smax`` (ring buffer when Smax == window), attends over
    the cache with explicit per-slot positions.

    ``layer``: if given, ``cache`` leaves are LAYER-STACKED (L, B, Hkv,
    Smax, Dh) and this layer's row is updated with a single in-place
    dynamic-update-slice (the decode path unrolls layers so the donated
    stacked cache is never copied).
    """
    B = x.shape[0]
    stacked = layer is not None
    smax = cache["k"].shape[3 if stacked else 2]
    q, k, v = _project_qkv(p, x, n_heads, n_kv, head_dim)
    tvec = jnp.full((B, 1), t, jnp.int32)
    if mrope is not None:
        cos, sin = rope_mod.mrope_tables(positions3, head_dim,
                                         mrope["theta"], mrope["sections"])
        q = rope_mod.apply_rope(q, cos, sin)
        k = rope_mod.apply_rope(k, cos, sin)
    elif rope_theta:
        cos, sin = rope_tables_b(tvec, head_dim, rope_theta)
        q = rope_mod.apply_rope(q, cos, sin)
        k = rope_mod.apply_rope(k, cos, sin)
    slot = jnp.mod(t, smax)
    c = dict(cache)
    # the new token's (Hkv, Dh) row, heads-major: (B, Hkv, 1, Dh)
    kw = jnp.swapaxes(k, 1, 2).astype(cache["k"].dtype)
    vw = jnp.swapaxes(v, 1, 2).astype(cache["v"].dtype)
    if stacked:
        c["k"] = jax.lax.dynamic_update_slice(
            cache["k"], kw[None], (layer, 0, 0, slot, 0))
        c["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vw[None], (layer, 0, 0, slot, 0))
        c["pos"] = jax.lax.dynamic_update_slice(
            cache["pos"], tvec[None], (layer, 0, slot))
        k_all = jax.lax.dynamic_slice_in_dim(c["k"], layer, 1, 0)[0]
        v_all = jax.lax.dynamic_slice_in_dim(c["v"], layer, 1, 0)[0]
        pos_all = jax.lax.dynamic_slice_in_dim(c["pos"], layer, 1, 0)[0]
    else:
        c["k"] = jax.lax.dynamic_update_slice(cache["k"], kw,
                                              (0, 0, slot, 0))
        c["v"] = jax.lax.dynamic_update_slice(cache["v"], vw,
                                              (0, 0, slot, 0))
        c["pos"] = jax.lax.dynamic_update_slice(cache["pos"], tvec, (0, slot))
        k_all, v_all, pos_all = c["k"], c["v"], c["pos"]
    # The LM decode step attends through the jnp oracle on every route:
    # per-slot cache positions (ring buffers) have no kernel yet (ROADMAP
    # 1.2).  Counted, so a run can report which lowering decode used.
    metrics.inc("attention_lowerings_traced_total", phase="decode",
                lowering="jnp_naive")
    o = attn_ref.attention_decode(
        q[:, 0], k_all, v_all, pos_all, jnp.full((B,), t, jnp.int32),
        window=window, softcap=softcap, scale=scale)
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1),
                     p["wo"].astype(x.dtype))
    return out, c


def rope_tables_b(positions, head_dim, theta):
    return rope_mod.rope_tables(positions, head_dim, theta)
