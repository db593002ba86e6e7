"""Pure-jnp oracles for attention (the Viscosity "software" lowering).

Three implementations:
  * ``attention_naive`` — the simple masked-softmax oracle used as the
    ground-truth in tests (never used at scale);
  * ``attention_chunked`` — the memory-efficient online-softmax jnp version
    (lax.scan over KV chunks).  This is the production software fallback and
    the XLA path lowered by the dry-run;
  * ``attention_decode`` — ``attention_naive``'s mathematics for one query
    token per row over a heads-major KV cache (the LM decode step).

All support: causal masking, sliding windows (``window > 0``), GQA
(``Hkv`` divides ``H``), gemma-style logit softcapping, and explicit
query/key positions (decode: ``q_pos`` is the absolute position of the
query tokens; ``kv_len`` masks the unwritten tail of a preallocated cache,
``attention_decode`` masks it by key position -1).

Layout: q (B, Sq, H, D); k, v (B, Skv, Hkv, D); output (B, Sq, H, D);
``attention_decode`` takes q (B, H, D) and k, v (B, Hkv, Skv, D).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(scores, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(scores / cap) * cap
    return scores


def _positions(B, S, offset):
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    if offset is not None:
        pos = pos + offset.astype(jnp.int32).reshape(-1, 1)
    return jnp.broadcast_to(pos, (B, S))


def _mask(q_pos, k_pos, *, causal: bool, window: int,
          kv_len: Optional[jax.Array], explicit_kpos: bool = False):
    """(B, Sq, Skv) boolean admissibility mask."""
    m = jnp.ones((q_pos.shape[0], q_pos.shape[1], k_pos.shape[1]), bool)
    qp = q_pos[:, :, None]
    kp = k_pos[:, None, :]
    if causal:
        m &= kp <= qp
    if window and window > 0:
        m &= kp > qp - window
    if kv_len is not None:
        m &= kp < kv_len.astype(jnp.int32).reshape(-1, 1, 1)
    if explicit_kpos:
        m &= kp >= 0  # ring-buffer slots not yet written carry position -1
    return m


def _repeat_kv(k, H):
    Hkv = k.shape[2]
    if Hkv == H:
        return k
    assert H % Hkv == 0, (H, Hkv)
    return jnp.repeat(k, H // Hkv, axis=2)


def attention_naive(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float = 0.0,
                    q_offset: Optional[jax.Array] = None,
                    kv_len: Optional[jax.Array] = None,
                    k_positions: Optional[jax.Array] = None) -> jax.Array:
    """O(Sq*Skv) oracle. Compute in f32, return q.dtype.

    ``k_positions`` (B, Skv): explicit absolute key positions (ring-buffer
    caches); slots marked -1 are masked out.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    sc = scale or (1.0 / D ** 0.5)
    # mixed precision: keep K/V in their storage dtype (bf16 caches read
    # once, no f32 copies) and accumulate the dots in f32 (MXU-native)
    kf = _repeat_kv(k, H)
    vf = _repeat_kv(v, H)
    qf = (q.astype(jnp.float32) * sc).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores, softcap)
    q_pos = _positions(B, Sq, q_offset)
    k_pos = (k_positions.astype(jnp.int32) if k_positions is not None
             else _positions(B, Skv, None))
    mask = _mask(q_pos, k_pos, causal=causal, window=window, kv_len=kv_len,
                 explicit_kpos=k_positions is not None)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vf.dtype), vf,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attention_decode(q, k, v, k_positions, q_pos, *, window: int = 0,
                     softcap: float = 0.0, scale: float = 0.0) -> jax.Array:
    """One query token per row over a heads-major cache, without moving it.

    q (B, H, D); k, v (B, Hkv, Skv, D); ``k_positions`` (B, Skv) absolute
    key positions, -1 for slots not yet written; ``q_pos`` (B,) the query's
    position.  GQA reads q as (B, Hkv, G, D), query head h = kv head
    h // G, as ``_repeat_kv`` maps them.  Causal; returns (B, H, D) in
    q.dtype, equal to ``attention_naive`` on the same operands.

    Each of the G query heads of a kv head takes its own one-row product
    against k and v.  One (G, D) x (D, Skv) product per kv head is an MXU
    product, for which XLA's TPU compiler lays a layer-stacked, slot-vmapped
    pool out again so that the layer's slice is contiguous: two copies of
    the whole pool per layer every tick (at mistral-nemo widths, 12 slots x
    4352, 21 GB a tick).  The G one-row products fuse into one pass over k
    and one over v, read where they lie, as G = 1 always did.
    """
    # the scope names these ops in the compiled program (op_name)
    with jax.named_scope("attention_decode"):
        B, H, D = q.shape
        Hkv = k.shape[1]
        G = H // Hkv
        sc = scale or (1.0 / D ** 0.5)
        qf = (q.astype(jnp.float32) * sc).astype(q.dtype)
        qg = qf.reshape(B, Hkv, G, D)
        scores = jnp.stack([jnp.einsum("bhd,bhkd->bhk", qg[:, :, g], k,
                                       preferred_element_type=jnp.float32)
                            for g in range(G)], 2)
        scores = _softcap(scores, softcap)
        mask = _mask(q_pos.astype(jnp.int32).reshape(B, 1),
                     k_positions.astype(jnp.int32), causal=True, window=window,
                     kv_len=None, explicit_kpos=True)          # (B, 1, Skv)
        scores = jnp.where(mask[:, :, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.stack([jnp.einsum("bhk,bhkd->bhd", probs[:, :, g], v,
                                    preferred_element_type=jnp.float32)
                         for g in range(G)], 2)
        return out.reshape(B, H, D).astype(q.dtype)


def attention_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                      softcap: float = 0.0, scale: float = 0.0,
                      q_offset: Optional[jax.Array] = None,
                      kv_len: Optional[jax.Array] = None,
                      kv_chunk: int = 512) -> jax.Array:
    """Online-softmax over KV chunks: peak activation O(Sq * kv_chunk).

    The production software fallback; equals ``attention_naive`` to f32
    rounding (tested).  Used by the dry-run as the XLA attention path.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Dv = v.shape[-1]   # may be < D under reduced-width (surviving lanes)
    C = min(kv_chunk, Skv)
    if Skv % C:  # pad KV to a chunk multiple; padding masked via kv_len
        pad = C - Skv % C
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = (kv_len if kv_len is not None
                  else jnp.full((B,), Skv, jnp.int32))
        Skv = Skv + pad
    nC = Skv // C
    sc = scale or (1.0 / D ** 0.5)
    qf = q.astype(jnp.float32) * sc
    q_pos = _positions(B, Sq, q_offset)

    kc = _repeat_kv(k, H).reshape(B, nC, C, H, D).transpose(1, 0, 2, 3, 4)
    vc = _repeat_kv(v, H).reshape(B, nC, C, H, Dv).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        ci, kb, vb = xs
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf.astype(kb.dtype), kb,
                            preferred_element_type=jnp.float32)
        scores = _softcap(scores, softcap)
        k_pos = (ci * C + jnp.arange(C, dtype=jnp.int32))[None, :]
        k_pos = jnp.broadcast_to(k_pos, (B, C))
        mask = _mask(q_pos, k_pos, causal=causal, window=window, kv_len=kv_len)
        scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new[..., None])
        l_corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * l_corr + jnp.sum(p, axis=-1)
        acc = acc * l_corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, Dv), jnp.float32)
    ci = jnp.arange(nC, dtype=jnp.int32)
    # checkpoint the chunk body: backward residuals are then one chunk's
    # (m, l, acc) carry instead of every chunk's (B,H,Sq,C) score tensors
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (ci, kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_ref_blocked(q, k, v, *, causal: bool = True, window: int = 0,
                          softcap: float = 0.0, scale: float = 0.0,
                          kv_len: int = 0, bq: int = 128, bk: int = 128):
    """Pure-jnp replica of the Pallas flash kernel's *blocked* algorithm.

    Layout (B, H, S, D) like ``kernel.flash_attention_bhsd``; same block
    skipping, same masks, same f32 online-softmax update order, same
    GQA head mapping — interpret-mode kernel output must match this
    oracle **bit-for-bit** for every admissible (bq, bk).  The parity
    tests sweep the tuner's whole config space against it.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    sc = scale or (1.0 / D ** 0.5)
    kv_len = kv_len or Skv
    out = jnp.zeros((B, H, Sq, D), q.dtype)
    for b in range(B):
        for h in range(H):
            kh = h * Hkv // H  # the kernel's GQA BlockSpec index map
            for qi in range(nq):
                q_start = qi * bq
                m = jnp.full((bq, 1), NEG_INF, jnp.float32)
                l = jnp.zeros((bq, 1), jnp.float32)
                acc = jnp.zeros((bq, D), jnp.float32)
                for ki in range(nk):
                    k_start = ki * bk
                    run = k_start < kv_len
                    if causal:
                        run &= k_start <= q_start + bq - 1
                    if window and window > 0:
                        run &= (k_start + bk - 1) > (q_start - window)
                    if not run:
                        continue
                    qb = q[b, h, q_start:q_start + bq].astype(
                        jnp.float32) * sc
                    kb = k[b, kh, k_start:k_start + bk].astype(jnp.float32)
                    vb = v[b, kh, k_start:k_start + bk].astype(jnp.float32)
                    s = jax.lax.dot_general(
                        qb, kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if softcap and softcap > 0.0:
                        s = jnp.tanh(s / softcap) * softcap
                    qp = q_start + jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 0)
                    kp = k_start + jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 1)
                    mask = kp < kv_len
                    if causal:
                        mask &= kp <= qp
                    if window and window > 0:
                        mask &= kp > qp - window
                    s = jnp.where(mask, s, NEG_INF)
                    m_cur = jnp.max(s, axis=1, keepdims=True)
                    m_new = jnp.maximum(m, m_cur)
                    p = jnp.exp(s - m_new)
                    corr = jnp.exp(m - m_new)
                    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                    m = m_new
                    pv = jax.lax.dot_general(
                        p, vb, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    acc = acc * corr + pv
                o = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
                out = out.at[b, h, q_start:q_start + bq].set(o)
    return out


def attention_flops(B, Sq, Skv, H, D, causal=True) -> int:
    """Analytic useful-FLOP model (used by the roofline report)."""
    frac = 0.5 if (causal and Sq == Skv) else 1.0
    return int(4 * B * H * Sq * Skv * D * frac)
