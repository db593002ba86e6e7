"""The plain reference: the model's forward pass in float32, written from
the published architecture (RMSNorm, RoPE, grouped-query causal attention,
SwiGLU MLP, untied LM head) and from nothing of the program.

It makes its own weights again from the seed, one layer at a time
(``model.layer_weights``), after the program's state has been freed, and
runs every sampled sequence through one layer before the next, so only one
layer's weights are ever held.  Matrix products run at ``highest``
precision: a float32 product on a TPU is otherwise done in bf16 passes.

``mode="fp8"`` is the control: every weight product takes its operands
rounded to float8 (e4m3, scaled per row of activations and per column of
weights) and accumulates in float32, the step below the bf16 the
configuration states.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from bench.model import layer_weights, seed_key, sizes, top_weights

HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 512          # sequences are padded to a multiple of this
Q_CHUNK = 256         # queries per block of attention scores
POS_BUCKET = 128      # served positions are padded to a multiple of this


def _fp8(a, axis):
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(a, w, mode):
    if mode == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.dot(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    """Causal softmax attention; q (T, H, hd), k/v (T, H, hd)."""
    T, H, hd = q.shape
    C = min(Q_CHUNK, T)

    def block(args):
        qb, i0 = args
        s = jnp.einsum("chd,thd->hct", qb, k, precision=HIGHEST) / hd ** 0.5
        mask = jnp.arange(T)[None, :] <= (i0 + jnp.arange(C))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        return jnp.einsum("hct,thd->chd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(T // C, C, H, hd),
                            jnp.arange(0, T, C)))
    return o.reshape(T, H, hd)


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def _layer(key, i, x, *, s, mode):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               layer_weights(key, i, dict(s)))
    s = dict(s)
    T, hd = x.shape[0], s["head_dim"]
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], s["eps"])
    q, k, v = _mm(h, a["wq"], mode), _mm(h, a["wk"], mode), \
        _mm(h, a["wv"], mode)
    if s["bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(T, s["heads"], hd), s["theta"])
    k = _rope(k.reshape(T, s["kv_heads"], hd), s["theta"])
    v = v.reshape(T, s["kv_heads"], hd)
    group = s["heads"] // s["kv_heads"]
    o = _attend(q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1))
    x = x + _mm(o.reshape(T, -1), a["wo"], mode)
    m = p["mlp"]
    h = _rms(x, p["ln2"]["scale"], s["eps"])
    g = jax.nn.silu(_mm(h, m["w1"], mode)) * _mm(h, m["w3"], mode)
    return x + _mm(g, m["w2"], mode)


@functools.partial(jax.jit, static_argnames=("s",))
def _top(key, *, s):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  top_weights(key, dict(s)))


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _logits(top, x, pos, *, eps, mode):
    h = _rms(x[pos], top["final_norm"]["scale"], eps)
    return _mm(h, top["lm_head"]["w"], mode)


def _bucket(n: int, size: int) -> int:
    return -(-n // size) * size


def logits(conf, seed: int, seqs, mode: str = "f32"):
    """For each (prompt, served) pair, the logits (n, V) that predict each
    of the ``n`` served tokens: positions P-1 .. P+n-2 of the sequence
    prompt + served[:-1].  Sequences and positions are padded to buckets,
    so a cell compiles these programs once."""
    s = tuple(sorted(sizes(conf).items()))
    key = seed_key(seed)
    top = _top(key, s=s)
    xs, pos = [], []
    for prompt, served in seqs:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks = np.pad(toks, (0, _bucket(len(toks), BUCKET) - len(toks)))
        xs.append(_embed(top["embed"]["table"], jnp.asarray(toks)))
        p = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        pos.append(np.pad(p, (0, _bucket(len(p), POS_BUCKET) - len(p)),
                          mode="edge"))
    for i in range(dict(s)["layers"]):
        xs = [_layer(key, jnp.int32(i), x, s=s, mode=mode) for x in xs]
    return [_logits(top, x, jnp.asarray(p), eps=dict(s)["eps"],
                    mode=mode)[:len(served)]
            for x, p, (_, served) in zip(xs, pos, seqs)]


@jax.jit
def _gaps(ref, tok):
    best = jnp.max(ref, -1)
    return best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]


def gaps(ref_logits, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position."""
    n = len(tokens)
    m = _bucket(n, POS_BUCKET)
    ref = jnp.pad(ref_logits, ((0, m - n), (0, 0)))
    tok = np.pad(np.asarray(tokens, np.int32), (0, m - n))
    return np.asarray(_gaps(ref, jnp.asarray(tok)))[:n]
