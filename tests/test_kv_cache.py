"""The decode KV cache's layout: heads-major k/v (B, Hkv, Smax, Dh).

Pins the dimension order every writer and reader of the cache shares:
``init_kv_cache``, the prefill write (plain and ring buffer), the decode
write (per-layer and layer-stacked), the decode attention read, and the
multi-chip partition spec.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import ref as attn_ref
from repro.launch.partition import make_cache_pspec_fn
from repro.models import attention as attn

B, HKV, DH = 2, 3, 8


def _kv(S, seed=0):
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kk, (B, S, HKV, DH), jnp.float32),
            jax.random.normal(kv, (B, S, HKV, DH), jnp.float32))


def test_init_kv_cache_is_heads_major():
    c = attn.init_kv_cache(B, 16, HKV, DH, jnp.bfloat16)
    assert c["k"].shape == c["v"].shape == (B, HKV, 16, DH)
    assert c["k"].dtype == jnp.bfloat16
    assert c["pos"].shape == (B, 16)
    assert (np.asarray(c["pos"]) == -1).all()


@pytest.mark.parametrize("S", [5, 8, 13, 21])
def test_prefill_write_puts_position_p_at_slot_p_mod_smax(S):
    """S <= Smax fills slots [0, S); a prefill longer than the ring keeps
    its last Smax tokens, position p at slot p % Smax.  Either way the
    row of position p is k[:, p] in heads-major order."""
    smax = 8
    k, v = _kv(S)
    c = attn.cache_write_prefill(
        attn.init_kv_cache(B, smax, HKV, DH, jnp.float32), k, v)
    assert c["k"].shape == (B, HKV, smax, DH)
    pos = np.asarray(c["pos"])
    kept = range(max(0, S - smax), S)
    for p in kept:
        slot = p % smax
        assert (pos[:, slot] == p).all()
        np.testing.assert_array_equal(np.asarray(c["k"][:, :, slot]),
                                      np.asarray(k[:, p]))
        np.testing.assert_array_equal(np.asarray(c["v"][:, :, slot]),
                                      np.asarray(v[:, p]))
    unwritten = sorted(set(range(smax)) - {p % smax for p in kept})
    assert (pos[:, unwritten] == -1).all()
    assert not np.asarray(c["k"][:, :, unwritten]).any()


def _attn_params(D, seed=1):
    return attn.init_attention(jax.random.PRNGKey(seed), D, 2 * HKV, HKV,
                               DH, jnp.float32)


@pytest.mark.parametrize("stacked", [False, True])
def test_decode_write_lands_at_slot_t_mod_smax(stacked):
    """A decode step writes the new token's (Hkv, Dh) row at slot
    t % Smax of its layer, and nothing else."""
    D, smax, t, layer, L = 16, 8, 11, 1, 3
    p = _attn_params(D)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 1, D), jnp.float32)
    one = attn.init_kv_cache(B, smax, HKV, DH, jnp.float32)
    cache = (jax.tree_util.tree_map(lambda a: jnp.stack([a] * L), one)
             if stacked else one)
    _, c = attn.attn_decode(p, x, cache, jnp.int32(t), n_heads=2 * HKV,
                            n_kv=HKV, head_dim=DH, rope_theta=1e4,
                            layer=layer if stacked else None)
    k, pos = np.asarray(c["k"]), np.asarray(c["pos"])
    if stacked:
        assert not k[np.arange(L) != layer].any()
        k, pos = k[layer], pos[layer]
    assert k.shape == (B, HKV, smax, DH)
    slot = t % smax
    assert (pos[:, slot] == t).all()
    assert (np.delete(pos, slot, axis=1) == -1).all()
    assert k[:, :, slot].any()
    assert not np.delete(k, slot, axis=2).any()


@pytest.mark.parametrize("window,softcap,heads", [(0, 0.0, HKV),
                                                  (0, 0.0, 2 * HKV),
                                                  (5, 0.0, 2 * HKV),
                                                  (0, 30.0, 2 * HKV)])
def test_attention_decode_matches_naive(window, softcap, heads):
    """The decode read over the heads-major cache is the naive oracle's
    mathematics: causal, windowed, softcapped, GQA, with unwritten (-1)
    and ring-ordered slots masked by their explicit positions."""
    smax = 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, heads, DH), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, HKV, smax, DH), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, HKV, smax, DH), jnp.bfloat16)
    # row 0: a ring past its first lap; row 1: three slots written so far
    pos = jnp.array([[8, 9, 10, 11, 4, 5, 6, 7],
                     [0, 1, 2, -1, -1, -1, -1, -1]], jnp.int32)
    t = jnp.array([11, 2], jnp.int32)
    got = attn_ref.attention_decode(q, k, v, pos, t, window=window,
                                    softcap=softcap)
    want = attn_ref.attention_naive(
        q[:, None], jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=True, window=window, softcap=softcap, q_offset=t,
        k_positions=pos)[:, 0]
    assert got.shape == (B, heads, DH) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


@pytest.mark.parametrize("kv_heads,sharded", [(8, -3), (6, -2)])
def test_cache_pspec_shards_kv_heads_else_seq(kv_heads, sharded):
    """(..., B, Hkv, S, D): kv-heads over "model" when they divide it,
    else the sequence."""
    mesh = types.SimpleNamespace(shape={"data": 2, "model": 4})
    fn = make_cache_pspec_fn(2, mesh)
    spec = fn("grp/0/k", (3, 2, kv_heads, 32, 16), mesh)
    want = [None, ("data",), None, None, None]
    want[sharded] = "model"
    assert spec == P(*want)
