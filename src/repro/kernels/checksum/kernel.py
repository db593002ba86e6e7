"""Pallas TPU kernel for the Fig. 4 checksum module.

Grid over row blocks of the word view; each block reduces its rows to one
lane-dense (1, 128) row of partial popcounts (VPU bit ops, no MXU), and the
wrapper sums the partials.  A lane row keeps the output block aligned to
the TPU tiling; every per-lane partial is at most ``32 * block_rows``, so
it is exact in f32.  This is the cheap always-on detector the paper routes
through the Cohort queues; here it runs over stage outputs / canaries.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _checksum_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.uint32)
    x = (x & 0x55555555) + ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x & 0x0F0F0F0F) + ((x >> 4) & 0x0F0F0F0F)
    x = (x & 0x00FF00FF) + ((x >> 8) & 0x00FF00FF)
    x = (x & 0x0000FFFF) + ((x >> 16) & 0x0000FFFF)
    # per-word counts are <= 32: exact through int32 (Mosaic has no
    # uint32 -> f32 cast) and f32
    counts = x.astype(jnp.int32).astype(jnp.float32)
    o_ref[...] = jnp.sum(counts, axis=0, keepdims=True)


def checksum_pallas_words(words, *, block_rows: int = 64, lanes: int = 128,
                          interpret: bool = False) -> jax.Array:
    """words: flat uint32 array -> uint32 checksum."""
    n = words.shape[0]
    per_block = block_rows * lanes
    nb = max(1, -(-n // per_block))
    padded = jnp.zeros((nb * per_block,), jnp.uint32).at[:n].set(words)
    x = padded.reshape(nb * block_rows, lanes)
    partials = pl.pallas_call(
        _checksum_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((None, 1, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, lanes), jnp.float32),
        interpret=interpret,
    )(x)
    return jnp.sum(partials.astype(jnp.uint32))
