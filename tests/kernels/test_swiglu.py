"""Fused SwiGLU kernel: interpret-mode vs the jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.swiglu import swiglu, swiglu_ref


def _mk(rng, M, D, F, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(size=(M, D)), dtype)
    w1 = jnp.asarray(rng.normal(size=(D, F)) * 0.1, dtype)
    w3 = jnp.asarray(rng.normal(size=(D, F)) * 0.1, dtype)
    w2 = jnp.asarray(rng.normal(size=(F, D)) * 0.1, dtype)
    return x, w1, w3, w2


@pytest.mark.parametrize("M,D,F", [(8, 32, 64), (128, 64, 512),
                                   (256, 128, 1024), (64, 96, 160)])
@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_kernel_matches_oracle(rng, M, D, F, act):
    x, w1, w3, w2 = _mk(rng, M, D, F)
    ref = swiglu_ref(x, w1, w3, w2, act=act)
    out = swiglu(x, w1, w3, w2, act=act, route="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("M", [1, 4, 12, 13])
def test_unaligned_rows_match_oracle(rng, M):
    """Row counts that are no sublane multiple (short prefills, one-token
    decode) run through the padded row tile and match the oracle."""
    x, w1, w3, w2 = _mk(rng, M, 64, 256)
    ref = swiglu_ref(x, w1, w3, w2)
    out = swiglu(x, w1, w3, w2, route="interpret")
    assert out.shape == (M, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


def test_bf16_contract(rng):
    x, w1, w3, w2 = _mk(rng, 128, 64, 256, jnp.bfloat16)
    ref = swiglu_ref(x, w1, w3, w2)
    out = swiglu(x, w1, w3, w2, route="interpret")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)


@settings(max_examples=15, deadline=None)
@given(M=st.sampled_from([8, 16, 128]), D=st.sampled_from([32, 64]),
       F=st.sampled_from([128, 256, 512]))
def test_property_matches_oracle(M, D, F):
    rng = np.random.default_rng(M + D + F)
    x, w1, w3, w2 = _mk(rng, M, D, F)
    ref = swiglu_ref(x, w1, w3, w2)
    out = swiglu(x, w1, w3, w2, route="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("M,D,F,tiles", [
    # qwen1.5-4b: the tiles it always had, under Mosaic's default limit
    (640, 2560, 6912, ("128", "128", "128", "7.50")),
    (1, 2560, 6912, ("8", "128", "128", "3.98")),
    # mistral-nemo-12b: bf 512 fits under the cap, with a raised limit
    (4096, 5120, 14336, ("128", "512", "128", "37.50")),
    (1, 5120, 14336, ("8", "512", "128", "30.47"))])
def test_tiles_counted_when_traced(M, D, F, tiles):
    """Each traced call counts its widths, the tiles chosen and the scoped
    VMEM their blocks hold (bf16, as served), once a trace."""
    import functools

    import jax

    from repro.obs import metrics
    reg = metrics.Registry()
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for s in ((M, D), (D, F), (D, F), (F, D))]
    with metrics.use(reg):
        jax.make_jaxpr(functools.partial(swiglu, route="hw"))(*args)
    fam = reg.families["swiglu_tiles_traced_total"]
    assert dict(fam.samples) == \
        {(str(D), str(F)) + tiles: 1.0}
