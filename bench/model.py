"""A configuration file's sizes, as the program's ``ModelConfig``, and the
weights the benchmark makes for it from ``--seed``.

The configuration files use the keys of the model's published
``config.json``.  The weights are the benchmark's own: random bf16 values
drawn from the seed, one jitted call on the device, laid out as the
program's parameter tree.  ``layer_weights`` is the one definition of
every value; the reference (``reference.py``) calls it again, layer by
layer, and so never reads what the program holds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WEIGHT_DTYPE = jnp.bfloat16
EMBED_STD = 0.02      # embedding rows
NORM_STD = 0.1        # RMSNorm scales: 1 + NORM_STD * N(0, 1)
BIAS_STD = 0.1        # q/k/v biases, where the model has them


def sizes(conf):
    """The numbers the benchmark computes with, from a configuration file."""
    heads = conf["num_attention_heads"]
    return dict(
        layers=conf["num_hidden_layers"], d=conf["hidden_size"],
        heads=heads, kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        eps=conf["rms_norm_eps"], theta=conf["rope_theta"],
        bias=bool(conf.get("attention_bias", False)))


def program_config(conf):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ATTN_GLOBAL, ModelConfig
    if conf["hidden_act"] != "silu" or conf["tie_word_embeddings"]:
        raise ValueError("the serving path runs untied SwiGLU models")
    s = sizes(conf)
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ff"], vocab_size=s["vocab"],
        layer_pattern=(ATTN_GLOBAL,), qkv_bias=s["bias"],
        rope_theta=float(s["theta"]), norm_eps=float(s["eps"]),
        tie_embeddings=False, mlp_act="silu", dtype="bfloat16",
        param_dtype="bfloat16", remat=False)


def seed_key(seed: int) -> jax.Array:
    """A threefry key holding all 64 bits of ``seed``."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number in [0, 2**64)")
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def _normal(key, i, shape, std):
    return jax.random.normal(jax.random.fold_in(key, i), shape,
                             jnp.float32) * std


def layer_weights(key, layer, s):
    """Layer ``layer``'s weights (bf16), in the program's layout."""
    k = jax.random.fold_in(key, 1000 + layer)
    d, hd, f = s["d"], s["head_dim"], s["ff"]
    hq, hkv = s["heads"] * hd, s["kv_heads"] * hd
    attn = {"wq": _normal(k, 0, (d, hq), d ** -0.5),
            "wk": _normal(k, 1, (d, hkv), d ** -0.5),
            "wv": _normal(k, 2, (d, hkv), d ** -0.5),
            "wo": _normal(k, 3, (hq, d), hq ** -0.5)}
    if s["bias"]:
        attn.update(bq=_normal(k, 4, (hq,), BIAS_STD),
                    bk=_normal(k, 5, (hkv,), BIAS_STD),
                    bv=_normal(k, 6, (hkv,), BIAS_STD))
    tree = {"ln1": {"scale": 1.0 + _normal(k, 7, (d,), NORM_STD)},
            "attn": attn,
            "ln2": {"scale": 1.0 + _normal(k, 8, (d,), NORM_STD)},
            "mlp": {"w1": _normal(k, 9, (d, f), d ** -0.5),
                    "w3": _normal(k, 10, (d, f), d ** -0.5),
                    "w2": _normal(k, 11, (f, d), f ** -0.5)}}
    return jax.tree_util.tree_map(lambda a: a.astype(WEIGHT_DTYPE), tree)


def top_weights(key, s):
    """Embedding, final norm and LM head (bf16), in the program's layout."""
    d, v = s["d"], s["vocab"]
    tree = {"embed": {"table": _normal(key, 0, (v, d), EMBED_STD)},
            "final_norm": {"scale": 1.0 + _normal(key, 1, (d,), NORM_STD)},
            "lm_head": {"w": _normal(key, 2, (d, v), d ** -0.5)}}
    return jax.tree_util.tree_map(lambda a: a.astype(WEIGHT_DTYPE), tree)


def make_params(conf, seed: int):
    """Every weight of the model, made on the device in one jitted call."""
    s = sizes(conf)

    @jax.jit
    def init(key):
        params = top_weights(key, s)
        params["layers"] = jax.vmap(lambda i: layer_weights(key, i, s))(
            jnp.arange(s["layers"]))
        return params

    return init(seed_key(seed))


def check_layout(params, cfg):
    """The program's own parameter tree must have these shapes exactly."""
    from repro.models import build_model
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or \
            jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")


def param_count(conf) -> int:
    s = sizes(conf)
    d, hd, f = s["d"], s["head_dim"], s["ff"]
    hq, hkv = s["heads"] * hd, s["kv_heads"] * hd
    layer = 2 * d * hq + 2 * d * hkv + 3 * d * f + 2 * d
    layer += (hq + 2 * hkv) if s["bias"] else 0
    return s["layers"] * layer + 2 * s["vocab"] * d + d


def kv_bytes_per_token(conf) -> int:
    s = sizes(conf)
    return 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * 2
