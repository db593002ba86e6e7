"""Parameter / cache / batch PartitionSpecs for the production mesh.

Name-driven rules (we control every param name):
  * column-sharded projections (last dim over "model"): wq wk wv wg wr w1 w3
    cwk cwr in_proj bq bk bv conv_w conv_b lm_head.w
  * row-sharded projections (dim -2 over "model"): wo w2 cwv out_proj and
    the embedding table (vocab dim)
  * per-head vectors (dim -1): A_log D dt_bias u ln/norm/mix replicated
Indivisible dims fall back to replication (recorded; a hillclimb target).

Batch inputs shard over ("pod","data"); decode caches shard batch over
("pod","data") and, in the heads-major KV cache (..., B, Hkv, S, D),
kv-heads over "model" when divisible, else the sequence.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

COL = {"wq", "wk", "wv", "wg", "wr", "w1", "w3", "cwk", "cwr", "in_proj",
       "router", "w_lora_a"}
ROW = {"wo", "w2", "cwv", "out_proj", "table"}
VEC = {"bq", "bk", "bv", "conv_b", "A_log", "D", "dt_bias", "conv_w",
       "w_lora_b"}
HEAD2 = {"u"}
LM_HEAD = {"w"}


def _div(n: int, m: int) -> bool:
    return m > 0 and n % m == 0


def _model_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape.get(a, 1)
        return out
    return mesh.shape.get(axis, 1)


# Axis assignment per parameter family; variants (EXPERIMENTS.md §Perf)
# override these (e.g. 2D attention sharding, expert parallelism).
DEFAULT_AXES = {"attn": "model", "ffn": "model", "vocab": "model",
                "expert": None, "ssm": "model"}


def _batch_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return axes if axes else None


ATTN_NAMES = {"wq", "wk", "wv", "wo", "bq", "bk", "bv", "wg", "wr"}


def param_pspec(path: str, shape, mesh: Mesh, axes=None) -> P:
    axes = axes or DEFAULT_AXES
    name = path.split("/")[-1]
    is_moe = "moe" in path and name in ("w1", "w2", "w3")
    if name in ATTN_NAMES:
        ax = axes["attn"]
    elif name in LM_HEAD or name == "table":
        ax = axes["vocab"]
    elif name in ("in_proj", "out_proj", "conv_w", "conv_b", "A_log", "D",
                  "dt_bias"):
        ax = axes["ssm"]
    else:
        ax = axes["ffn"]
    m = _axis_size(mesh, ax)
    nd = len(shape)
    spec = [None] * nd
    if is_moe and axes.get("expert") and nd >= 3 and \
            _div(shape[-3], _axis_size(mesh, axes["expert"])):
        spec[-3] = axes["expert"]
    if name in COL and nd >= 2:
        if _div(shape[-1], m):
            spec[-1] = ax
    elif name in ROW and nd >= 2:
        if _div(shape[-2], m):
            spec[-2] = ax
    elif name in LM_HEAD and nd >= 2 and "lm_head" in path:
        if _div(shape[-1], m):
            spec[-1] = ax
    elif name in VEC or name in HEAD2:
        if nd >= 1 and _div(shape[-1], m) and shape[-1] >= m:
            if name in HEAD2 and nd >= 2:
                if _div(shape[-2], m):
                    spec[-2] = ax
            else:
                spec[-1] = ax
    return P(*spec)


def tree_pspecs(tree, mesh: Mesh, fn) -> Any:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    _, tdef = jax.tree_util.tree_flatten(tree)
    specs = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        specs.append(fn(key, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(tdef, specs)


def params_pspecs(params, mesh: Mesh, axes=None):
    return tree_pspecs(params, mesh,
                       lambda p, s, m: param_pspec(p, s, m, axes))


def params_shardings(params, mesh: Mesh, axes=None):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), params_pspecs(params, mesh, axes))


def opt_pspecs(opt_state, params_specs):
    """AdamW moments mirror params; count replicated."""
    from repro.optim.adamw import AdamWState
    return AdamWState(count=P(), mu=params_specs, nu=params_specs)


# ------------------------------------------------------------- activations
def batch_pspec(path: str, shape, mesh: Mesh) -> P:
    b_axes = _batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in (b_axes or ())])) or 1
    nd = len(shape)
    spec = [None] * nd
    if nd >= 1 and b_axes and _div(shape[0], total):
        spec[0] = b_axes
    return P(*spec)


def cache_pspec(path: str, shape, mesh: Mesh) -> P:
    """Decode cache leaves: stacked (L, B, ...) or per-app (B, ...).

    Heuristic: the batch dim is the first dim whose size matches the known
    batch (handled by the caller passing concrete shapes through
    ``make_cache_pspec_fn``); here we shard dim (kv-heads / ssm-heads) over
    model when a dim is divisible and looks like a head axis.
    """
    raise NotImplementedError  # replaced by make_cache_pspec_fn


def make_cache_pspec_fn(batch: int, mesh: Mesh, attn_axis="model"):
    b_axes = _batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in (b_axes or ())])) or 1
    m = _axis_size(mesh, attn_axis)

    def fn(path: str, shape, _mesh) -> P:
        nd = len(shape)
        spec = [None] * nd
        # find the batch dim (first dim equal to the serving batch)
        b_dim = None
        for i, s in enumerate(shape[:3]):
            if s == batch:
                b_dim = i
                break
        if b_dim is not None and b_axes and _div(batch, total):
            spec[b_dim] = b_axes
        name = path.split("/")[-1]
        if name in ("k", "v") and nd >= 2 and b_dim is not None:
            # (..., B, Hkv, S, D): shard kv-heads over model if divisible;
            # else shard the SEQ dim (flash-decode style partial softmax —
            # XLA inserts the max/sum combines). Without this, MHA caches
            # (e.g. qwen1.5 kv=20) replicate and overflow HBM at 32k x 128.
            if _div(shape[-3], m):
                spec[-3] = attn_axis
            elif _div(shape[-2], m):
                spec[-2] = attn_axis
        elif name == "pos" and nd >= 2 and b_dim is not None:
            if _div(shape[-1], m):
                spec[-1] = attn_axis
        elif name == "ssm" and nd >= 3:
            # (L, B, H, N, P): ssm heads over model
            if _div(shape[-3], m):
                spec[-3] = attn_axis
        elif name == "wkv" and nd >= 3:
            if _div(shape[-3], m):
                spec[-3] = attn_axis
        elif name == "conv" and nd >= 1 and _div(shape[-1], m):
            spec[-1] = attn_axis
        elif name in ("shift_tm", "shift_cm") and _div(shape[-1], m):
            spec[-1] = attn_axis
        return P(*spec)

    return fn


def rules_for(cfg, mesh: Mesh) -> Dict[str, Any]:
    """Per-arch logical-axis rules: drop indivisible shardings (recorded as
    replication; the roofline flags these as hillclimb targets)."""
    from repro.launch.sharding import DEFAULT_RULES
    rules = dict(DEFAULT_RULES)
    m = _model_size(mesh)
    if cfg.num_heads % m:
        rules["heads"] = None
    if cfg.num_kv_heads % m:
        rules["kv_heads"] = None
    if cfg.d_ff % m:
        rules["mlp"] = None
    if cfg.vocab_size % m:
        rules["vocab"] = None
    if cfg.ssm is not None:
        d_inner = cfg.ssm.expand * cfg.d_model
        if d_inner % m:
            rules["ssm_inner"] = None
        nheads = (d_inner // cfg.ssm.head_dim if cfg.family == "hybrid"
                  else cfg.d_model // max(cfg.ssm.rwkv_head_dim, 1))
        if nheads % m:
            rules["ssm_heads"] = None
    return rules
