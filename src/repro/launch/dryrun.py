from repro.launch.xla_presets import force_host_device_count
force_host_device_count(512)
# ^ MUST precede any jax import: jax locks the device count on first init.
import os
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the real jitted program (train_step with
AdamW update / prefill / decode_step) with explicit in/out shardings on the
production mesh, compiles it AOT (no allocation), and records:
  * memory_analysis()   — per-device argument/output/temp bytes (fits?)
  * cost_analysis()     — XLA's flops/bytes (loop bodies counted once)
  * trip-count-aware HLO stats (launch/hlo_analysis.py): per-device FLOPs,
    HBM-traffic proxy, per-collective link bytes  -> the roofline terms
  * the roofline terms themselves (seconds) + dominant bottleneck.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--force]

Results are cached as JSON under artifacts/dryrun/.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import ARCH_NAMES, SHAPES, applicable, get_config
from repro.launch import hlo_analysis
from repro.launch.mesh import chip_peaks, make_production_mesh
from repro.launch.partition import (batch_pspec, make_cache_pspec_fn,
                                    params_pspecs, rules_for, tree_pspecs)
from repro.launch.sharding import axis_rules
from repro.models import build_model, input_specs, params_specs
from repro.obs.logging import configure as obs_configure, get_logger

log = get_logger("launch.dryrun")

# The chip the dry-run's roofline terms are computed for: the mesh is
# virtual CPU devices, so the target kind is named here, not read from them.
TARGET_KIND = "TPU v5 lite"
PEAKS = chip_peaks(TARGET_KIND)

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _count_params(params_sds) -> int:
    return int(sum(np.prod(l.shape) for l in
                   jax.tree_util.tree_leaves(params_sds)))


def _active_params(cfg, params_sds) -> int:
    total = _count_params(params_sds)
    if cfg.moe is None:
        return total
    flat = jax.tree_util.tree_flatten_with_path(params_sds)[0]
    expert = 0
    for path, leaf in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        if "moe" in keys and keys[-1] in ("w1", "w2", "w3"):
            expert += int(np.prod(leaf.shape))
    active = total - expert + expert * cfg.moe.top_k // cfg.moe.num_experts
    return active


def model_flops(cfg, shape, params_sds) -> float:
    n = _active_params(cfg, params_sds)
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_lowered(arch: str, shape_name: str, mesh,
                  loss_chunk: Optional[int] = None,
                  microbatch: Optional[int] = None,
                  overrides: Optional[Dict[str, Any]] = None,
                  rules: Optional[Dict[str, Any]] = None,
                  axes: Optional[Dict[str, Any]] = None,
                  grad_unreduced: bool = False,
                  zero1: bool = False):
    """Build and lower the cell's program. Returns (lowered, meta).

    ``rules``/``axes``: sharding-variant overrides (§Perf hillclimbs) —
    logical-axis rules for activations and axis assignment for params.
    """
    cfg = get_config(arch)
    import dataclasses
    if loss_chunk:
        cfg = dataclasses.replace(cfg, loss_chunk=loss_chunk)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        raise SkipCell(why)
    rules = rules if rules is not None else rules_for(cfg, mesh)
    attn_axis = (axes or {}).get("attn", "model")
    model = build_model(cfg)
    with mesh, axis_rules(rules, mesh):
        p_sds = params_specs(model)
        p_sh = _ns(mesh, params_pspecs(p_sds, mesh, axes))
        specs = input_specs(cfg, shape, model)
        if shape.kind == "train":
            opt_sds = jax.eval_shape(optim.init, p_sds)
            o_specs = tree_pspecs(opt_sds, mesh,
                                  lambda p, s, m: P())  # rebuilt below
            o_sh = _ns(mesh, optim.AdamWState(
                count=P(), mu=params_pspecs(p_sds, mesh, axes),
                nu=params_pspecs(p_sds, mesh, axes)))
            b_sds = specs["batch"]
            b_sh = _ns(mesh, tree_pspecs(b_sds, mesh, batch_pspec))
            ocfg = optim.AdamWConfig()
            # microbatch count: keep per-device micro batch ~4 sequences
            dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                              if a in mesh.shape]))
            b_local = max(1, shape.global_batch // dp)
            k = microbatch if microbatch else max(1, b_local // 4)

            def grads_of(params, mb):
                (loss, _), g = jax.value_and_grad(
                    model.forward, has_aux=True)(params, mb)
                return loss, g

            # §Perf HC-A: keep per-microbatch grads UNREDUCED over the data
            # axes so the cross-replica all-reduce runs once per step, not
            # once per microbatch (jax 'unreduced' PartitionSpec).
            dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
            g_specs = params_pspecs(p_sds, mesh, axes)
            def _isP(x):
                return isinstance(x, P)

            def _extend(s, shape):
                """Additionally shard a free dim over the data axes
                (ZeRO-style: grads reduce-scatter, moments stay sharded)."""
                lst = list(s) + [None] * (len(shape) - len(s))
                dp_total = max(1, int(np.prod([mesh.shape[a]
                                               for a in dp_axes])))
                for i, d in enumerate(shape):
                    if lst[i] is None and d % dp_total == 0:
                        lst[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                        break
                return P(*lst)

            def _extended_sh():
                flatspecs = jax.tree_util.tree_flatten(g_specs, is_leaf=_isP)
                flatleaves = jax.tree_util.tree_leaves(p_sds)
                ext = [NamedSharding(mesh, _extend(s, l.shape))
                       for s, l in zip(flatspecs[0], flatleaves)]
                return jax.tree_util.tree_unflatten(flatspecs[1], ext)

            g_unred_sh = g_red_sh = None
            if grad_unreduced == "unreduced":  # needs Explicit-mode mesh
                g_unred_sh = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, P(*s,
                                                    unreduced=set(dp_axes))),
                    g_specs, is_leaf=_isP)
            elif grad_unreduced or zero1:
                # data-sharded accumulator: per-microbatch partial sums
                # land via reduce-scatter (half the all-reduce bytes);
                # one all-gather restores replication at the update.
                g_unred_sh = _extended_sh()
                grad_unreduced = True
            g_red_sh = _ns(mesh, g_specs)
            if zero1:
                # ZeRO-1: AdamW moments sharded over data too — the only
                # way a 46B-param MoE's f32 optimizer fits 16 GB chips
                o_sh = optim.AdamWState(
                    count=NamedSharding(mesh, P()),
                    mu=_extended_sh(), nu=_extended_sh())

            def train_step(params, opt_state, batch):
                if k > 1:
                    mbs = jax.tree_util.tree_map(
                        lambda a: a.reshape((k, a.shape[0] // k)
                                            + a.shape[1:]), batch)

                    def body(carry, mb):
                        g_acc, l_acc = carry
                        loss, g = grads_of(params, mb)
                        if grad_unreduced:
                            g = jax.lax.with_sharding_constraint(
                                g, g_unred_sh)
                        g_acc = jax.tree_util.tree_map(
                            lambda A, B: A + B.astype(jnp.float32),
                            g_acc, g)
                        return (g_acc, l_acc + loss), None

                    g0 = jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    if grad_unreduced:
                        g0 = jax.lax.with_sharding_constraint(g0, g_unred_sh)
                    (grads, loss), _ = jax.lax.scan(
                        body, (g0, jnp.float32(0)), mbs)
                    if grad_unreduced and not zero1:  # reduce once, here
                        grads = jax.lax.with_sharding_constraint(
                            grads, g_red_sh)
                    # zero1: grads STAY data-sharded; the optimizer update
                    # runs on sharded moments and the param delta is
                    # all-gathered once (the ZeRO-1 pattern)
                    grads = jax.tree_util.tree_map(lambda g: g / k, grads)
                    loss = loss / k
                else:
                    loss, grads = grads_of(params, batch)
                params, opt_state, om = optim.update(ocfg, grads,
                                                     opt_state, params)
                return params, opt_state, loss, om["grad_norm"]

            fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_sds, opt_sds, b_sds)
        elif shape.kind == "prefill":
            b_sds = specs["batch"]
            cache_fn = make_cache_pspec_fn(shape.global_batch, mesh,
                                           attn_axis=attn_axis)
            b_spec = {}
            for k, v in b_sds.items():
                if k == "cache":
                    b_spec[k] = tree_pspecs(v, mesh, cache_fn)
                else:
                    b_spec[k] = tree_pspecs(v, mesh, batch_pspec)
            b_sh = _ns(mesh, b_spec)
            state_sh = None  # prefill output sharding: let XLA propagate
            fn = jax.jit(model.prefill, in_shardings=(p_sh, b_sh),
                         donate_argnums=(1,))
            lowered = fn.lower(p_sds, b_sds)
        else:  # decode
            cache_sds, tok_sds, t_sds = (specs["cache"], specs["tokens"],
                                         specs["t"])
            cache_fn = make_cache_pspec_fn(shape.global_batch, mesh,
                                           attn_axis=attn_axis)
            c_spec = tree_pspecs(cache_sds, mesh, cache_fn)
            c_sh = _ns(mesh, c_spec)
            tok_sh = NamedSharding(mesh, batch_pspec("tokens",
                                                     tok_sds.shape, mesh))
            t_sh = NamedSharding(mesh, P())
            fn = jax.jit(model.decode_step,
                         in_shardings=(p_sh, c_sh, tok_sh, t_sh),
                         out_shardings=(None, c_sh),
                         donate_argnums=(1,))
            lowered = fn.lower(p_sds, cache_sds, tok_sds, t_sds)
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "params": _count_params(p_sds),
            "active_params": _active_params(cfg, p_sds),
            "model_flops": model_flops(cfg, shape, p_sds),
            "microbatch": (microbatch or "auto") if shape.kind == "train"
            else None,
            "rules": {k: (list(v) if isinstance(v, tuple) else v)
                      for k, v in rules.items()}}
    return lowered, meta


class SkipCell(Exception):
    pass


def roofline_terms(stats: hlo_analysis.CompStats, n_chips: int,
                   mfl: float) -> Dict[str, Any]:
    coll = float(sum(stats.coll_bytes.values()))
    t_comp = stats.flops / PEAKS.flops_bf16        # per-device flops already
    t_mem = stats.bytes_hbm / PEAKS.hbm_bw
    t_coll = coll / PEAKS.ici_link_bw
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    bound = max(t_comp, t_mem, t_coll)
    mfu = (mfl / n_chips / PEAKS.flops_bf16) / bound if bound > 0 else 0.0
    return {**terms, "dominant": dom,
            "useful_flops_ratio": (mfl / n_chips) / max(stats.flops, 1.0),
            "roofline_fraction": mfu,
            "coll_bytes": {k: float(v) for k, v in stats.coll_bytes.items()},
            "n_coll": {k: int(v) for k, v in stats.n_coll.items()}}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = ART_DIR, force: bool = False,
             loss_chunk: Optional[int] = None,
             microbatch: Optional[int] = None,
             overrides: Optional[Dict[str, Any]] = None,
             tag: str = "") -> Dict[str, Any]:
    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{arch}__{shape_name}__{mesh_name}{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            cached = json.load(f)
        if cached.get("status") != "fail":   # failed cells retry
            return cached
    t0 = time.time()
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name}
    HBM_LIMIT = 15.5e9   # v5e 16 GB minus runtime reserve
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = int(np.prod(list(mesh.shape.values())))
        dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                          if a in mesh.shape]))
        b_local = max(1, SHAPES[shape_name].global_batch // dp)
        # train cells: auto-bump gradient-accumulation microbatches until
        # the per-device temp memory fits HBM (an OOM-at-compile is a bug)
        k = microbatch or max(1, b_local // 4)
        while True:
            lowered, meta = build_lowered(arch, shape_name, mesh,
                                          loss_chunk=loss_chunk,
                                          microbatch=k,
                                          overrides=overrides)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            temp = getattr(ma, "temp_size_in_bytes", 0)
            if (SHAPES[shape_name].kind != "train" or temp <= HBM_LIMIT
                    or k >= b_local or microbatch):
                break
            k = min(b_local, k * 2)
        meta["microbatch"] = k if SHAPES[shape_name].kind == "train" \
            else None
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else (ca or {})
        txt = compiled.as_text()
        # cache the SPMD HLO so analyzer changes re-analyze without
        # recompiling (compiles are minutes; parses are seconds)
        import gzip
        hlo_dir = os.path.join(os.path.dirname(out_dir), "hlo")
        os.makedirs(hlo_dir, exist_ok=True)
        with gzip.open(os.path.join(
                hlo_dir, f"{arch}__{shape_name}__{mesh_name}{tag}.txt.gz"),
                "wt") as zf:
            zf.write(txt)
        stats = hlo_analysis.analyze(txt, world=n_chips)
        # HW-route projection: the Pallas flash kernel keeps score tensors
        # in VMEM on TPU; subtract their XLA-path HBM traffic (score shapes
        # are (.., >=1024, attn_chunk); see hlo_analysis.score_tensor_bytes)
        cfg_now = get_config(arch)
        score_b = hlo_analysis.score_tensor_bytes(txt, cfg_now.attn_chunk)
        rec.update(meta)
        rec.update({
            "status": "ok", "n_chips": n_chips,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
                "output_bytes": getattr(ma, "output_size_in_bytes", 0),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
                "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
            },
            # resident = args (donated outputs alias them) + non-aliased
            # outputs + temps; donation avoids DOUBLING, not residency
            "fits_hbm": bool(getattr(ma, "argument_size_in_bytes", 0)
                             + getattr(ma, "output_size_in_bytes", 0)
                             - getattr(ma, "alias_size_in_bytes", 0)
                             + getattr(ma, "temp_size_in_bytes", 0)
                             <= 16e9),
            "xla_cost": {k: float(v) for k, v in dict(ca).items()
                         if isinstance(v, (int, float))},
            "hlo": {"flops_per_dev": stats.flops,
                    "hbm_bytes_per_dev": stats.bytes_hbm,
                    "bytes_by_kind": {k: float(v) for k, v in
                                      sorted(stats.bytes_by_kind.items(),
                                             key=lambda kv: -kv[1])},
                    "top_ops": [[round(b / 1e9, 3), d]
                                for b, d in stats.top_ops[:16]]},
            "roofline": roofline_terms(stats, n_chips, meta["model_flops"]),
        })
        rec["roofline"]["score_bytes_per_dev"] = score_b
        hw_mem = max(stats.bytes_hbm - score_b, 0.0) / PEAKS.hbm_bw
        terms = {"compute_s": rec["roofline"]["compute_s"],
                 "memory_s": hw_mem,
                 "collective_s": rec["roofline"]["collective_s"]}
        bound = max(terms.values())
        rec["roofline"]["hw_route"] = {
            **terms,
            "dominant": max(terms, key=terms.get),
            "roofline_fraction":
                (meta["model_flops"] / n_chips / PEAKS.flops_bf16) / bound
                if bound > 0 else 0.0}
    except SkipCell as e:
        rec.update({"status": "skip", "reason": str(e)})
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-4000:]})
    rec["wall_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    obs_configure(stream=sys.stdout)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()

    cells = []
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))
    t0 = time.time()
    n_ok = n_skip = n_fail = 0
    for i, (arch, shape, mp) in enumerate(cells):
        rec = run_cell(arch, shape, mp, out_dir=args.out, force=args.force)
        n_ok += rec["status"] == "ok"
        n_skip += rec["status"] == "skip"
        n_fail += rec["status"] == "fail"
        dom = rec.get("roofline", {}).get("dominant", "-")
        log.info("cell", i=f"{i + 1}/{len(cells)}", arch=arch,
                 shape=shape, mesh="multi" if mp else "single",
                 status=rec["status"], wall_s=rec["wall_s"], dom=dom)
        if rec["status"] == "fail":
            log.error("cell_failed", arch=arch, shape=shape,
                      error=rec["error"][:300])
    log.info("done", wall_s=round(time.time() - t0), ok=n_ok,
             skip=n_skip, fail=n_fail)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
