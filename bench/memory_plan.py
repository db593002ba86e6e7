"""Compile a cell's decode program and its longest prefill for a described
v5e (no chip needed) and print what the compiler says they hold.

    JAX_PLATFORMS=cpu python bench/memory_plan.py --workload <name>

Run it before a cell's first chip run, to see that its slots fit.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, default=0,
                    help="try another slot count than the mix's")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import model, spec, traffic
    from repro.core.routing import RoutingPlan
    from repro.models import build_model
    from repro.serve.engine import RESIDENT, build_decode, build_prefill
    from repro.train.runner import model_stage_names
    from repro.viscosity import HW

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.cell(args.workload)
    conf, mix = cell.config, cell.traffic
    cfg = model.program_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    s = model.sizes(conf)
    params = placed(jax.eval_shape(
        lambda k: {**model.top_weights(k, s), "layers": jax.vmap(
            lambda i: model.layer_weights(k, i, s))(
                jnp.arange(s["layers"]))}, model.seed_key(0)))
    names = list(model_stage_names(cfg))
    plan = RoutingPlan.for_stages(names, target=HW)
    mask = placed(jax.ShapeDtypeStruct((len(names),), jnp.bool_))
    cache1 = jax.eval_shape(lambda: build_model(cfg).init_cache(
        1, mix["max_len"]))
    S = args.slots or mix["slots"]
    pool = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((S,) + a.shape, a.dtype), cache1)
    P = max(traffic.ladder(mix))
    batch = placed({"tokens": jax.ShapeDtypeStruct((1, P), jnp.int32),
                    "cache": cache1})
    if not args.slots:
        pre = build_prefill(cfg, plan, RESIDENT).lower(params, batch,
                                                       mask).compile()
        print(f"{args.workload}: prefill P={P}: {pre.memory_analysis()}",
              flush=True)
    dec = build_decode(cfg, plan, RESIDENT).lower(
        params, placed(pool),
        placed(jax.ShapeDtypeStruct((S, 1, 1), jnp.int32)),
        placed(jax.ShapeDtypeStruct((S,), jnp.int32)), mask).compile()
    print(f"{args.workload}: decode S={S} max_len={mix['max_len']}: "
          f"{dec.memory_analysis()}", flush=True)


if __name__ == "__main__":
    main()
