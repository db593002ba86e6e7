"""System-level step benchmarks on this host: staged LM train/decode under
0/1/2 faults + the reconfiguration (recompile) cost — the framework-level
analogue of the paper's Fig. 5/6 measurement."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.serve import ServeConfig, ServeEngine
from repro.train import TrainConfig, TrainRunner
from repro.models import build_model
from repro.obs import trace as obs_trace


def run(seed: int = 0):
    rows = []
    cfg = get_config("gemma2-2b").reduced()
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, batch=4,
                                  seq_len=64))
    r = TrainRunner(cfg, optim.AdamWConfig(),
                    TrainConfig(steps=1, seed=seed), data)
    params, opt, err = r.init_state()
    batch = data.device_batch(0)

    # NOTE: on this CPU host the healthy train route is already the SW
    # oracle, so fault plans equal the healthy plan and the dispatcher
    # dedupes them (reconfig_us on the *fault rows is a cache hit; the
    # degradation ratios bound measurement noise, not a real hw->sw gap).
    def timed_steps(plan, label):
        t0 = time.perf_counter()
        fn = r.dispatcher.get(plan)
        compile_us = (time.perf_counter() - t0) * 1e6
        # donation-safe fresh copies (the jitted step donates its inputs)
        pp = jax.tree_util.tree_map(jnp.copy, params)
        oo = jax.tree_util.tree_map(jnp.copy, opt)
        ee = jnp.zeros(())
        pp, oo, ee, m = fn(pp, oo, ee, batch)   # warm
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            pp, oo, ee, m = fn(pp, oo, ee, batch)
        m["loss"].block_until_ready()
        step_us = (time.perf_counter() - t0) / n * 1e6
        rows.append((f"train_step_{label}", step_us,
                     f"reconfig_us={compile_us:.0f}"))
        return step_us

    plan0 = r.plan()
    t_h = timed_steps(plan0, "healthy")
    plan1 = plan0.with_fault("flash_attention")
    t_1 = timed_steps(plan1, "1fault")
    plan2 = plan1.with_fault("swiglu_mlp")
    t_2 = timed_steps(plan2, "2fault")
    rows.append(("train_degradation_1fault", 0.0, f"{t_1/t_h:.3f}x"))
    rows.append(("train_degradation_2fault", 0.0, f"{t_2/t_h:.3f}x"))

    # serving: decode latency + failover cost mid-stream.  The healthy
    # route must differ from the fallback for the fault to be a real
    # reconfiguration (plan-keyed dispatch dedupes identical routings),
    # so healthy stages run the interpreted kernel lowering on CPU.
    from repro.viscosity import INTERPRET
    model = build_model(cfg)
    params_s = model.init(jax.random.PRNGKey(seed))
    eng = ServeEngine(cfg, params_s, ServeConfig(max_len=96,
                                                 hw_route=INTERPRET))
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (4, 32), 0,
                                 cfg.vocab_size).astype(jnp.int32)
    t0 = time.perf_counter()
    eng.generate(prompts, 24, fault_at_step=(12, "flash_attention"))
    st = [s.end - s.start for s in obs_trace.recent_spans(t0)[0]
          if s.name == "serve.tick"]
    rows.append(("decode_step_healthy", float(np.median(st[:12]) * 1e6),
                 "b=4"))
    rows.append(("decode_failover_spike", float(st[12] * 1e6),
                 "recompile-on-fault"))
    rows.append(("decode_step_post_fault", float(np.median(st[13:]) * 1e6),
                 "sw-routed stage"))
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0,
                    help="init/data RNG seed")
    for row in run(seed=ap.parse_args().seed):
        print("%s,%.1f,%s" % row)
