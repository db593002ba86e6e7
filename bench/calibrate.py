"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

One process builds the cell's engine once and, for each seed, makes that
seed's weights, serves the mix at the cell's own load for a lead-in and
``--seconds``, samples the finished requests as a run does and prints the
widest logit gap of the served tokens (the program's reading) and the
verdict that the cell's limit gives it.  For the control seeds it also
prints the fp8 control's widest gap on the same prompts and positions
(``check.control_gaps``), and its verdict, which has to be not correct.  The limit goes between
the largest program reading and the smallest control reading; see
``bench/limits/<workload>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import check, driver, model, run, spec, traffic
    from repro.serve import RESIDENT, ServeConfig, ServeEngine
    from repro.viscosity import HW

    cell = spec.cell(args.workload)
    run.require_chips(jax, cell.chips)
    run.enable_compile_cache(jax)
    conf, mix = cell.config, cell.traffic
    limit = float(cell.limits["widest_logit_gap"]["limit"])
    s = model.sizes(conf)
    cfg = model.program_config(conf)
    seeds = [int(x) for x in args.seeds.split(",")]
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    engine = None
    for seed in seeds:
        if engine is not None:
            engine.params = None        # two sets of weights do not fit
            gc.collect()
        params = model.make_params(conf, seed)
        if engine is None:
            engine = ServeEngine(cfg, params, ServeConfig(
                max_len=mix["max_len"], max_slots=mix["slots"],
                hw_route=HW, failover=RESIDENT))
            run.warm_up(engine, mix, s["vocab"])
        else:
            engine.params = params
        del params
        reqs = traffic.generate(mix, s["vocab"], seed, args.seconds)
        rec = driver.drive(engine, mix, reqs, args.seconds)
        by_rid = {r.rid: r for r in reqs}
        picked = check.sample(rec.completions, mix, seed)
        seqs = [(by_rid[r].prompt, rec.completions[r].tokens)
                for r in picked]
        gc.collect()
        ok, widest, _ = check.verdict(
            check.served_gaps(conf, seed, seqs), limit)
        out = {"seed": seed, "requests": len(seqs),
               "tokens": sum(len(t) for _, t in seqs),
               "program": widest, "program_correct": ok}
        if seed in controls:
            ok, widest, _ = check.verdict(
                check.control_gaps(conf, seed, seqs), limit)
            out.update(control=widest, control_correct=ok)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
