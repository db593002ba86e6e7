"""Find an open-loop mix's knee on the chip: the highest offered rate at
which no backlog grows through the window.

    python bench/sweep.py --workload <name> --seeds <n>,... --seconds <s> \
        --rates 1.5,2,2.5,3

One process, one engine, one set of weights (the first seed's); for each
rate and each seed the seed's traffic is served with ``rate_per_s``
replaced, and one JSON line gives the tokens per second, the TTFT
percentiles and the queue (requests submitted but not yet admitted) over
the first and the last third of the window.  Several seeds at one rate
show how far the seed's traffic alone spreads the end-to-end metrics.  A
cell below the knee takes about four fifths of it, as a number in its
traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def queue_thirds(rec):
    """Mean number of requests waiting for a slot at the start of each
    step, over the first and the last third of the window."""
    import numpy as np
    span = rec.we - rec.ws
    admitted = sorted(rec.steps[r.admit_step].start if r.admit_step
                      is not None else np.inf for r in rec.reqs.values())
    due = sorted(r.due for r in rec.reqs.values())
    out = []
    for lo, hi in ((0, 1 / 3), (2 / 3, 1)):
        ts = [s.start for s in rec.steps
              if rec.ws + lo * span <= s.start < rec.ws + hi * span]
        q = [np.searchsorted(due, t, "right") - np.searchsorted(
            admitted, t, "right") for t in ts]
        out.append(float(np.mean(q)) if q else 0.0)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import driver, model, run, spec, stats, traffic
    from repro.serve import RESIDENT, ServeConfig, ServeEngine
    from repro.viscosity import HW

    cell = spec.cell(args.workload)
    run.require_chips(jax, cell.chips)
    run.enable_compile_cache(jax)
    conf, mix = cell.config, dict(cell.traffic)
    s = model.sizes(conf)
    seeds = [int(x) for x in args.seeds.split(",")]
    engine = ServeEngine(model.program_config(conf),
                         model.make_params(conf, seeds[0]), ServeConfig(
                             max_len=mix["max_len"], max_slots=mix["slots"],
                             hw_route=HW, failover=RESIDENT))
    run.warm_up(engine, mix, s["vocab"])
    for rate, seed in ((float(r), sd) for r in args.rates.split(",")
                       for sd in seeds):
        mix["rate_per_s"] = rate
        reqs = traffic.generate(mix, s["vocab"], seed, args.seconds)
        rec = driver.drive(engine, mix, reqs, args.seconds)
        first, last = queue_thirds(rec)
        print(json.dumps({
            "rate_per_s": rate, "seed": seed,
            "due": len(stats.due_in_window(rec)), **stats.end_to_end(rec),
            "queue_first_third": first, "queue_last_third": last}),
            flush=True)


if __name__ == "__main__":
    main()
