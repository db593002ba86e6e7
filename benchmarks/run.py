"""Benchmark harness: one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (per assignment contract).

The exit status is part of the contract: a sub-benchmark that crashes,
returns no rows, or returns malformed rows fails the whole run (exit 1)
— a broken bench can never silently vanish from the aggregate.
``--seed`` forwards to every module whose ``run()`` accepts one, so CI
runs are reproducible.

``--emit-json PATH`` additionally writes the machine-readable trajectory
snapshot (``BENCH_<n>.json``): per-bench ``us_per_call`` + ``derived``,
the backend fingerprint, tuner cache-hit stats, and a ``calibration_us``
reference timing (a fixed jitted matmul) that ``benchmarks/compare.py``
uses to normalize away CI-runner speed differences before applying its
regression thresholds.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback

MODULES = [
    "benchmarks.datacenter_fig2",    # Fig. 2 (a,b)
    "benchmarks.casestudy_fig5",     # Fig. 5 FFT/AES/DCT
    "benchmarks.passthrough_fig6",   # Fig. 6 stage x size sweep
    "benchmarks.multifault_fig7",    # Fig. 7 two-fault sweep
    "benchmarks.hotspare_fig8",      # Fig. 8 FPGA fallback
    "benchmarks.kernel_micro",       # per-kernel parity + wall
    "benchmarks.step_bench",         # staged train/serve under faults
    "benchmarks.serve_bench",        # continuous vs fixed-batch serving
    "benchmarks.traffic_bench",      # open-loop goodput/tail under faults
    "benchmarks.chaos_bench",        # randomized fault-schedule soak
    "benchmarks.fleet_bench",        # MC fault trace through the fleet
    "benchmarks.roofline",           # dry-run roofline summary
]


def _row_error(row) -> str:
    """Why ``row`` is not a valid (name, us_per_call, derived) row."""
    if not isinstance(row, (tuple, list)) or len(row) != 3:
        return "not a 3-tuple"
    name, us, _derived = row
    if not isinstance(name, str) or not name:
        return "empty/non-string name"
    if isinstance(us, bool) or not isinstance(us, (int, float)) \
            or not math.isfinite(us):
        return f"non-finite us_per_call {us!r}"
    return ""


def calibration_us(reps: int = 5) -> float:
    """Reference timing: fixed jitted 512x512 f32 matmul, best-of-reps.

    Scales with the host's raw compute speed the same way the benches
    do, so ``new_us / new_calibration`` is comparable across runners.
    """
    import jax
    import jax.numpy as jnp

    x = jnp.ones((512, 512), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()   # compile outside the timed region
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def main(argv=None) -> None:
    import importlib
    import inspect

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0,
                    help="forwarded to every bench run() that takes one")
    ap.add_argument("--emit-json", metavar="PATH", default=None,
                    help="also write the BENCH_<n>.json trajectory "
                         "snapshot (see benchmarks/compare.py)")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only modules whose name contains SUBSTR")
    args = ap.parse_args(argv)

    # The preset layer is the one sanctioned XLA_FLAGS surface; applying
    # here (before any bench imports jax) mirrors initialize_runtime.
    from repro.launch import compile_cache, xla_presets
    xla_presets.apply()
    compile_cache.enable()

    modules = [m for m in MODULES
               if args.only is None or args.only in m]
    failures = []
    benches = {}
    modules_s = {}
    print("name,us_per_call,derived")
    for modname in modules:
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            kw = ({"seed": args.seed} if "seed" in
                  inspect.signature(mod.run).parameters else {})
            rows = list(mod.run(**kw))
            if not rows:
                raise RuntimeError(f"{modname}.run() returned no rows")
            bad = [(row, err) for row in rows
                   if (err := _row_error(row))]
            if bad:
                raise RuntimeError(
                    f"{modname} emitted malformed row(s): {bad[:3]}")
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
                benches[name] = {"us_per_call": round(float(us), 1),
                                 "derived": str(derived),
                                 "module": modname}
            modules_s[modname] = round(time.time() - t0, 1)
            print(f"# {modname} done in {time.time()-t0:.1f}s",
                  file=sys.stderr)
        except Exception:  # noqa: BLE001 - every failure must be counted
            failures.append(modname)
            print(f"# {modname} FAILED", file=sys.stderr)
            traceback.print_exc()
    if args.emit_json:
        from repro.kernels import tuning
        from repro.kernels.tuning.cache import backend_fingerprint
        snap = {
            "schema": 1,
            "backend": backend_fingerprint(),
            "calibration_us": round(calibration_us(), 1),
            "tuner": tuning.stats(),
            "failures": failures,
            "modules_s": modules_s,
            "benches": benches,
        }
        with open(args.emit_json, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.emit_json} ({len(benches)} benches)",
              file=sys.stderr)
    if failures:
        print(f"# {len(failures)} benchmark(s) failed: "
              f"{', '.join(failures)}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
