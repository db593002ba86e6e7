"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model with weights made on the device from the seed,
serves its traffic through ``ServeEngine.session()`` on the HW route with
RESIDENT failover, warms up every prefill length of the mix's ladder and
the decode program, runs a lead-in of traffic, then measures for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` profiles exactly the window and reports its per-layer
metrics.  After the window the program's state is freed and a sample of
the finished requests is compared with the float32 reference
(``check.py``).  The last line of standard output is one JSON object.

There is no CPU fallback: without as many TPU chips as the cell asks for,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), for every program."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclass
class RunView:
    """What a per-layer metric reader gets."""
    rec: Any                 # driver.Record
    s: Dict[str, Any]        # model.sizes of the configuration
    peaks: Any               # peaks.Peaks of the chip
    seconds: float
    compiles_window: int
    trace: Optional[Any]     # xtrace.Trace, in a --trace 1 run


def warm_up(engine, mix, vocab: int):
    """Every prefill length of the ladder, every slot index and the
    decode program, once each."""
    import numpy as np
    from bench import traffic
    from repro.serve import Request
    lens = traffic.ladder(mix)
    rng = np.random.default_rng(0)
    sess = engine.session()
    for i in range(max(len(lens), mix["slots"])):
        sess.submit(Request(rid=i, prompt=rng.integers(
            0, vocab, lens[i % len(lens)]).astype(np.int32),
            max_new_tokens=3))
    while sess.pending():
        sess.step()
    sess.poll()
    sess.close()


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_proc: float, *, keep: Optional[dict] = None
             ) -> Dict[str, Any]:
    import jax
    from bench import check, driver, model, stats, traffic, xtrace
    from bench.compile_log import CompileLog
    from bench.peaks import peaks
    from bench.spec import metric_reader
    from repro.serve import RESIDENT, ServeConfig, ServeEngine
    from repro.viscosity import HW

    log = CompileLog(jax)
    conf, mix = cell.config, cell.traffic
    s = model.sizes(conf)
    cfg = model.program_config(conf)
    params = model.make_params(conf, seed)
    jax.block_until_ready(params)
    model.check_layout(params, cfg)
    engine = ServeEngine(cfg, params, ServeConfig(
        max_len=mix["max_len"], max_slots=mix["slots"],
        hw_route=HW, failover=RESIDENT))
    del params
    warm_up(engine, mix, s["vocab"])
    reqs = traffic.generate(mix, s["vocab"], seed, seconds)
    print(f"bench: set-up compiles {log.compiles} ({log.compile_s:.1f} s), "
          f"persistent-cache hits {log.cache_hits}", flush=True)

    marks: Dict[str, Any] = {}

    def before_window():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)

    def at_window_start():
        marks["setup_s"] = time.time() - t_proc
        marks["compiles0"] = log.compiles
        if trace:   # a span made before the profiler started is dropped
            marks["span"] = jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN)
            marks["span"].__enter__()

    span = jax.profiler.TraceAnnotation if trace else driver.no_span
    rec = driver.drive(engine, mix, reqs, seconds, span=span,
                       before_window=before_window,
                       at_window_start=at_window_start)
    if trace:
        marks["span"].__exit__(None, None, None)
    compiles_window = log.compiles - marks["compiles0"]
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    tr = None
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        tr = xtrace.Trace(xtrace.xplane_file(TRACE_DIR), chips=len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)  # hundreds of MB
        print(f"bench: trace written in {t1 - t0:.1f} s, read in "
              f"{time.perf_counter() - t1:.1f} s", flush=True)

    due = stats.due_in_window(rec)
    print(f"bench: {len(due)} requests due in the window "
          f"({len(due) / seconds:.3f}/s), {len(rec.completions)} finished "
          f"since the lead-in began, {len(rec.steps)} steps; "
          f"generator late by p50 "
          f"{1e3 * stats.percentile(rec.late_s or [0], 50):.3f} ms, max "
          f"{1e3 * max(rec.late_s or [0]):.3f} ms; compiles in window "
          f"{compiles_window}; peak_bytes_in_use {peak}", flush=True)
    view = RunView(rec, s, peaks(devices[0].device_kind), seconds,
                   compiles_window, tr)
    if trace:
        out = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(view)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = stats.end_to_end(rec)
        e2e["setup_s"] = marks["setup_s"]
        out = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
               for m in cell.end_to_end}

    # ---- correctness, with the program's state freed --------------------
    by_rid = {r.rid: r for r in reqs}
    picked = check.sample(rec.completions, mix, seed)
    seqs = [(by_rid[r].prompt, rec.completions[r].tokens) for r in picked]
    if keep is not None:
        keep.update(record=rec, seqs=seqs)
    del engine
    gc.collect()
    t0 = time.perf_counter()
    per_seq = check.served_gaps(conf, seed, seqs)
    limit = float(cell.limits["widest_logit_gap"]["limit"])
    ok, widest, bad = check.verdict(per_seq, limit)
    print(f"bench: checked {len(seqs)} requests, "
          f"{sum(len(t) for _, t in seqs)} served tokens (prompts "
          f"{[len(p) for p, _ in seqs]}) in {time.perf_counter() - t0:.1f} s",
          flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result: Dict[str, Any] = {"correct": bool(ok), "attempted": len(due),
                              "failed": int(bad), "metrics": out,
                              "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["check"] = {"widest_logit_gap": {"value": widest,
                                            "limit": limit}}
    return result


def main(argv=None):
    t_proc = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import spec
    cell = spec.cell(args.workload)
    import jax
    devices = require_chips(jax, cell.chips)
    enable_compile_cache(jax)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t_proc)
    for name, c in result["check"].items():
        print(f"check: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
