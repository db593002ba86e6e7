"""Re-derive roofline stats from cached HLO (no recompilation).

Usage: PYTHONPATH=src python -m repro.launch.reanalyze [--mesh single]
Updates the hlo/roofline fields of each artifacts/dryrun/*.json in place.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os

import sys

from repro.configs import get_config
from repro.launch import hlo_analysis
from repro.launch.dryrun import ART_DIR, PEAKS, roofline_terms
from repro.obs.logging import configure as obs_configure, get_logger

log = get_logger("launch.reanalyze")


def reanalyze_one(json_path: str, hlo_path: str) -> bool:
    with open(json_path) as f:
        rec = json.load(f)
    if rec.get("status") != "ok" or not os.path.exists(hlo_path):
        return False
    with gzip.open(hlo_path, "rt") as zf:
        txt = zf.read()
    n_chips = rec["n_chips"]
    stats = hlo_analysis.analyze(txt, world=n_chips)
    cfg = get_config(rec["arch"])
    score_b = hlo_analysis.score_tensor_bytes(txt, cfg.attn_chunk)
    rec["hlo"] = {
        "flops_per_dev": stats.flops,
        "hbm_bytes_per_dev": stats.bytes_hbm,
        "bytes_by_kind": {k: float(v) for k, v in
                          sorted(stats.bytes_by_kind.items(),
                                 key=lambda kv: -kv[1])},
        "top_ops": [[round(b / 1e9, 3), d] for b, d in stats.top_ops[:16]],
    }
    rec["roofline"] = roofline_terms(stats, n_chips, rec["model_flops"])
    rec["roofline"]["score_bytes_per_dev"] = score_b
    hw_mem = max(stats.bytes_hbm - score_b, 0.0) / PEAKS.hbm_bw
    terms = {"compute_s": rec["roofline"]["compute_s"], "memory_s": hw_mem,
             "collective_s": rec["roofline"]["collective_s"]}
    bound = max(terms.values())
    rec["roofline"]["hw_route"] = {
        **terms, "dominant": max(terms, key=terms.get),
        "roofline_fraction":
            (rec["model_flops"] / n_chips / PEAKS.flops_bf16) / bound
            if bound > 0 else 0.0}
    with open(json_path, "w") as f:
        json.dump(rec, f, indent=1)
    return True


def main():
    obs_configure(stream=sys.stdout)
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default=None)
    args = ap.parse_args()
    hlo_dir = os.path.join(os.path.dirname(ART_DIR), "hlo")
    n = 0
    for jp in sorted(glob.glob(os.path.join(ART_DIR, "*.json"))):
        base = os.path.basename(jp)[:-5]
        if args.mesh and not base.endswith(args.mesh):
            pass
        hp = os.path.join(hlo_dir, base + ".txt.gz")
        if reanalyze_one(jp, hp):
            n += 1
            log.info("reanalyzed", cell=base)
    log.info("done", cells=n)


if __name__ == "__main__":
    main()
