"""Prefill's share of the chip's bf16 peak: the model FLOPs of the window's
admissions over the host-clock seconds the steps spent admitting."""
from bench import flops, layer


def read(run):
    steps = [k for k in layer.window_steps(run) if run.rec.steps[k].admitted]
    if not steps:
        return None
    work = sum(flops.prefill_flops(run.s, P)
               for k in steps for P in layer.prefill_lengths(run, k))
    secs = sum(layer.prefill_seconds(run, k) for k in steps)
    return 100.0 * work / (secs * run.peaks.flops_bf16)
