"""Decode's share of the chip's bf16 peak: the model FLOPs of the tokens
the window's ticks decoded over the ticks' host-clock seconds."""
from bench import flops, layer


def read(run):
    ctx = layer.decode_contexts(run)
    steps = [k for k in layer.window_steps(run) if ctx.get(k)]
    if not steps:
        return None
    work = sum(flops.decode_flops(run.s, c) for k in steps for c in ctx[k])
    secs = sum(run.rec.steps[k].dt for k in steps)
    return 100.0 * work / (secs * run.peaks.flops_bf16)
