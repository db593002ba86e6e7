"""Model FLOPs of every token the window processed (prefill and decode,
attention at each request's real context) over the window's seconds times
the chip's bf16 peak."""
from bench import flops, layer


def read(run):
    ctx = layer.decode_contexts(run)
    total = 0.0
    for k in layer.window_steps(run):
        total += sum(flops.prefill_flops(run.s, P)
                     for P in layer.prefill_lengths(run, k))
        total += sum(flops.decode_flops(run.s, c) for c in ctx.get(k, ()))
    return 100.0 * total / (run.seconds * run.peaks.flops_bf16)
