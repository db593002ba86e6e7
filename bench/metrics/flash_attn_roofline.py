"""The Pallas flash-attention kernel's share of its roofline, in prefill:
the least time of every prefill attention in the window (causal FLOPs
over the bf16 peak, or q/k/v/o bytes over HBM bandwidth, whichever is
longer; ``flops.flash_call``) over the kernel's device time in the
trace."""
from bench import flops, layer

PATTERN = r"flash_att|^%branch_1_fun[.\d]* = bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\} custom-call\("


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.op_seconds(PATTERN)
    if device_s <= 0:
        return None
    least = sum(flops.least_time(*flops.flash_call(run.s, P), run.peaks)[0]
                for k in layer.traced_steps(run)
                for P in layer.prefill_lengths(run, k))
    return 100.0 * run.s["layers"] * least / device_s
