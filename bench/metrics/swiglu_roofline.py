"""The Pallas swiglu kernel's share of its roofline: the least time of
every swiglu call in the window (max of FLOPs over the bf16 peak and bytes
over HBM bandwidth; ``flops.swiglu_call``) over the kernel's device time in
the trace.  Rows per call: the prompt length in prefill, the decoded slots
in a tick; the weights are read once per call.  Decode calls are bound by
memory, long prefills by compute."""
from bench import flops, layer

PATTERN = r"swiglu|^%branch_1_fun[.\d]* = bf16\[\d+(?:,\d+)?,\d+\]\{[^}]*\} custom-call\("


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.op_seconds(PATTERN)
    if device_s <= 0:
        return None
    ctx = layer.decode_contexts(run)
    least = 0.0
    for k in layer.traced_steps(run):
        rows = layer.prefill_lengths(run, k)
        if ctx.get(k):
            rows = rows + [len(ctx[k])]
        least += sum(flops.least_time(*flops.swiglu_call(run.s, m),
                                      run.peaks)[0] for m in rows)
    return 100.0 * run.s["layers"] * least / device_s
