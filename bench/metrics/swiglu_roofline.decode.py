"""The Pallas swiglu kernel's share of its roofline in decode: the least
time of every covered decode tick's gated MLPs (each layer's weights read
once a tick, and the active slots' rows in and out, at HBM bandwidth;
``flops.swiglu_call``) over the device time of the swiglu calls inside
those ticks (``bench/ticks.py``)."""
from bench import flops, ticks

# the kernel's own custom call, by its instruction name (an op that only
# reads its result names it among its operands)
PATTERN = r"^%swiglu[.\d]* = \S+ custom-call\("


def read(run):
    t = ticks.kernel_in_ticks(run, PATTERN)
    if t is None or t.device_s <= 0:
        return None
    least = sum(flops.least_time(*flops.swiglu_call(run.s, a["active"]),
                                 run.peaks)[0] for a in t.attrs)
    return 100.0 * run.s["layers"] * least / t.device_s
