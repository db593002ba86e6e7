"""Decode attention's share of its roofline: the least time of the
attention of every covered decode tick over the device time of the
attention ops inside those ticks (``bench/ticks.py``).  A tick's least
time reads, in every layer, the k and v rows of the ``kv_live``
positions its slots attend (an attribute of the program's ``serve.tick``
span) and each decoded token's q and o, in bf16, at HBM bandwidth, or
does its FLOPs at the bf16 peak, whichever is longer.  The program reads
every slot's whole cache, live or not, so the share says how far decode
attention is from reading only what it needs.  No reading where the
program's ticks carry no ``kv_live``."""
from bench import flops, ticks


def pattern(s):
    """The decode attention's device ops, by result shape: the scores of
    each query group (f32 [slots, kv heads, positions]), the softmax's
    max and sum (f32 [slots, kv heads, group]), the probabilities (bf16
    [slots, 1, kv heads, group, positions]) and each group's product with
    v (bf16 [slots, kv heads, head_dim]); a group's ops fuse into one
    multi-output op whose result is a tuple."""
    hkv, hd = s["kv_heads"], s["head_dim"]
    g = s["heads"] // hkv
    return (rf"^%\S+ = \(?(?:f32\[\d+,{hkv},\d+\]|f32\[\d+,{hkv},{g}\]"
            rf"|bf16\[\d+,1,{hkv},{g},\d+\]|bf16\[\d+,{hkv},{hd}\])\{{")


def least_s(s, kv_live: int, active: int, peaks) -> float:
    hd = s["head_dim"]
    nbytes = 2.0 * s["layers"] * hd * (2 * s["kv_heads"] * kv_live
                                       + 2 * s["heads"] * active)
    work = s["layers"] * flops.attention_flops(s, kv_live)
    return flops.least_time(work, nbytes, peaks)[0]


def read(run):
    t = ticks.kernel_in_ticks(run, pattern(run.s))
    if t is None or t.device_s <= 0 or \
            any("kv_live" not in a for a in t.attrs):
        return None
    least = sum(least_s(run.s, a["kv_live"], a["active"], run.peaks)
                for a in t.attrs)
    return 100.0 * least / t.device_s
