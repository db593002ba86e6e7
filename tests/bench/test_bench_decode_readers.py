"""The readers of the decode kernels' rooflines (``bench/ticks.py``,
``decode_attn_roofline``, ``swiglu_roofline.decode``) on a synthetic
trace: they take the kernel's ops that start inside the program's
``serve.tick`` spans and no other op, over the ticks the device's op line
covers, and read nothing where the ticks carry no ``kv_live``."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops, model, peaks, spec, ticks
from repro.obs import trace as obs_trace

PLANE = "/device:TPU:0"
S = model.sizes(spec.load_json(
    f"{spec.ROOT}/bench/configs/mistral-nemo-12b-l10.json"))
V5E = peaks.CHIP_PEAKS["TPU v5 lite"]
ATTN = ("%fusion.45 = (f32[12,8,4352]{2,1,0:T(8,128)S(1)}, f32[12,8,4352]"
        "{2,1,0:T(8,128)S(1)}) fusion(bf16[12,8,4,128]{3,2,1,0} %q)")
SWIGLU = ("%swiglu.11 = bf16[12,8,5120]{2,1,0:T(8,128)(2,1)S(1)} "
          "custom-call(bf16[12,8,5120]{2,1,0} %pad.0)")
READS_SWIGLU = ("%slice.260 = bf16[12,1,5120]{2,1,0} slice(bf16[12,8,5120]"
                "{2,1,0} %swiglu.11)")
WEIGHTS = ("%slice_bitcast_fusion.8 = bf16[1024,5120]{0,1:T(8,128)(2,1)} "
           "fusion(bf16[10,5120,1024]{2,1,0} %wk)")
PREFILL = ("%swiglu.3 = bf16[4096,5120]{1,0:T(8,128)(2,1)} "
           "custom-call(bf16[4096,5120]{1,0} %x)")
# (name, start and end inside a 50 ms step, in seconds)
TICK_OPS = [(ATTN, 0.015, 0.017), (SWIGLU, 0.018, 0.023),
            (READS_SWIGLU, 0.023, 0.024), (WEIGHTS, 0.025, 0.026)]
ADMIT_OPS = [(PREFILL, 0.002, 0.010)]


def synthetic(n=30, cut=None, kv_live=True):
    """``n`` steps of 50 ms; every step ticks ``active`` slots, even steps
    admit first.  The trace clock is an affine map of perf_counter; with
    ``cut`` the device's op line stops after that share of the window."""
    offset, rate = -2.5e11, 1e9 * (1 - 25e-6)
    t0, made, host, ops = 1000.0, [], [], []
    names = {}
    for k in range(n):
        s = t0 + 0.05 * k
        host.append((offset + rate * s - 800, offset + rate * (s + 0.045),
                     "bench.step"))
        attrs = {"step": k, "active": 1 + k % 12}
        if kv_live:
            attrs["kv_live"] = 4100 * attrs["active"] + k
        made.append(obs_trace.TimedSpan(2 * k, "serve.tick", s + 0.013,
                                        s + 0.044, -1, attrs))
        for name, a, b in TICK_OPS + (ADMIT_OPS if k % 2 == 0 else []):
            ops.append((s + a, s + b, names.setdefault(name, len(names))))
    ops = np.asarray(ops, np.float64)
    start, end = offset + rate * ops[:, 0], offset + rate * ops[:, 1]
    ws, we = t0 - 0.001, t0 + 0.05 * n
    window = (offset + rate * ws, offset + rate * we)
    keep = np.ones(len(ops), bool)
    if cut is not None:
        keep = start < window[0] + cut * (window[1] - window[0])
    line = SimpleNamespace(start=start[keep], end=end[keep],
                           key=ops[keep, 2].astype(np.int64))
    tr = SimpleNamespace(window=window, planes=[PLANE], ops={PLANE: line},
                         names=names, host=host)
    steps = [SimpleNamespace(start=t0 + 0.05 * k) for k in range(n)]
    run = SimpleNamespace(trace=tr, s=S, peaks=V5E, rec=SimpleNamespace(
        steps=steps, ws=ws, we=we))
    # the ticks that end before the op line's last op does
    covered = [sp.attrs for sp in made
               if offset + rate * sp.end <= line.end.max()]
    return run, made, covered


@pytest.fixture()
def serve(monkeypatch):
    def use(made):
        monkeypatch.setattr(obs_trace, "recent_spans", lambda t0, t1: (
            tuple(sp for sp in made if sp.end > t0 and sp.start < t1),
            True))
    return use


@pytest.mark.parametrize("cut", [None, 0.5])
def test_readers_take_the_kernels_ops_inside_covered_ticks(serve, cut):
    run, made, covered = synthetic(cut=cut)
    serve(made)
    assert 0 < len(covered) < len(made)
    swiglu = spec.metric_reader("swiglu_roofline.decode")(run)
    least = sum(flops.least_time(*flops.swiglu_call(S, a["active"]), V5E)[0]
                for a in covered)
    assert swiglu == pytest.approx(
        100 * S["layers"] * least / (0.005 * len(covered)), rel=1e-4)
    attn = spec.metric_reader("decode_attn_roofline")
    least = sum(attn.__globals__["least_s"](S, a["kv_live"], a["active"],
                                            V5E) for a in covered)
    assert attn(run) == pytest.approx(
        100 * least / (0.002 * len(covered)), rel=1e-4)


def test_attention_least_time_reads_the_live_rows():
    least_s = spec.metric_reader("decode_attn_roofline").__globals__[
        "least_s"]
    # 10 layers of k and v rows (8 kv heads x 128, bf16) for 4097
    # positions, and q and o (32 heads x 128) of one token
    nbytes = 10 * 2 * 128 * (2 * 8 * 4097 + 2 * 32)
    assert least_s(S, 4097, 1, V5E) == pytest.approx(nbytes / 819e9)


def test_no_kv_live_no_reading(serve):
    """The spans of a program that does not count ``kv_live``: the
    attention reader reads nothing, and does not raise."""
    run, made, _ = synthetic(kv_live=False)
    serve(made)
    assert spec.metric_reader("decode_attn_roofline")(run) is None
    assert ticks.kernel_in_ticks(run, r"^no such op$").device_s == 0.0


def test_no_trace_no_reading():
    run, _, _ = synthetic()
    run.trace = None
    assert ticks.kernel_in_ticks(run, "swiglu") is None
    assert spec.metric_reader("swiglu_roofline.decode")(run) is None
