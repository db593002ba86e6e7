"""The readers of the program's timed spans (``bench/spans.py``): the fit
of the host clock onto the profiler's, on the recorded TPU trace, and the
split of the device's idle time between the engine's spans, on a synthetic
trace."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spans, spec, xtrace
from repro.obs import trace as obs_trace

TRACE = os.path.join(spec.BENCH, "testdata", "tpu_window.xplane.pb")
PLANE = "/device:TPU:0"


def _run(tr, stamps, ws=-np.inf, we=np.inf):
    steps = [SimpleNamespace(start=float(t)) for t in stamps]
    return SimpleNamespace(trace=tr, rec=SimpleNamespace(steps=steps, ws=ws,
                                                         we=we))


def test_clock_fit_recovers_an_affine_map():
    """perf_counter stamps made from the recorded ``bench.step`` spans
    through a known map, with 2 us of jitter and lead-in steps before the
    trace's: the fit pairs the last steps with the trace's and finds the
    map."""
    tr = xtrace.Trace(TRACE)
    traced = np.asarray(sorted(h[0] for h in tr.host
                               if h[2] == spans.STEP_SPAN))
    assert len(traced) >= 10
    offset, rate = 3.7e12, 1e9 * (1 + 40e-6)     # 40 ppm of drift
    rng = np.random.default_rng(0)
    stamps = (traced - offset) / rate + rng.uniform(-2e-6, 2e-6,
                                                    len(traced))
    lead = stamps[0] - 0.05 * np.arange(5, 0, -1)
    fit = spans.clock_fit(_run(tr, np.concatenate([lead, stamps])))
    assert fit.pairs == len(traced)
    assert fit.rate == pytest.approx(rate, rel=2e-5)
    assert np.abs(fit.to_trace(stamps) - traced).max() < 5e3     # ns
    assert fit.residual_p95_ns < spans.MAX_RESIDUAL_NS
    # a record that ends a step before the trace leaves the pairs out of
    # line: the fit shows it, and the readers of the device trace read
    # nothing
    fit = spans.clock_fit(_run(tr, np.concatenate([lead, stamps[:-1]])))
    assert fit.residual_p95_ns > spans.MAX_RESIDUAL_NS


class FakeTrace:
    """The part of ``xtrace.Trace`` the readers use, built from lists."""

    def __init__(self, window, ops, host, modules):
        self.window = window
        self.window_s = (window[1] - window[0]) * 1e-9
        self.planes = [PLANE]
        ops = np.clip(np.asarray(ops, np.float64), *window)
        self.ops = {PLANE: SimpleNamespace(start=ops[:, 0], end=ops[:, 1])}
        self.host = host
        modules = np.asarray(modules, np.float64)
        self.progs = {PLANE: SimpleNamespace(start=modules[:, 0],
                                             end=modules[:, 1])}

    def busy(self, plane):
        return xtrace._union(np.stack([self.ops[plane].start,
                                       self.ops[plane].end], 1))

    @property
    def busy_s(self):
        return self.busy(PLANE)[0] * 1e-9


def _synthetic(n=40, seed=1, cut=None):
    """``n`` engine steps of 50 ms: even steps admit one request, every
    step ticks; the device works in the waits and at random elsewhere,
    up to the window's end or, with ``cut``, only through that share of
    the window (a trace whose device lines stop early).  Returns the run
    and its spans."""
    rng = np.random.default_rng(seed)
    offset, rate = -2.5e11, 1e9 * (1 - 25e-6)
    t0 = 1000.0
    made, ids = [], iter(range(10 ** 6))

    def add(name, a, b, parent=-1, **attrs):
        made.append(obs_trace.TimedSpan(next(ids), name, a, b, parent,
                                        attrs))
        return made[-1].id

    stamps, ops, host = [], [], []
    for k in range(n):
        s = t0 + 0.05 * k
        stamps.append(s)
        host.append((offset + rate * s - 800, offset + rate * (s + 0.045),
                     "bench.step"))
        step = add("serve.step", s, s + 0.045, step=k)
        if k % 2 == 0:
            adm = add("serve.admit", s + 0.001, s + 0.012, step, rid=k)
            add("serve.admit.wait", s + 0.008, s + 0.012, adm)
            ops.append((s + 0.003, s + 0.011))
        tick = add("serve.tick", s + 0.013, s + 0.044, step, step=k)
        add("serve.tick.wait", s + 0.018, s + 0.040, tick)
        ops.append((s + 0.017, s + 0.039))
        for a in rng.uniform(s, s + 0.05, 3):
            ops.append((a, a + rng.uniform(1e-4, 3e-3)))
    ws, we = t0 + 0.02, t0 + 0.05 * n - 0.01
    ops.append((we - 0.002, we + 0.01))     # busy to the window's end
    ops = offset + rate * np.asarray(ops)
    window = (offset + rate * (ws + 0.003), offset + rate * (we + 0.004))
    starts = np.sort(rng.uniform(*window, 7 * n))
    modules = np.stack([starts, starts + 1e5], 1)
    if cut is not None:
        end = window[0] + cut * (window[1] - window[0])
        ops = np.minimum(ops[ops[:, 0] < end], end)
        modules = np.minimum(modules[modules[:, 0] < end], end)
    tr = FakeTrace(window, ops, host, modules)
    run = _run(tr, stamps, ws, we)
    return run, made


def _serve(monkeypatch, made):
    monkeypatch.setattr(obs_trace, "recent_spans", lambda t0, t1: (
        tuple(s for s in made if s.end > t0 and s.start < t1), True))


@pytest.fixture()
def synthetic(monkeypatch):
    run, made = _synthetic()
    _serve(monkeypatch, made)
    return run


def _idle_outside(run, names, end):
    """Idle share (%) of [window start, end) outside the named spans."""
    tr = run.trace
    w0 = tr.window[0]
    _, merged = xtrace._union(np.concatenate(
        [spans.on_trace(run, n) for n in names]))
    edges = np.concatenate([[w0], np.clip(merged, w0, end).ravel(), [end]])
    outside = edges.reshape(-1, 2)
    outside = outside[outside[:, 1] > outside[:, 0]]
    idle = spans.overlap_ns(spans.idle_ns(tr, PLANE), outside)
    return 100.0 * idle / (end - w0)


def test_idle_shares_split_the_device_idle(synthetic):
    """idle.tick + idle.admit + the idle outside both spans is the whole
    of ``device_idle_share``."""
    run = synthetic
    tick = spec.metric_reader("idle.tick")(run)
    admit = spec.metric_reader("idle.admit")(run)
    total = spec.metric_reader("device_idle_share")(run)
    assert tick > 0 and admit > 0
    rest = _idle_outside(run, ["serve.tick", "serve.admit"],
                         run.trace.window[1])
    assert tick + admit + rest == pytest.approx(total, rel=1e-9)
    assert tick + admit <= total


def test_device_readers_take_the_covered_window(monkeypatch):
    """Device lines that stop at 60% of the window: ``device_idle_share``
    counts the rest as idle, the span readers divide the covered part
    only, and their split of it still adds up."""
    run, made = _synthetic(cut=0.6)
    _serve(monkeypatch, made)
    tr = run.trace
    w0, w1 = tr.window
    end = tr.ops[PLANE].end.max()
    assert end == pytest.approx(w0 + 0.6 * (w1 - w0))
    covered = 100.0 * (1 - tr.busy_s * 1e9 / (end - w0))
    total = spec.metric_reader("device_idle_share")(run)
    assert total == pytest.approx(0.6 * covered + 40.0)  # the rest: idle
    tick = spec.metric_reader("idle.tick")(run)
    admit = spec.metric_reader("idle.admit")(run)
    rest = _idle_outside(run, ["serve.tick", "serve.admit"], end)
    assert tick + admit + rest == pytest.approx(covered, rel=1e-9)
    steps = spans.on_trace(run, "serve.step")
    inside = int(((steps[:, 0] < end) & (steps[:, 1] > w0)).sum())
    assert inside < 30
    assert spec.metric_reader("programs_per_step")(run) == pytest.approx(
        len(tr.progs[PLANE].start) / inside)


def test_host_times_and_programs_per_step(synthetic):
    run = synthetic
    # ticks of 31 ms less a 22 ms wait; admissions of 11 ms less 4 ms
    assert spec.metric_reader("tick_host_ms")(run) == pytest.approx(9.0)
    assert spec.metric_reader("admit_host_ms")(run) == pytest.approx(7.0)
    w0, w1 = run.trace.window
    steps = spans.on_trace(run, "serve.step")
    inside = ((steps[:, 0] < w1) & (steps[:, 1] > w0)).sum()
    assert inside == 40
    assert spec.metric_reader("programs_per_step")(run) == pytest.approx(7.0)


@pytest.mark.parametrize("fault", ["no_ring", "ring_short", "bad_fit"])
def test_readers_read_nothing_without_whole_spans(synthetic, monkeypatch,
                                                  fault):
    """A program without timed spans (as before they existed), a ring that
    no longer reaches the window's start, or clocks that do not fit: every
    span reader gives None and none raises."""
    run = synthetic
    if fault == "no_ring":
        monkeypatch.delattr(obs_trace, "recent_spans")
    elif fault == "ring_short":
        monkeypatch.setattr(obs_trace, "recent_spans",
                            lambda t0, t1: ((), False))
    else:
        run.trace.host = [(a + 5e5 * (i % 2), b, name)
                          for i, (a, b, name) in enumerate(run.trace.host)]
    names = ["idle.tick", "idle.admit", "programs_per_step"]
    if fault != "bad_fit":     # the host-clock readers need no fit
        names += ["tick_host_ms", "admit_host_ms"]
    for name in names:
        assert spec.metric_reader(name)(run) is None, name
