"""The program's timed spans (``repro.obs.trace.span``), for the per-layer
metric readers.

Spans carry ``time.perf_counter`` stamps, the clock of ``driver.Record``;
the profiler trace has a clock of its own.  ``clock_fit`` maps the one
onto the other by least squares: a line through the pairs of each
``bench.step`` host span's start in the trace and the ``StepRec.start``
stamped just inside it.  Device idle time inside a kind of span is the
overlap of the device's idle intervals (the window less the union of its
op intervals) with those spans, mapped onto the trace's clock.

A device's lines in the trace can stop before the window does, while the
host's run on to its end: on a v5e the chat cell's ``XLA Ops`` and ``XLA
Modules`` stop some 34.6 s into the 51 s window, after ~4.6 M op events.
So the readers of the device trace take the part of the window that a
line covers, from the window's start to the end of its last event.

A reader reads nothing (None) where the program keeps no timed spans,
where its ring no longer reaches back to the window's start, or, for a
reader of the device trace, where the fit's 95th-percentile residual is
over 100 us.
"""
from __future__ import annotations

from collections import defaultdict
from typing import List, NamedTuple, Optional

import numpy as np

from bench import xtrace

STEP_SPAN = "bench.step"
MAX_RESIDUAL_NS = 100e3


class Fit(NamedTuple):
    offset_ns: float          # trace clock at perf_counter 0
    rate: float               # trace ns per perf_counter second
    residual_p95_ns: float
    pairs: int

    def to_trace(self, t) -> np.ndarray:
        return self.offset_ns + self.rate * np.asarray(t, np.float64)


def window_spans(run) -> Optional[List]:
    """The program's spans that overlap ``[rec.ws, rec.we)``; None when
    the program keeps none or the ring no longer reaches ``rec.ws``."""
    from repro.obs import trace
    recent = getattr(trace, "recent_spans", None)
    if recent is None:
        return None
    spans, whole = recent(run.rec.ws, run.rec.we)
    return list(spans) if whole else None


def fit_line(stamps_s, trace_ns) -> Fit:
    """Least squares ``trace_ns ~ offset + rate * stamps_s``."""
    x = np.asarray(stamps_s, np.float64)
    y = np.asarray(trace_ns, np.float64)
    xm, ym = x.mean(), y.mean()
    rate = float(np.sum((x - xm) * (y - ym)) / np.sum((x - xm) ** 2))
    offset = float(ym - rate * xm)
    res = np.abs(y - (offset + rate * x))
    return Fit(offset, rate, float(np.percentile(res, 95)), len(x))


def clock_fit(run) -> Optional[Fit]:
    """The perf_counter -> trace map of a traced run.  The trace keeps the
    steps that overlap its window, which are the last of the run's (the
    window opens between two steps and closes after the last)."""
    if run.trace is None:
        return None
    traced = sorted(h[0] for h in run.trace.host if h[2] == STEP_SPAN)
    n = len(traced)
    if n < 2 or len(run.rec.steps) < n:
        return None
    return fit_line([s.start for s in run.rec.steps[-n:]], traced)


def on_trace(run, name: str) -> Optional[np.ndarray]:
    """The window's spans named ``name`` as [start, end) rows on the
    trace's clock (ns), or None."""
    if run.trace is None or not run.trace.planes:
        return None
    spans = window_spans(run)
    fit = clock_fit(run)
    if spans is None or fit is None or fit.residual_p95_ns > MAX_RESIDUAL_NS:
        return None
    t = np.asarray([(s.start, s.end) for s in spans if s.name == name],
                   np.float64).reshape(-1, 2)
    return fit.to_trace(t)


def overlap_ns(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two unions of intervals."""
    la, ma = xtrace._union(a.reshape(-1, 2))
    lb, mb = xtrace._union(b.reshape(-1, 2))
    lu, _ = xtrace._union(np.concatenate([ma, mb]))
    return la + lb - lu


def covered_ns(line, w0: float) -> float:
    """End of the part of the window a trace line covers: its last
    event's end (``w0`` when it has none)."""
    return float(line.end.max()) if len(line.end) else w0


def idle_ns(tr, plane: str) -> np.ndarray:
    """The device's idle intervals in the covered window: its complement
    of the union of the plane's op intervals."""
    w0 = tr.window[0]
    _, busy = tr.busy(plane)
    end = covered_ns(tr.ops[plane], w0)
    edges = np.concatenate([[w0], busy.ravel(), [end]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def idle_share(tr, intervals_ns: np.ndarray) -> Optional[float]:
    """Share (%) of the covered window in which the device idled inside
    the intervals, averaged over the chips used."""
    w0 = tr.window[0]
    shares = [overlap_ns(idle_ns(tr, p), intervals_ns)
              / (covered_ns(tr.ops[p], w0) - w0)
              for p in tr.planes if covered_ns(tr.ops[p], w0) > w0]
    return 100.0 * float(np.mean(shares)) if shares else None


def host_ms(run, name: str, wait: str) -> Optional[float]:
    """Mean host time of the window's ``name`` spans outside their
    ``wait`` children, in ms."""
    spans = window_spans(run)
    if spans is None:
        return None
    rec = run.rec
    outer = [s for s in spans
             if s.name == name and s.start >= rec.ws and s.end <= rec.we]
    if not outer:
        return None
    waited = defaultdict(float)
    for s in spans:
        if s.name == wait:
            waited[s.parent] += s.end - s.start
    return 1e3 * sum(s.end - s.start - waited[s.id] for s in outer) \
        / len(outer)
