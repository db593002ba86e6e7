"""Drive one ``EngineSession`` through the lead-in and the measured window.

The loop submits each request when it is due, steps the engine while it
has work and sleeps until the next request is due when it has none.  It keeps,
for every step, its start and end on the host clock, the requests it
admitted (admission is FIFO in submission order) and the decode tick's
``dt``; for every request, when it was due, its step of admission and
whether it finished.  Host spans (``jax.profiler.TraceAnnotation``) name
what the host was doing, for the trace's idle gaps.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclass
class StepRec:
    start: float
    end: float
    admitted: List[int]
    dt: float                # the decode tick's own host-clock seconds
    active: int              # slots the tick decoded
    tick_sum: float = 0.0    # serve_decode_tick_seconds: this step's delta
    tick_count: int = 0


@dataclass
class ReqRec:
    rid: int
    prompt_len: int
    n: int                   # tokens the request asks for
    due: float               # host clock
    admit_step: Optional[int] = None
    finish_step: Optional[int] = None


@dataclass
class Record:
    t0: float                # start of traffic
    ws: float                # window start
    we: float                # window end
    steps: List[StepRec] = field(default_factory=list)
    reqs: Dict[int, ReqRec] = field(default_factory=dict)
    completions: Dict[int, object] = field(default_factory=dict)
    late_s: List[float] = field(default_factory=list)   # submit - due


def tick_histogram():
    """(sum, count) of the program's ``serve_decode_tick_seconds``."""
    from repro.obs import metrics
    fam = metrics.registry().families.get("serve_decode_tick_seconds")
    if fam is None:
        return 0.0, 0
    return (sum(h.sum for h in fam.samples.values()),
            sum(h.count for h in fam.samples.values()))


def no_span(name):
    return contextlib.nullcontext()


def drive(engine, mix, reqs, seconds: float, *,
          span: Callable = no_span,
          before_window: Optional[Callable] = None,
          at_window_start: Optional[Callable] = None) -> Record:
    from repro.serve import Request

    sess = engine.session()
    clock = time.perf_counter
    t0 = clock()
    rec = Record(t0=t0, ws=t0 + mix["lead_s"], we=t0 + mix["lead_s"]
                 + seconds)
    fifo: collections.deque = collections.deque()
    pending = collections.deque(sorted(reqs, key=lambda r: r.due_s))
    prepared = started = False

    def submit(r, due):
        now = clock()
        sess.submit(Request(rid=r.rid, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens))
        fifo.append(r.rid)
        rec.reqs[r.rid] = ReqRec(r.rid, len(r.prompt), r.max_new_tokens,
                                 due)
        if rec.ws <= due < rec.we:
            rec.late_s.append(now - due)

    while True:
        now = clock()
        if not prepared and before_window and now >= rec.ws - 2.0:
            before_window()
            prepared = True
            continue
        if not started and now >= rec.ws:
            started = True
            if at_window_start:
                at_window_start()
        if now >= rec.we:
            break
        with span("bench.submit"):
            while pending and t0 + pending[0].due_s <= now:
                r = pending.popleft()
                submit(r, t0 + r.due_s)
        if not sess.pending():
            wake = t0 + pending[0].due_s if pending else rec.we
            if not started:
                wake = min(wake, rec.ws)
            if not prepared and before_window:
                wake = min(wake, rec.ws - 2.0)
            with span("bench.idle"):
                time.sleep(max(0.0, min(wake, rec.we) - clock()))
            continue
        a0 = sess.stats["admitted"]
        h0 = tick_histogram()
        with span("bench.step"):
            s = clock()
            tick = sess.step()
            e = clock()
        h1 = tick_histogram()
        admitted = [fifo.popleft()
                    for _ in range(sess.stats["admitted"] - a0)]
        k = len(rec.steps)
        for rid in admitted:
            rec.reqs[rid].admit_step = k
        rec.steps.append(StepRec(s, e, admitted, tick["dt"], tick["active"],
                                 h1[0] - h0[0], h1[1] - h0[1]))
        with span("bench.poll"):
            done = sess.poll()
        for c in done:
            rec.completions[c.rid] = c
            rec.reqs[c.rid].finish_step = k
    sess.close()
    return rec


def token_stamps(rec: Record, r: ReqRec) -> np.ndarray:
    """Host-clock times of request ``r``'s tokens.  The admitting step
    makes the first token (prefill) and, in its decode tick, the second;
    each later step makes one more, until the budget is spent."""
    if r.admit_step is None:
        return np.zeros((0,))
    last = min(r.admit_step + max(r.n - 2, 0), len(rec.steps) - 1)
    ends = [rec.steps[k].end for k in range(r.admit_step, last + 1)]
    if r.n >= 2:
        ends = ends[:1] + ends
    return np.asarray(ends[:r.n])
