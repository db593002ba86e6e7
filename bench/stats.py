"""End-to-end numbers from a run's record, by the host clock.

A percentile is the linear interpolation between order statistics (numpy's
default).  TTFT runs from when a request was due to the end of the step
that admitted it; a request due in the window with no first token by the
window's end counts with its wait so far.  An inter-token gap counts when
both its tokens came inside the window.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.driver import Record, token_stamps


def percentile(values, q: float) -> float:
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))


def due_in_window(rec: Record) -> List:
    return [r for r in rec.reqs.values() if rec.ws <= r.due < rec.we]


def ttft_s(rec: Record) -> List[float]:
    out = []
    for r in due_in_window(rec):
        first = (rec.steps[r.admit_step].end if r.admit_step is not None
                 else np.inf)
        out.append(min(first, rec.we) - r.due)
    return out


def itl_s(rec: Record) -> List[float]:
    """Gaps between a request's token deliveries.  Its first two tokens
    are delivered together, at the end of the admitting step, so the gap
    between them is no gap and is left out."""
    gaps = []
    for r in rec.reqs.values():
        t = token_stamps(rec, r)
        a, b = t[:-1], t[1:]
        if r.n >= 2 and len(a):
            a, b = a[1:], b[1:]
        keep = (a >= rec.ws) & (b <= rec.we)
        gaps.extend((b - a)[keep].tolist())
    return gaps


def window_tokens(rec: Record) -> int:
    n = 0
    for s in rec.steps:
        if rec.ws <= s.end <= rec.we:
            n += len(s.admitted) + s.active
    return n


def end_to_end(rec: Record) -> Dict[str, float]:
    seconds = rec.we - rec.ws
    ttft = ttft_s(rec)
    return {"tokens_per_s": window_tokens(rec) / seconds,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "itl_p95_ms": 1e3 * percentile(itl_s(rec), 95)}
