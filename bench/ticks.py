"""Device time of a kernel's ops inside the program's decode ticks, for
the readers of the decode kernels' rooflines.

A tick is the program's ``serve.tick`` span (``bench/spans.py`` maps it
onto the trace's clock).  An op belongs to the tick its start falls in;
ops outside every tick (prefills, eager work between programs) are left
out.  Only ticks that lie wholly inside the part of the window the
device's op line covers are taken, so a kernel's time and the ticks'
least time cover the same ticks.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from bench import spans

TICK_SPAN = "serve.tick"


class Ticks(NamedTuple):
    device_s: float               # the matching ops' seconds in the ticks
    attrs: List[Dict]             # each covered tick's span attributes


def kernel_in_ticks(run, pattern: str) -> Optional[Ticks]:
    """The ops matching ``pattern`` inside the covered ticks, on the first
    chip's op line; None where the program's spans cannot be placed on
    the trace or no tick is covered."""
    iv = spans.on_trace(run, TICK_SPAN)
    if iv is None or not len(iv):
        return None
    attrs = [s.attrs for s in spans.window_spans(run) if s.name == TICK_SPAN]
    order = np.argsort(iv[:, 0])
    tr = run.trace
    line = tr.ops[tr.planes[0]]
    w0 = tr.window[0]
    keep = order[(iv[order, 0] >= w0)
                 & (iv[order, 1] <= spans.covered_ns(line, w0))]
    if not len(keep):
        return None
    iv = iv[keep]
    rx = re.compile(pattern)
    names = sorted(tr.names, key=tr.names.get)
    hit = np.asarray([bool(rx.search(n)) for n in names], bool)
    ops = hit[line.key] if len(line.key) else np.zeros(0, bool)
    start = line.start[ops]
    j = np.searchsorted(iv[:, 0], start, "right") - 1
    inside = (j >= 0) & (start < iv[np.maximum(j, 0), 1])
    device_ns = float(np.sum((line.end[ops] - start)[inside]))
    return Ticks(device_ns * 1e-9, [attrs[i] for i in keep])
