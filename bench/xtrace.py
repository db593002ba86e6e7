"""Reduce a profiler trace of the measured window to device numbers.

The window is the host span ``bench.window`` that the driver opens at the
window's start and closes at its end.  Device work is the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane; on a TPU an op event's name is its HLO
instruction (``%name = shape opcode(operands) ...``).  Busy time is the
union of the op intervals inside the window, averaged over the chips used.
A kernel's time is the sum of the durations of the ops whose name matches
its pattern.  An idle gap inside a program's execution (the ``XLA
Modules`` line) is named after that program; one between programs is
named after the innermost host span that covers its middle.

A window holds millions of op events, so they are kept as arrays of
start, end and an index into the table of distinct names.
"""
from __future__ import annotations

import array
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
HOST_SPAN = re.compile(r"^(bench\.|PjitFunction)")
CONTAINERS = {"while", "conditional", "call"}   # ops that hold other ops
OPCODE = re.compile(r" = .*?[\]\})] ([a-z][a-z0-9_-]*)\(")


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(iv: np.ndarray) -> Tuple[float, np.ndarray]:
    """Total length of a union of [start, end) intervals, and the merged
    intervals in order."""
    if len(iv) == 0:
        return 0.0, np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(iv) - 1]])
    merged = np.stack([iv[first, 0], reach[last]], 1)
    return float(np.sum(merged[:, 1] - merged[:, 0])), merged


def opcode(name: str) -> str:
    m = OPCODE.search(name)
    return m.group(1) if m else ""


def label(name: str, width: int = 120) -> str:
    """An op's instruction name, result shape and opcode, shortened."""
    m = OPCODE.search(name)
    return (name[:m.end() - 1] if m else name)[:width]


class _Line:
    """One trace line's events, clipped to [w0, w1)."""

    def __init__(self, events, w0, w1, names: Dict[str, int]):
        s, e, k = array.array("d"), array.array("d"), array.array("l")
        for ev in events:
            a = ev.start_ns
            b = a + ev.duration_ns
            if b <= w0 or a >= w1:
                continue
            i = names.get(ev.name)
            if i is None:
                i = names[ev.name] = len(names)
            s.append(max(a, w0))
            e.append(min(b, w1))
            k.append(i)
        self.start = np.frombuffer(s, np.float64)
        self.end = np.frombuffer(e, np.float64)
        self.key = np.frombuffer(k, np.int64)

    def intervals(self) -> np.ndarray:
        return np.stack([self.start, self.end], 1)


class Trace:
    """Device ops, programs and host spans of one trace, in its window."""

    def __init__(self, path: str, chips: int = 1):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        host_planes = [p for p in pd.planes if p.name.startswith("/host:")]
        self.window: Optional[Tuple[float, float]] = None
        for plane in host_planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        self.window = (ev.start_ns,
                                       ev.start_ns + ev.duration_ns)
        if self.window is None:
            raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
        w0, w1 = self.window
        self.window_s = (w1 - w0) * 1e-9
        self.host: List[Tuple[float, float, str]] = [
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in host_planes for line in plane.lines
            for ev in line.events
            if HOST_SPAN.match(ev.name) and ev.name != WINDOW_SPAN
            and ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0]
        self.names: Dict[str, int] = {}
        self.modules: Dict[str, int] = {}
        self.ops: Dict[str, _Line] = {}
        self.progs: Dict[str, _Line] = {}
        devices = sorted((p for p in pd.planes
                          if re.match(r"^/device:TPU:\d+$", p.name)),
                         key=lambda p: int(p.name.rsplit(":", 1)[1]))
        for plane in devices[:chips]:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    self.ops[plane.name] = _Line(line.events, w0, w1,
                                                 self.names)
                elif line.name == "XLA Modules":
                    self.progs[plane.name] = _Line(line.events, w0, w1,
                                                   self.modules)
        self.planes = sorted(self.ops)
        self._table = sorted(self.names, key=self.names.get)

    # ------------------------------------------------------------ busy
    def busy(self, plane: str) -> Tuple[float, np.ndarray]:
        return _union(self.ops[plane].intervals())

    @property
    def busy_s(self) -> float:
        if not self.planes:
            return 0.0
        return float(np.mean([self.busy(p)[0] for p in self.planes])) * 1e-9

    # ---------------------------------------------------------- kernels
    def _seconds_by_name(self) -> np.ndarray:
        tot = np.zeros(len(self._table))
        for p in self.planes:
            line = self.ops[p]
            tot += np.bincount(line.key, weights=line.end - line.start,
                               minlength=len(self._table))
        return tot * 1e-9

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``,
        summed over the chips used."""
        rx = re.compile(pattern)
        hit = [i for i, n in enumerate(self._table) if rx.search(n)]
        return float(self._seconds_by_name()[hit].sum()) if hit else 0.0

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took the most device time (ops that only hold
        other ops, such as a ``while`` or ``conditional``, left out)."""
        secs = self._seconds_by_name()
        order = [i for i in np.argsort(-secs)
                 if opcode(self._table[i]) not in CONTAINERS]
        return [[label(self._table[i]), float(secs[i])] for i in order[:n]]

    # -------------------------------------------------------- idle gaps
    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle device time in the window, by what held it: a program
        that was running (``in <program>``), or else the innermost host
        span covering the gap's middle (``host`` where none does)."""
        if not self.planes:
            return []
        p = self.planes[0]
        w0, w1 = self.window
        _, busy = self.busy(p)
        edges = np.concatenate([[w0], busy.ravel(), [w1]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        mids = gaps.mean(1)
        tot: Dict[str, float] = defaultdict(float)
        progs = self.progs.get(p)
        inside = np.zeros(len(gaps), bool)
        if progs is not None and len(progs.start):
            order = np.argsort(progs.start)
            ps, pe, pk = (progs.start[order], progs.end[order],
                          progs.key[order])
            j = np.searchsorted(ps, mids, "right") - 1
            ok = j >= 0
            inside[ok] = mids[ok] < pe[j[ok]]
            table = sorted(self.modules, key=self.modules.get)
            for g, jj in zip(np.flatnonzero(inside), j[inside]):
                tot["in " + table[pk[jj]].split("(")[0]] += \
                    gaps[g, 1] - gaps[g, 0]
        host = sorted(self.host)
        starts = np.asarray([h[0] for h in host])
        longest = max((he - hs for hs, he, _ in host), default=0.0)
        for (s, e), mid in zip(gaps[~inside], mids[~inside]):
            name, best = "host", None
            for k in range(np.searchsorted(starts, mid, "right") - 1, -1, -1):
                hs, he, hn = host[k]
                if mid - hs > longest:
                    break
                if hs <= mid < he and (best is None or he - hs < best):
                    name, best = hn, he - hs
            tot[name] += e - s
        return [[k, float(v) * 1e-9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
