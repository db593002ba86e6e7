"""Per-step work in the window, for the per-layer metric readers.

A step's admissions are batch-1 prefills; its decode tick gives one token
to every active slot.  A request admitted at step ``a`` with prompt ``P``
decodes, at step ``a + j``, the token at position ``P + j``, which attends
``P + j + 1`` keys.
"""
from __future__ import annotations

from typing import Dict, List


def window_steps(run) -> List[int]:
    """Indices of the steps that started and ended inside the window."""
    rec = run.rec
    return [k for k, s in enumerate(rec.steps)
            if s.start >= rec.ws and s.end <= rec.we]


def traced_steps(run) -> List[int]:
    """Indices of the steps that started inside the window: the trace is
    clipped to the window, so their device work is (nearly all) in it."""
    rec = run.rec
    return [k for k, s in enumerate(rec.steps) if rec.ws <= s.start < rec.we]


def decode_contexts(run) -> Dict[int, List[int]]:
    """Step index -> the context length of each token its tick decoded."""
    out: Dict[int, List[int]] = {}
    rec = run.rec
    for r in rec.reqs.values():
        if r.admit_step is None or r.n < 2:
            continue
        for j in range(r.n - 1):
            k = r.admit_step + j
            if k >= len(rec.steps):
                break
            out.setdefault(k, []).append(r.prompt_len + j + 1)
    return out


def prefill_lengths(run, k: int) -> List[int]:
    return [run.rec.reqs[rid].prompt_len for rid in run.rec.steps[k].admitted]


def prefill_seconds(run, k: int) -> float:
    """A step's host-clock seconds outside its decode tick: admission."""
    s = run.rec.steps[k]
    return (s.end - s.start) - s.dt
