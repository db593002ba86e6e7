"""One general generator for every traffic mix (``bench/traffic/*.json``).

A mix gives an open loop of Poisson arrivals at ``rate_per_s``,
bounded-Pareto prompt and output lengths, and a ladder that prompt
lengths are rounded up to.  The ladder (the set of prefill shapes) is the
same for every seed.

``--seed`` draws everything: every arrival gap, every length and every
prompt token.  The draws are stratified in blocks of ``block`` requests:
in each block, each of the gaps, the prompt lengths and the output lengths
takes one uniform draw from each of the ``block`` strata ``[i/block,
(i+1)/block)``, in an order drawn from the seed, through the law's inverse
CDF.  Each single draw is still uniform, so each gap is exponential and
each length follows its bounded Pareto over the whole range, up to the
bound; but every block holds its share of long and short requests, so the
seed changes which requests come and when, and hardly how much work
a window holds: the spread between seeds is then that of the order of
arrivals, not of the amount of work.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class Req:
    rid: int
    prompt: np.ndarray       # (P,) int32
    max_new_tokens: int
    due_s: float             # offset from the start of traffic


def bounded_pareto(u, lo: float, hi: float, alpha: float):
    """Inverse CDF of the Pareto(alpha) law bounded to [lo, hi]."""
    u = np.asarray(u, np.float64)
    ratio = (lo / hi) ** alpha
    return lo / (1.0 - u * (1.0 - ratio)) ** (1.0 / alpha)


def stratified(rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``n`` uniform draws on [0, 1), in blocks of ``k`` that each hold
    one draw from every stratum ``[i/k, (i+1)/k)``, in a drawn order."""
    return np.concatenate([(rng.permutation(k) + rng.random(k)) / k
                           for _ in range(n // k)])


def lengths(spec, u) -> np.ndarray:
    """The lengths a length spec gives at uniform draws ``u`` (whole
    tokens, rounded down), rounded up to its ladder where it has one."""
    x = np.floor(bounded_pareto(u, spec["lo"], spec["hi"],
                                spec["alpha"])).astype(np.int64)
    x = np.clip(x, spec["lo"], spec["hi"])
    ladder = spec.get("ladder")
    if ladder:
        rungs = np.asarray(sorted(ladder))
        x = rungs[np.searchsorted(rungs, x, side="left")]
    return x


def ladder(mix) -> List[int]:
    """Every prompt length a mix can send, the same for every seed."""
    return sorted(mix["prompt"]["ladder"])


def n_requests(mix, seconds: float) -> int:
    """Requests generated: enough for the lead-in and the window with room
    to spare, so no run can run out of traffic."""
    span = mix["lead_s"] + seconds
    return int(math.ceil(mix["rate_per_s"] * span * 1.5)) + 16


def generate(mix, vocab: int, seed: int, seconds: float) -> List[Req]:
    k = mix["block"]
    n = -(-n_requests(mix, seconds) // k) * k
    draw = np.random.default_rng([int(seed), 1])
    gaps = -np.log1p(-stratified(draw, n, k)) / mix["rate_per_s"]
    plen = lengths(mix["prompt"], stratified(draw, n, k))
    olen = lengths(mix["output"], stratified(draw, n, k))
    due = np.cumsum(gaps)
    tokens = np.random.default_rng(int(seed))
    return [Req(rid=i,
                prompt=tokens.integers(0, vocab, int(plen[i]),
                                       dtype=np.int32),
                max_new_tokens=int(olen[i]), due_s=float(due[i]))
            for i in range(n)]
