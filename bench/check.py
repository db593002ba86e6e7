"""What decides ``correct``: served tokens against the float32 reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one
that served the most tokens, goes through the reference once, over each
prompt followed by its served tokens.  The number compared is the widest
gap by which a served (greedy) token's reference logit lies below the
reference's best at its position.  The limit is the cell's own
(``bench/limits/<workload>.json``) and was set from sound runs of the
program and from the fp8 control (``calibrate.py``).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference

SAMPLE_SALT = 0x5EED


def sample(completions: Dict[int, object], mix, seed: int) -> List[int]:
    """Finished requests to check: the one with the most served tokens,
    then others in an order drawn from the seed, until the sample holds
    ``check.min_tokens`` served tokens or ``check.max_requests`` requests."""
    want = mix["check"]
    done = sorted(completions.values(),
                  key=lambda c: (-len(c.tokens), -c.prompt_len, c.rid))
    if not done:
        return []
    rest = done[1:]
    order = np.random.default_rng([int(seed), SAMPLE_SALT]).permutation(
        len(rest))
    picked, n = [done[0].rid], len(done[0].tokens)
    for i in order:
        if n >= want["min_tokens"] or len(picked) >= want["max_requests"]:
            break
        picked.append(rest[i].rid)
        n += len(rest[i].tokens)
    return picked


def served_gaps(conf, seed: int, seqs) -> List[np.ndarray]:
    """Per sequence, the gap of each served token."""
    ref = reference.logits(conf, seed, seqs)
    return [reference.gaps(r, served) for r, (_, served) in zip(ref, seqs)]


def control_gaps(conf, seed: int, seqs) -> List[np.ndarray]:
    """The fp8 control in the program's place: per sequence, the gap of
    the token the fp8 forward puts first, at each served position."""
    ref = reference.logits(conf, seed, seqs)
    low = reference.logits(conf, seed, seqs, mode="fp8")
    return [reference.gaps(r, np.asarray(np.argmax(lo, -1)))
            for r, lo in zip(ref, low)]


def verdict(per_seq: List[np.ndarray], limit: float):
    """(correct, widest gap, requests over the limit)."""
    if not per_seq:
        return False, float("inf"), 0
    widest = [float(np.max(g)) for g in per_seq]
    bad = sum(w > limit for w in widest)
    return bad == 0, max(widest), bad
