"""The retrieval-augmented QA mix (``bench/traffic/rag-4k.json``): every
prompt fills the same context budget, so a seed changes only the arrival
gaps, the output lengths and the tokens, never how many requests a run
generates or how long their prompts are."""
import numpy as np
import pytest

from bench import spec, traffic

SEEDS = (1, 7, 2 ** 31 + 12345, 2 ** 33 + 5)
SECONDS = 51


@pytest.fixture(scope="module")
def runs():
    m = spec.load_json(f"{spec.BENCH}/traffic/rag-4k.json")
    return m, [traffic.generate(m, 131072, s, SECONDS) for s in SEEDS]


def test_every_seed_the_same_prefill_work(runs):
    m, reqs = runs
    assert len({len(r) for r in reqs}) == 1
    for rs in reqs:
        assert {len(r.prompt) for r in rs} == {m["prompt"]["lo"]} == \
            set(traffic.ladder(m))
        assert all(len(r.prompt) + r.max_new_tokens <= m["max_len"]
                   for r in rs)


def test_seed_draws_gaps_outputs_and_tokens(runs):
    m, reqs = runs
    for a, b in zip(reqs, reqs[1:]):
        assert [r.due_s for r in a] != [r.due_s for r in b]
        assert [r.max_new_tokens for r in a] != \
            [r.max_new_tokens for r in b]
        assert not np.array_equal(a[0].prompt, b[0].prompt)
    outs = np.asarray([r.max_new_tokens for rs in reqs for r in rs])
    assert outs.min() >= m["output"]["lo"] and outs.max() <= m["output"]["hi"]
