"""The benchmark's traffic generator: the same seed gives the same
requests, another seed other requests from the same laws, and every seed
the same prompt ladder."""
import numpy as np
import pytest

from bench import spec, traffic

BIG_SEED = 2 ** 31 + 12345


def mix(name="chat"):
    return spec.load_json(f"{spec.BENCH}/traffic/{name}.json")


def key(reqs):
    return [(r.rid, r.prompt.tobytes(), r.max_new_tokens, r.due_s)
            for r in reqs]


def many(m, seeds, seconds=40):
    return [r for s in seeds for r in traffic.generate(m, 1000, s, seconds)]


def test_same_seed_same_requests():
    m = mix()
    a = traffic.generate(m, 1000, BIG_SEED, 40)
    b = traffic.generate(m, 1000, BIG_SEED, 40)
    assert key(a) == key(b)
    assert key(a) != key(traffic.generate(m, 1000, BIG_SEED + 1, 40))


def test_seed_draws_lengths_and_arrivals():
    m = mix()
    runs = [traffic.generate(m, 1000, s, 40) for s in (1, 7, BIG_SEED)]
    for a, b in ((runs[0], runs[1]), (runs[1], runs[2])):
        assert [r.max_new_tokens for r in a] != \
            [r.max_new_tokens for r in b]
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
        assert [r.due_s for r in a] != [r.due_s for r in b]


def test_every_seed_same_ladder():
    m = mix()
    for s in (1, 7, BIG_SEED):
        assert {len(r.prompt) for r in traffic.generate(m, 1000, s, 40)} \
            <= set(traffic.ladder(m))
    # across seeds every rung is sent
    assert {len(r.prompt) for r in many(m, range(20))} == \
        set(traffic.ladder(m))


def test_requests_fit_the_slots():
    m = mix()
    for r in many(m, range(10)):
        assert len(r.prompt) + r.max_new_tokens <= m["max_len"]
        assert m["prompt"]["lo"] <= len(r.prompt) <= m["prompt"]["hi"]
        assert m["output"]["lo"] <= r.max_new_tokens <= m["output"]["hi"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000


def test_open_loop_rate_and_span():
    m = mix()
    reqs = traffic.generate(m, 1000, 5, 40)
    due = np.asarray([r.due_s for r in reqs])
    assert due[0] >= 0.0 and np.all(np.diff(due) >= 0)
    # the mean gap is 1/rate within the stratified draws' spread, and
    # there is traffic past the window
    assert due[-1] / len(reqs) == pytest.approx(1 / m["rate_per_s"],
                                                rel=0.1)
    assert due[-1] > m["lead_s"] + 40


def test_bounded_pareto_quantiles():
    x = traffic.bounded_pareto([0.0, 1.0], 32, 640, 1.2)
    np.testing.assert_allclose(x, [32, 640])
    spec_ = {"lo": 32, "hi": 640, "alpha": 1.2, "ladder": [32, 100, 640]}
    lens = traffic.lengths(spec_, (np.arange(500) + 0.5) / 500)
    assert set(lens) <= {32, 100, 640} and lens.min() == 32


def test_every_block_holds_the_same_work():
    """Every block holds one output length from each stratum of the law,
    so no block holds more than its share of long requests."""
    m = mix()
    reqs = traffic.generate(m, 1000, BIG_SEED, 40)
    k, o = m["block"], m["output"]
    assert len(reqs) % k == 0
    edges = np.floor(traffic.bounded_pareto(np.arange(k + 1) / k, o["lo"],
                                            o["hi"], o["alpha"]))
    for i in range(0, len(reqs), k):
        out = sorted(r.max_new_tokens for r in reqs[i:i + k])
        assert all(edges[j] <= x <= edges[j + 1]
                   for j, x in enumerate(out))


@pytest.mark.parametrize("k", [1, 16, 64])
def test_stratified_one_draw_per_stratum(k):
    u = traffic.stratified(np.random.default_rng(3), 4 * k, k)
    assert u.shape == (4 * k,) and u.min() >= 0.0 and u.max() < 1.0
    for b in u.reshape(4, k):
        assert sorted(np.floor(b * k).astype(int)) == list(range(k))
    # the order within a block is drawn
    if k > 1:
        assert any(list(np.argsort(b)) != list(range(k))
                   for b in u.reshape(4, k))


@pytest.mark.parametrize("part", ["prompt", "output"])
def test_lengths_follow_the_law_to_its_bound(part):
    """Each draw is uniform, so the lengths follow the bounded Pareto (as
    rounded), tail included: the longest reach the bound."""
    m = mix()
    got = np.asarray([len(r.prompt) if part == "prompt" else
                      r.max_new_tokens for r in many(m, range(40))])
    law = m[part]
    ref = traffic.lengths(law, (np.arange(20000) + 0.5) / 20000)
    for q in (50, 90, 99):
        assert np.percentile(got, q) == pytest.approx(
            np.percentile(ref, q), rel=0.1)
    assert got.max() >= 0.9 * law["hi"]


def test_gaps_are_exponential():
    m = mix()
    gaps = np.concatenate([np.diff([0.0] + [r.due_s for r in
                                            traffic.generate(m, 1000, s, 40)])
                           for s in range(20)]) * m["rate_per_s"]
    assert gaps.mean() == pytest.approx(1.0, rel=0.02)
    assert np.mean(gaps > 2.0) == pytest.approx(np.exp(-2.0), abs=0.02)
    assert gaps.max() > 4.0
