"""Window and percentile arithmetic of the benchmark, on a hand-made
record: TTFT from when a request was due, unfinished requests counted
with their wait so far, inter-token gaps inside the window only."""
import numpy as np
import pytest

from bench import layer, stats
from bench.driver import Record, ReqRec, StepRec, token_stamps


def record():
    # window [10, 20); steps of 1 s ending at 1.5, 2.5, ... 24.5
    rec = Record(t0=0.0, ws=10.0, we=20.0)
    for k in range(24):
        rec.steps.append(StepRec(start=k + 0.6, end=k + 1.5, admitted=[],
                                 dt=0.5, active=0))
    def req(rid, P, n, due, a):
        rec.reqs[rid] = ReqRec(rid, P, n, due, a)
        if a is not None:
            rec.steps[a].admitted.append(rid)
    req(0, 32, 5, 8.0, 9)      # due before the window
    req(1, 64, 4, 10.2, 10)    # first token at 11.5
    req(2, 32, 20, 12.0, 13)   # still decoding when the window ends
    req(3, 32, 3, 19.0, None)  # due in the window, never admitted
    for r in rec.reqs.values():
        if r.admit_step is None:
            continue
        for j in range(r.n - 1):
            k = r.admit_step + j
            if k < len(rec.steps):
                rec.steps[k].active += 1
    return rec


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(range(101), 95) == 95.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_token_stamps_first_two_together():
    rec = record()
    np.testing.assert_allclose(token_stamps(rec, rec.reqs[1]),
                               [11.5, 11.5, 12.5, 13.5])
    assert len(token_stamps(rec, rec.reqs[3])) == 0
    # a request still decoding has stamps up to the last step only
    assert token_stamps(rec, rec.reqs[2])[-1] == 24.5


def test_ttft_counts_unfinished_with_their_wait():
    rec = record()
    got = sorted(stats.ttft_s(rec))
    # rid 1: 11.5 - 10.2; rid 2: 14.5 - 12; rid 3: never -> 20 - 19
    np.testing.assert_allclose(got, sorted([1.3, 2.5, 1.0]))
    assert [r.rid for r in stats.due_in_window(rec)] == [1, 2, 3]


def test_itl_inside_window_only():
    rec = record()
    gaps = stats.itl_s(rec)
    # rid 0 tokens at 10.5(x2), 11.5, 12.5, 13.5 -> 3 gaps inside
    # rid 1 at 11.5(x2), 12.5, 13.5 -> 2; rid 2 from 14.5 on, inside
    # until 19.5 -> 5
    assert len(gaps) == 3 + 2 + 5
    np.testing.assert_allclose(gaps, 1.0)


def test_window_tokens_and_steps():
    rec = record()
    # steps ending in [10, 20]: ends 10.5 .. 19.5
    expect = sum(len(s.admitted) + s.active for s in rec.steps
                 if 10 <= s.end <= 20)
    assert stats.window_tokens(rec) == expect
    class Run:
        pass
    run = Run()
    run.rec = rec
    assert layer.window_steps(run) == list(range(10, 19))
    e2e = stats.end_to_end(rec)
    assert e2e["tokens_per_s"] == expect / 10.0
    assert e2e["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([1.3, 2.5, 1.0], 95))
    assert e2e["ttft_p50_ms"] == pytest.approx(1e3 * 1.3)


def test_decode_contexts():
    rec = record()

    class Run:
        pass
    run = Run()
    run.rec = rec
    ctx = layer.decode_contexts(run)
    assert ctx[10] == [34, 65]      # rid 0 (P 32, 2nd tick), rid 1 (P 64)
    assert ctx[13] == [33]
    assert layer.prefill_lengths(run, 13) == [32]
    assert layer.prefill_seconds(run, 13) == pytest.approx(0.4)


def test_ttft_tail_reader_matches_the_window_arithmetic():
    from bench import spec

    class Run:
        pass
    run = Run()
    run.rec = record()
    assert spec.metric_reader("ttft_p95_ms")(run) == pytest.approx(
        stats.end_to_end(run.rec)["ttft_p95_ms"])
