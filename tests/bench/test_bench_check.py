"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a tiny size (everything but the look for a chip): sound runs
pass, the fp8 control fails, and a served token altered where the decode
program produces it fails."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, peaks, run, spec

CONF = {"name": "tiny", "hidden_act": "silu", "hidden_size": 128,
        "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
        "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "tie_word_embeddings": False, "attention_bias": True}
MIX = {"rate_per_s": 6.0, "lead_s": 0.5, "block": 16,
       "prompt": {"alpha": 1.2, "lo": 16, "hi": 64, "ladder": [16, 32, 64]},
       "output": {"alpha": 1.5, "lo": 8, "hi": 24}, "slots": 4,
       "max_len": 96, "check": {"min_tokens": 60, "max_requests": 4}}
# At this size on the CPU the program's widest gap reads 0 to 0.01 and the
# fp8 control's 0.2 to 0.4 (three seeds each); the chip cell's own limit
# is in bench/limits/.
LIMIT = 0.1
SEED = 2 ** 31 + 7


def tiny_run(keep=None, seed=SEED):
    """A whole run on the CPU: the Pallas kernels in interpret mode, and
    the v5e's peaks for the CPU's (which has none in the table)."""
    import repro.viscosity
    bench = spec.load_json(f"{spec.ROOT}/BENCHMARK.json")
    cell = spec.Cell("tiny", 1, CONF, MIX,
                     {"widest_logit_gap": {"limit": LIMIT}},
                     bench["end_to_end"], bench["per_layer"])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.viscosity, "HW", "interpret")
        mp.setattr(peaks, "peaks",
                   lambda kind: peaks.CHIP_PEAKS["TPU v5 lite"])
        return run.run_cell(cell, seed, 1.5, False, jax.devices()[:1],
                            time.time(), keep=keep)


@pytest.fixture(scope="module")
def sound():
    keep = {}
    return tiny_run(keep), keep


def test_sound_run_is_correct(sound):
    res, keep = sound
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "check"
    gap = res["check"]["widest_logit_gap"]
    assert gap["limit"] == LIMIT and 0.0 <= gap["value"] < LIMIT
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # the sample holds the request that served the most tokens
    longest = max(len(c.tokens) for c in keep["record"].completions.values())
    assert max(len(t) for _, t in keep["seqs"]) == longest


def test_fp8_control_is_not_correct(sound):
    _, keep = sound
    ok, widest, bad = check.verdict(
        check.control_gaps(CONF, SEED, keep["seqs"]), LIMIT)
    assert not ok and widest > 2 * LIMIT and bad >= 1


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve import engine as eng_mod
    real = eng_mod.build_decode

    def altered(*a, **kw):
        fn, calls = real(*a, **kw), [0]

        def step(*args):
            logits, cache = fn(*args)
            calls[0] += 1
            if calls[0] % 7 == 0:     # every 7th tick serves a wrong token
                logits = jnp.roll(logits, 1, axis=-1)
            return logits, cache
        return step

    monkeypatch.setattr(eng_mod, "build_decode", altered)
    res = tiny_run()
    assert res["correct"] is False and res["failed"] >= 1
    assert res["check"]["widest_logit_gap"]["value"] > LIMIT


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen1.5-4b.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_sample_is_drawn_from_the_seed():
    class C:
        def __init__(self, rid, n):
            self.rid, self.tokens, self.prompt_len = rid, np.zeros(n), 8
    done = {i: C(i, n) for i, n in enumerate([5, 50, 7, 9, 11, 13, 20])}
    mix = {"check": {"min_tokens": 70, "max_requests": 3}}
    a = check.sample(done, mix, 1)
    assert a[0] == 1 and len(a) == 3 and a == check.sample(done, mix, 1)
    assert any(check.sample(done, mix, s) != a for s in range(2, 9))
