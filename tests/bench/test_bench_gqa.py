"""The comparison that decides ``correct`` on a grouped-query model whose
query width is not d_model, as mistral-nemo-12b's is (32 query heads of
128 on 8 kv heads, d_model 5120): a whole run through ``ServeEngine``
prefill and decode on the CPU at a tiny size, and its prefill logits
beside the float32 reference's on the same seeded weights."""
import time

import jax
import numpy as np
import pytest

from bench import check, model, peaks, reference, run, spec

# 4 query heads a kv head; query width 8 x 32 = 256 against d_model 128
CONF = {"name": "tiny-gqa", "hidden_act": "silu", "hidden_size": 128,
        "intermediate_size": 256, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
        "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
        "tie_word_embeddings": False, "attention_bias": False}
MIX = {"rate_per_s": 6.0, "lead_s": 0.5, "block": 16,
       "prompt": {"alpha": 1.2, "lo": 48, "hi": 48, "ladder": [48]},
       "output": {"alpha": 1.5, "lo": 8, "hi": 24}, "slots": 4,
       "max_len": 96, "check": {"min_tokens": 60, "max_requests": 4}}
LIMIT = 0.1          # as the tiny cell of test_bench_check.py
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def served():
    """A run of the tiny GQA cell (Pallas kernels in interpret mode), and
    the engine's prefill logits of one prompt."""
    import repro.viscosity
    from repro.serve import RESIDENT, ServeConfig, ServeEngine
    bench = spec.load_json(f"{spec.ROOT}/BENCHMARK.json")
    cell = spec.Cell("tiny-gqa", 1, CONF, MIX,
                     {"widest_logit_gap": {"limit": LIMIT}},
                     bench["end_to_end"], bench["per_layer"])
    keep = {}
    prompt = np.random.default_rng(SEED).integers(0, 512, 48, np.int32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.viscosity, "HW", "interpret")
        mp.setattr(peaks, "peaks",
                   lambda kind: peaks.CHIP_PEAKS["TPU v5 lite"])
        res = run.run_cell(cell, SEED, 1.5, False, jax.devices()[:1],
                           time.time(), keep=keep)
        engine = ServeEngine(model.program_config(CONF),
                             model.make_params(CONF, SEED), ServeConfig(
                                 max_len=96, max_slots=1,
                                 hw_route="interpret", failover=RESIDENT))
        logits, _ = engine.prefill(prompt)
    return res, keep, prompt, np.asarray(logits[0, -1], np.float32)


@pytest.mark.parametrize("case", ["program", "fp8_control",
                                  "prefill_logits"])
def test_gqa_wide_query_against_reference(served, case):
    res, keep, prompt, logits = served
    if case == "program":
        assert res["correct"] is True and res["failed"] == 0
        assert 0.0 <= res["check"]["widest_logit_gap"]["value"] < LIMIT
        assert sum(len(t) for _, t in keep["seqs"]) >= 24
    elif case == "fp8_control":
        ok, widest, bad = check.verdict(
            check.control_gaps(CONF, SEED, keep["seqs"]), LIMIT)
        assert not ok and widest > LIMIT and bad >= 1
    else:
        ref = np.asarray(reference.logits(
            CONF, SEED, [(prompt, np.zeros(1, np.int32))])[0][0])
        assert np.argmax(logits) == np.argmax(ref)
        rel = np.linalg.norm(logits - ref) / np.linalg.norm(ref)
        assert rel < 0.02, rel
