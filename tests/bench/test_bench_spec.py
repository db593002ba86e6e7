"""BENCHMARK.json against the contract the harness relies on, and the
FLOP and byte counts against hand counts at qwen1.5-4b widths."""
import os
import re

import pytest

from bench import flops, model, peaks, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
QWEN = model.sizes(spec.load_json(os.path.join(
    spec.BENCH, "configs", "qwen1.5-4b.json")))


def test_every_cell_resolves():
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.chips in (1, 4)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        assert cell.limits["widest_logit_gap"]["limit"] > 0
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200


def test_every_metric_has_a_reader_and_valid_fields():
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        conf = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
        assert conf["bytes"]["weights_bf16"] == 2 * model.param_count(conf)
        assert conf["bytes"]["kv_per_token"] == \
            model.kv_bytes_per_token(conf)


def test_qwen_hand_counts():
    # 40 layers of 2*2560*2560 (q, o) + 2*2560*2560 (k, v: 20 kv heads)
    # + 3*2560*6912 weights a token multiplies through
    per_layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert flops.matmul_params(QWEN) == per_layer
    assert flops.prefill_flops(QWEN, 100) == pytest.approx(
        40 * (2 * 100 * per_layer + 4 * 20 * 128 * 5050)
        + 2 * 2560 * 151936)
    assert flops.decode_flops(QWEN, 300) == pytest.approx(
        40 * (2 * per_layer + 4 * 2560 * 300) + 2 * 2560 * 151936)
    f, b = flops.swiglu_call(QWEN, 8)
    assert f == 6 * 8 * 2560 * 6912
    assert b == 2 * (3 * 2560 * 6912 + 2 * 8 * 2560)
    v5e = peaks.peaks("TPU v5 lite")
    t, bound = flops.least_time(f, b, v5e)
    assert bound == "memory" and t == pytest.approx(b / 819e9)
    f, b = flops.flash_call(QWEN, 640)
    assert f == 4 * 2560 * 640 * 641 / 2
    assert b == 2 * 640 * 4 * 2560
    # a chat-length prefill attention is bound by its bytes, a
    # document-length one by its FLOPs
    assert flops.least_time(*flops.flash_call(QWEN, 640), v5e)[1] == \
        "memory"
    assert flops.least_time(*flops.flash_call(QWEN, 4096), v5e)[1] == \
        "compute"
    assert model.param_count(spec.load_json(os.path.join(
        spec.BENCH, "configs", "qwen1.5-4b.json"))) == 3_950_369_280
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
