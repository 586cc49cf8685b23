"""The yardstick's own arithmetic: peaks, operation counts, draws, the spec files."""

import json
import os

import numpy as np
import pytest

import draws
import flops
import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="not in benchmarks/peaks.json"):
        spec.peaks_for("TPU v9 imaginary")


def _mc(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model_config"]


def test_layer_counts_by_hand():
    # Qwen1.5-7B: q,k,v,o 4096x4096 each with q/k/v bias, gate/up/down 4096x11008, two norms
    qwen = _mc("qwen1.5-7b-l16")
    by_hand = 4 * 4096 * 4096 + 3 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    assert flops.layer_params(qwen) == by_hand == 202_395_648
    # Mistral-7B: q,o 4096x4096, k,v 4096x1024 (8 KV heads of 128), MLP 4096x14336
    mistral = _mc("mistral-7b-l16")
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert flops.layer_params(mistral) == by_hand == 218_112_000
    assert flops.total_params(qwen) == 16 * 202_395_648 + 2 * 151936 * 4096 + 4096
    assert flops.total_params(mistral) == 16 * 218_112_000 + 2 * 32000 * 4096 + 4096


def test_train_flops_per_token_by_hand():
    m = _mc("mistral-7b-l16")
    matmul = 16 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 4096 * 32000
    assert flops.matmul_params(m) == matmul
    # forward 2 and activation-backward 2 per matmul parameter; attention QK^T and PV,
    # forward and backward, over half a 1024-token row on average
    attn = 2.0 * (16 * 2 * 2 * 32 * 128 * 512)
    assert flops.train_flops_per_token_lora(m, 1024) == pytest.approx(4.0 * matmul + attn)


def test_kernel_work_and_roofline():
    q = _mc("qwen1.5-7b-l16")
    w = flops.paged_decode_attention(q, [100, 300])
    assert w["flops"] == 2 * 2 * 32 * 128 * 400
    assert w["bytes"] == 2 * 32 * 128 * 400 * 2 + 2 * 2 * 32 * 128 * 2
    peaks = spec.peaks_for("TPU v5 lite")
    r = flops.roofline_seconds(w, peaks)
    assert r["bound"] == "memory" and r["seconds"] == pytest.approx(w["bytes"] / 819e9)
    s = flops.fused_sample(151936, 16, 3)
    assert s["bytes"] == 151936 * 16 * 3 * 4


def test_every_seed_gets_the_same_sizes_at_the_same_times():
    with open(os.path.join(BENCH, "traffic", "open-loop-adapters.json")) as f:
        t = json.load(f)
    a = draws.request_set(200, t, ["x", "y"], 1000, 11)
    b = draws.request_set(200, t, ["x", "y"], 1000, 3_000_000_019)  # above 2**31, as the driver's
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [(r["max_new_tokens"], r["adapter"], r["temperature"]) for r in a] == \
           [(r["max_new_tokens"], r["adapter"], r["temperature"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert a == draws.request_set(200, t, ["x", "y"], 1000, 11)  # the same seed, the same inputs
    lens = np.array([len(r["prompt"]) for r in a])
    assert lens.min() >= 64 and lens.max() == 1024 and 90 < np.median(lens) < 115
    due = draws.arrival_times(200, 2.8, t["schedule_seed"])
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] == pytest.approx(199 / 2.8, rel=0.02)
    assert sum(r["temperature"] == 0.0 for r in a) == 25  # one in eight greedy, for the output check


def test_listed_cells_load_and_widths_are_the_published_ones():
    bench = spec.benchmark_json()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.listed and cell.chips == 1
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
    published = {
        "qwen1.5-7b-l16": dict(hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
                               num_key_value_heads=32, vocab_size=151936, rope_theta=1e6,
                               rms_norm_eps=1e-6),
        "mistral-7b-l16": dict(hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
                               num_key_value_heads=8, vocab_size=32000, rope_theta=1e4,
                               rms_norm_eps=1e-5, sliding_window=4096),
    }
    for c in bench["configs"]:
        with open(os.path.join(BENCH, "..", c["file"])) as f:
            cfg = json.load(f)
        for k, v in published[c["name"]].items():
            assert cfg[k] == v, (c["name"], k)
        assert list(cfg["reduced"]) == c["reduced"] == ["num_hidden_layers"]
        spec.check_config(cfg)


def test_a_cell_outside_benchmark_json_must_say_it_is_a_rehearsal(tmp_path, monkeypatch):
    assert not spec.load_cell("rehearsal-serve").listed
    bad = dict(spec._read_json("workloads", "rehearsal-serve.json"), rehearsal=False)
    monkeypatch.setattr(spec, "_read_json", lambda *p: bad if p[0] == "workloads" else None)
    with pytest.raises(ValueError, match="not marked as a rehearsal"):
        spec.load_cell("rehearsal-serve")
