"""The configuration that brings its own weights and reference modules
(``mimo_v2``: window and global attention layers, sparse experts of which a chip
holds a share), at sizes the CPU holds: the program against the plain reference,
the int8 control, one broken run that must come out ``correct: false``, the
rehearsal cell, and hand counts for ``flops_moe.py``."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops_moe
import run as bench_run
import spec
from common import CompileCounter, Context


def _ctx(cellname, seed, seconds):
    return Context(cell=spec.load_cell(cellname), seed=seed, seconds=seconds, trace=False,
                   on_cpu=True, device={"platform": "cpu", "kind": "cpu", "count": 1},
                   t_process=time.perf_counter(), trace_dir="", counter=CompileCounter())


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def debug():
    cell = spec.load_cell("rehearsal-mimo")
    cfg = spec.register_preset(cell)
    weights = spec.load_module("weights_mimo_v2.py")
    reference = spec.load_module("reference", "mimo_v2.py")
    return cell, cfg, weights, reference


def test_the_program_agrees_with_the_reference_on_the_benchmarks_draws(debug):
    """float32 weights from the benchmark's draw, ``models.forward`` against the
    reference's full forward, base and one adapter, as logits."""
    from datatunerx_tpu.models import forward

    cell, cfg, weights, reference = debug
    mc = cell.model_fields
    params = weights.draw_params(mc, 3000000019, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 3000000019, count=2, rank=4, targets=["q_proj", "v_proj"],
                             b_std=0.05)
    assert lora["run0"]["v_proj"]["b"].shape == (2, 1, 4, 16)
    assert lora["run1"]["v_proj"]["b"].shape == (2, 3, 4, 32)
    tokens = np.random.default_rng(0).integers(10, mc["vocab_size"], size=90).tolist()
    one = jax.tree_util.tree_map(lambda a: a[1], lora)
    for ll, scale in ((None, 0.0), (one, 8.0)):
        want = reference.sequence_logits(params, mc, tokens, list(range(90)), ll, scale)
        got, _ = forward(params, jnp.asarray([tokens], jnp.int32), cfg,
                         lora=(({"layers": ll}, scale) if ll else None))
        np.testing.assert_allclose(got[0], want, atol=3e-5)
    # and the reference's own precision switch changes its answer
    low = reference.sequence_logits(params, mc, tokens, list(range(90)), precision="int8")
    assert float(jnp.abs(low - want).max()) > 1e-3 or float(jnp.abs(low - got[0]).max()) > 1e-3


def test_the_drawn_tree_is_the_programs_tree(debug):
    from datatunerx_tpu.models import init_params

    cell, cfg, weights, _ = debug
    drawn = jax.eval_shape(lambda: weights.draw_params(cell.model_fields, 1, dtype=jnp.float32))
    own = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(own)
    assert jax.tree_util.tree_map(lambda a: a.shape, drawn) == \
        jax.tree_util.tree_map(lambda a: a.shape, own)


def test_the_int8_control_fails_the_limit_the_sound_engine_passes():
    ctx = _ctx("test-mimo-serve", 7, 6.0)
    kind = spec.load_module("traffic", "kinds", ctx.cell.kind + ".py")
    r = kind.readings(ctx, True)
    limits = ctx.cell.workload["check"]["limits"]
    assert r["sound"]["served_tokens"] >= 400 and r["failed"] == 0
    assert r["sound"]["gap_mean"] <= limits["gap_mean"] < r["control"]["gap_mean"], r
    assert r["sound"]["gap_max"] <= limits["gap_max"]


def test_the_rehearsal_cell_is_correct_and_reports_no_device_metric(capsys):
    assert bench_run.main(["--workload", "rehearsal-mimo", "--seed", "3000000007",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu" and out["rehearsal"] is True


def test_unnormalised_routing_weights_make_a_run_incorrect(capsys, monkeypatch):
    """The fault: the program leaves the chosen experts' weights as the router
    gave them, where the published layer divides them by their sum."""
    real = spec.register_preset

    def broken(cell, **overrides):
        return real(cell, **dict(overrides, norm_topk_prob=False))

    monkeypatch.setattr(spec, "register_preset", broken)
    assert bench_run.main(["--workload", "test-mimo-serve", "--seed", "21",
                           "--seconds", "4", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False and out["failed"] == 0


def test_the_chip_cell_refuses_the_cpu_and_its_config_is_the_published_one():
    assert bench_run.main(["--workload", "mimo-serve-batch", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 3
    cell = spec.load_cell("mimo-serve-batch")
    pub, mc = cell.config, cell.model_fields
    for key, field in (("head_dim", "head_dim"), ("v_head_dim", "v_head_dim"),
                       ("swa_num_key_value_heads", "window_num_kv_heads"),
                       ("swa_rope_theta", "window_rope_theta"),
                       ("moe_intermediate_size", "expert_intermediate_size"),
                       ("num_experts_per_tok", "experts_per_token"),
                       ("partial_rotary_factor", "partial_rotary_factor"),
                       ("attention_value_scale", "attention_value_scale"),
                       ("add_swa_attention_sink_bias", "window_sink"),
                       ("norm_topk_prob", "norm_topk_prob"),
                       ("layernorm_epsilon", "rms_norm_eps")):
        assert pub[key] == mc[field], key
    assert pub["n_routed_experts"] == mc["experts_held"] == 16 and mc["experts_total"] == 256
    held = pub["reduced"]["num_hidden_layers"]["layers"]
    assert [("global", "window")[pub["hybrid_layer_pattern"][i]] for i in held] == mc["layer_types"]
    assert [("dense", "experts")[pub["moe_layer_freq"][i]] for i in held] == mc["ffn_types"]
    every = {m["name"] for m in cell.per_layer}
    assert {"moe_experts_roofline", "kv.behind_window_share", "step.decode_ms.mimo"} <= every
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]


@pytest.mark.parametrize("listed", spec.benchmark_json()["configs"], ids=lambda c: c["name"])
def test_a_listed_configuration_is_cut_only_where_it_says(listed):
    """No literal table (``test_yardstick.py`` keeps one of the two older
    configurations and stops at a third): the published keys are the top level
    of the configuration's own file, which ``spec.check_config`` holds
    ``model_config`` to; what was cut is ``reduced``, the same keys in the
    file and in BENCHMARK.json, never a width; every cell of it has a reader
    file for each per-layer metric it lists."""
    with open(os.path.join(spec.ROOT, listed["file"])) as f:
        cfg = json.load(f)
    spec.check_config(cfg)
    assert cfg["name"] == listed["name"] and cfg["source"] == listed["source"]
    assert list(cfg["reduced"]) == listed["reduced"]
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["here"] != cut["published"], key
        assert not key.endswith(("_dim", "_rank", "_size", "_per_tok")), key
    cells = [w["name"] for w in spec.benchmark_json()["workloads"] if w["config"] == listed["name"]]
    assert cells
    for name in cells:
        cell = spec.load_cell(name)
        assert cell.listed and {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert os.path.isfile(os.path.join(spec.HERE, "metrics", m["name"] + ".py")), m["name"]


def test_hand_counts_of_the_published_configuration():
    mc = spec.load_cell("mimo-serve-batch").model_fields
    # global: q 4096*64*192, k 4096*4*192, v 4096*4*128, o 64*128*4096
    assert flops_moe.attention_params(mc, "global") == 50331648 + 3145728 + 2097152 + 33554432
    assert flops_moe.attention_params(mc, "window") == 50331648 + 6291456 + 4194304 + 33554432 + 64
    assert flops_moe.expert_params(mc) == 3 * 4096 * 2048 == 25165824
    assert flops_moe.router_params(mc) == 4096 * 256 + 256
    assert flops_moe.dense_ffn_params(mc) == 3 * 4096 * 16384
    # what the program holds, leaf for leaf
    from datatunerx_tpu.models import init_params

    cfg = spec.register_preset(spec.load_cell("mimo-serve-batch"))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert flops_moe.total_params(mc) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 4523620160
    assert flops_moe.kv_bytes_per_token(mc) == (2 * 4 + 5 * 8) * 320 * 2 == 30720
    # one expert layer's step: 32 rows over 14 experts
    work = flops_moe.expert_layer_step(mc, 32, 14)
    assert work["flops"] == 2 * 3 * 4096 * 2048 * 32
    assert work["bytes"] == 14 * 25165824 * 2 + 32 * (2 * 4096 + 3 * 2048) * 2
    # decode attention: a window layer reads at most its window
    a = flops_moe.attention_decode_step(mc, "window", [100, 1000])
    assert a["bytes"] == 8 * 320 * (100 + 128) * 2 + 2 * 64 * 320 * 2
    g = flops_moe.attention_decode_step(mc, "global", [100, 1000])
    assert g["bytes"] == 4 * 320 * 1100 * 2 + 2 * 64 * 320 * 2 and g["flops"] == 2 * 64 * 320 * 1100
    assert flops_moe.decode_weight_bytes(mc, 16) == 2 * (
        flops_moe.total_params(mc) - 4096 * 152576 - 7 * 2 * 4096 - 4096)  # all but embedding and norms


def test_the_readers_of_the_expert_counters(debug):
    import moe_readers
    from common import Observed

    cell = debug[0]
    obs = Observed(cell=cell, engine_info={"moe_stats": {
        "decode_local_rows": 800, "decode_experts_hit": 300, "decode_max_rows": 500,
        "decode_layer_steps": 100}, "kv_behind_window_share": 61.5})
    assert moe_readers.rows_per_held_expert(obs) == 800 / (100 * 4)
    assert moe_readers.load_max_over_mean(obs) == 500 * 4 / 800
    assert moe_readers.kv_behind_window_share(obs) == 61.5
    assert moe_readers.experts_roofline(obs) is None  # no trace
    # a program from before it had expert layers: nothing to read, nothing raised
    old = Observed(cell=cell, engine_info={})
    for read in (moe_readers.rows_per_held_expert, moe_readers.load_max_over_mean,
                 moe_readers.kv_behind_window_share, moe_readers.experts_roofline,
                 moe_readers.decode_unscoped_share,
                 lambda o: moe_readers.decode_region_ms(o, moe_readers.ROUTE)):
        assert read(old) is None
    assert moe_readers.region_of("ragged-dot-none") == moe_readers.EXPERTS
    assert moe_readers.region_of("jit(f)/dtx.layers/while/body/dtx.moe_route/top_k") == "dtx.moe_route"
    assert moe_readers.region_of("jit(f)/convert") is None


def test_every_new_metric_has_a_reader_that_finds_nothing_on_an_empty_run(debug):
    from common import Observed

    cell = spec.load_cell("mimo-serve-batch")
    for m in cell.per_layer:
        reader = spec.load_module("metrics", m["name"] + ".py")
        assert reader.read(Observed(cell=cell, engine_info={"chunk": 8})) is None, m["name"]
