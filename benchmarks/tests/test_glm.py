"""The configuration whose every query selects the cached tokens it reads
(``glm_moe_dsa``: GLM-5), at sizes the CPU holds: the program against the plain
reference on the benchmark's own draws (logits and selected sets), what the
draw gives the index scores, the int8 control, two broken runs that must come
out ``correct: false``, the rehearsal cell, hand counts for ``flops_glm.py`` and
the readers of what this configuration adds."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops_glm
import run as bench_run
import spec
from common import CompileCounter, Context, Observed

CELL = "glm-serve-docs"
NEW = ["step.decode_ms.glm", "step.prefill_chunk_ms.glm", "step.decode_dsa_index_ms",
       "step.decode_dsa_select_ms", "step.decode_attn_ms.glm", "step.decode_kv_pool_ms.glm",
       "step.decode_weights_ms.glm", "step.decode_moe_experts_ms.glm", "step.decode_moe_route_ms.glm",
       "step.decode_unscoped_share.glm", "step.prefill_dsa_ms", "dsa.context_over_topk",
       "idle_share.serve_glm", "dsa_decode_roofline"]
SHARED = ["engine.gap_emit_ms.batch", "engine.gap_admit_ms.batch", "engine.gap_dispatch_ms.batch",
          "engine.gap_unnamed_share.batch"]


def _ctx(cellname, seed, seconds):
    return Context(cell=spec.load_cell(cellname), seed=seed, seconds=seconds, trace=False,
                   on_cpu=True, device={"platform": "cpu", "kind": "cpu", "count": 1},
                   t_process=time.perf_counter(), trace_dir="", counter=CompileCounter())


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def debug():
    cell = spec.load_cell("rehearsal-glm")
    cfg = spec.register_preset(cell)
    weights = spec.load_module("weights_glm_5.py")
    reference = spec.load_module("reference", "glm_5.py")
    return cell, cfg, weights, reference


def test_the_program_agrees_with_the_reference_on_the_benchmarks_draws(debug):
    """float32 weights from the benchmark's draw, ``models.forward`` against the
    reference's full forward, base and one adapter on ``q_b_proj`` / ``o_proj``,
    as logits, at a context over ``index_topk`` (32): rounding order only."""
    from datatunerx_tpu.models import forward

    cell, cfg, weights, reference = debug
    mc = cell.model_fields
    params = weights.draw_params(mc, 3000000019, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 3000000019, count=2, rank=4, targets=["q_b_proj", "o_proj", "q_proj"],
                             b_std=0.05)
    assert sorted(lora["run0"]) == sorted(lora["run1"]) == ["o_proj", "q_b_proj"]  # no q_proj to adapt
    assert lora["run1"]["q_b_proj"]["a"].shape == (2, 4, 48, 4)
    assert lora["run1"]["q_b_proj"]["b"].shape == (2, 4, 4, 4 * (16 + 8))
    tokens = np.random.default_rng(0).integers(10, mc["vocab_size"], size=120).tolist()
    one = jax.tree_util.tree_map(lambda a: a[1], lora)
    for ll, scale in ((None, 0.0), (one, 8.0)):
        want = reference.sequence_logits(params, mc, tokens, list(range(120)), ll, scale)
        got, _ = forward(params, jnp.asarray([tokens], jnp.int32), cfg,
                         lora=(({"layers": ll}, scale) if ll else None))
        assert float(jnp.abs(want).max()) > 0.05
        np.testing.assert_allclose(got[0], want, atol=2e-5)
    base = reference.sequence_logits(params, mc, tokens, list(range(120)))
    assert float(jnp.abs(base - want).max()) > 1e-3  # the adapter carries weight
    # a tail of padding is inert, and the reference's own precision switch changes its answer
    padded = reference.sequence_logits(params, mc, tokens + [0] * 38, list(range(120)), one, 8.0,
                                       valid_len=120)
    np.testing.assert_allclose(padded, want, atol=1e-7)
    low = reference.sequence_logits(params, mc, tokens, list(range(120)), one, 8.0, precision="int8")
    assert float(jnp.abs(low - want).max()) > 1e-3
    # the selection is a real one: every row past the 32nd reads 32 of what it sees, in every layer
    chosen = np.asarray(reference.sequence_selected(params, mc, tokens))
    assert chosen.shape == (5, 120, 120)
    assert (chosen.sum(-1) == np.minimum(np.arange(1, 121), 32)[None]).all()
    assert not np.triu(chosen, 1).any()
    # and the int8 control selects otherwise somewhere
    low_sets = np.asarray(reference.sequence_selected(params, mc, tokens, precision="int8"))
    assert (low_sets != chosen).any()


def test_the_drawn_tree_is_the_programs_tree(debug):
    from datatunerx_tpu.models import init_params

    cell, cfg, weights, _ = debug
    drawn = jax.eval_shape(lambda: weights.draw_params(cell.model_fields, 1, dtype=jnp.bfloat16))
    own = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(own)
    assert jax.tree_util.tree_map(lambda a: a.shape, drawn) == \
        jax.tree_util.tree_map(lambda a: a.shape, own)
    ix = drawn["layers"]["run1"]["indexer"]
    assert ix["wq_b"]["kernel"].shape == (4, 48, 3 * 16) and ix["k_norm"]["bias"].shape == (4, 16)
    assert "g_proj" not in drawn["layers"]["run1"] and "q_proj" not in drawn["layers"]["run1"]


@pytest.mark.parametrize("seed", [1, 2900000011])
def test_the_drawn_index_scores_cut_through_the_middle(seed):
    """From the drawn weights at the test size, over 200 normed rows: a query's
    index scores over its cached tokens have a standard deviation of the order
    of one hundredth to ten (no collapse, no blow-up), both signs of the head
    weights occur, and the scores that are not 0 are all different (but for a
    chance meeting of two float32 values), so the
    top-k is a cut through a continuous distribution. A score IS 0 where every
    head's product is negative: one in 2 ** 4 at this size's four heads (the
    CPU tests do exercise the tie rule), one in 2 ** 32 at the published 32."""
    cell = spec.load_cell("test-glm-serve")
    weights = spec.load_module("weights_glm_5.py")
    reference = spec.load_module("reference", "glm_5.py")
    mc = cell.model_fields
    p = jax.tree_util.tree_map(lambda a: a[0], weights.draw_params(mc, seed, dtype=jnp.float32)["layers"]["run1"])
    D, Hi, di = mc["hidden_size"], mc["index_heads"], mc["index_head_dim"]
    n = jax.random.normal(jax.random.PRNGKey(seed % 1000), (200, D))
    c_q = reference.rms_norm(n @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["scale"], 1e-5)
    q_idx = (c_q @ p["indexer"]["wq_b"]["kernel"]).reshape(200, Hi, di)
    k_idx = reference.layer_norm(n @ p["indexer"]["wk"]["kernel"], p["indexer"]["k_norm"]["scale"],
                                 p["indexer"]["k_norm"]["bias"])
    w = (n @ p["indexer"]["weights_proj"]["kernel"]) * (Hi * di) ** -0.5
    scores = np.asarray(reference.index_scores(q_idx, w, k_idx, "f32"))
    assert 0.01 < float(scores.std(axis=-1).mean()) < 10.0
    assert (np.asarray(w) > 0).any() and (np.asarray(w) < 0).any()
    # (two of a row's 190 float32 values may still meet: one pair in a few thousand rows)
    assert all(len(np.unique(row[row != 0])) >= int((row != 0).sum()) - 1 for row in scores)
    assert 0.5 * 2.0 ** -Hi < float((scores == 0).mean()) < 2 * 2.0 ** -Hi


def test_the_int8_control_fails_the_limits_the_sound_engine_passes():
    ctx = _ctx("test-glm-serve", 7, 6.0)
    kind = spec.load_module("traffic", "kinds", ctx.cell.kind + ".py")
    r = kind.readings(ctx, True)
    limits = ctx.cell.workload["check"]["limits"]
    assert r["sound"]["served_tokens"] >= 150 and r["failed"] == 0
    assert r["sound"]["gap_mean"] <= limits["gap_mean"] < r["control"]["gap_mean"], r
    assert r["sound"]["gap_max"] <= limits["gap_max"], r


def test_the_rehearsal_cell_is_correct_and_reports_no_device_metric(capsys):
    assert bench_run.main(["--workload", "rehearsal-glm", "--seed", "3000000007",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu" and out["rehearsal"] is True


@pytest.mark.parametrize("fault,change", [
    ("a selection of half the size", {"index_topk": 24}),
    ("a gate the model has not", {"routed_scaling_factor": 1.0}),
])
def test_a_fault_in_what_this_configuration_adds_makes_a_run_incorrect(capsys, monkeypatch, fault, change):
    """The PROGRAM selects 24 tokens a query where the configuration says 48,
    or weighs its experts without the published factor of 2.5; the reference as
    published. Either comes out ``correct: false``."""
    real = spec.register_preset
    monkeypatch.setattr(spec, "register_preset", lambda cell, **kw: real(cell, **dict(kw, **change)))
    assert bench_run.main(["--workload", "test-glm-serve", "--seed", "21",
                           "--seconds", "4", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False and out["failed"] == 0, fault


def test_the_chip_cell_refuses_the_cpu_and_its_config_is_the_published_one():
    assert bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 3
    cell = spec.load_cell(CELL)
    pub, mc = cell.config, cell.model_fields
    assert sorted(pub["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert pub["source"].endswith("zai-org/GLM-5/blob/main/config.json")
    listed = next(c for c in spec.benchmark_json()["configs"] if c["name"] == cell.config_name)
    assert sorted(listed["reduced"]) == sorted(pub["reduced"]) and listed["source"] == pub["source"]
    assert spec.benchmark_json()["configs"][-1] is not None and spec.benchmark_json()["workloads"][-1]["name"] == CELL
    red = pub["reduced"]
    assert (red["num_hidden_layers"]["published"], red["num_hidden_layers"]["layers"]) == (78, [0, 3, 4, 5, 6])
    assert (red["n_routed_experts"]["published"], red["n_routed_experts"]["chips_sharing_a_layer"]) == (256, 16)
    assert red["vocab_size"]["published"] == 154880 == 8 * pub["vocab_size"]
    for key, field in (("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
                       ("moe_intermediate_size", "expert_intermediate_size"),
                       ("moe_intermediate_size", "shared_expert_intermediate_size"),
                       ("num_attention_heads", "num_heads"), ("kv_lora_rank", "kv_lora_rank"),
                       ("q_lora_rank", "q_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
                       ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
                       ("index_n_heads", "index_heads"), ("index_head_dim", "index_head_dim"),
                       ("index_topk", "index_topk"), ("num_experts_per_tok", "experts_per_token"),
                       ("n_routed_experts", "experts_held"), ("n_group", "n_group"),
                       ("topk_group", "topk_group"), ("routed_scaling_factor", "routed_scaling_factor"),
                       ("norm_topk_prob", "norm_topk_prob"), ("vocab_size", "vocab_size"),
                       ("num_hidden_layers", "num_layers"), ("attention_bias", "attention_bias")):
        assert pub[key] == mc[field], key
    assert pub["qk_head_dim"] == mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"] == 256
    assert pub["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"} and mc["rope_theta"] == 1e6
    assert pub["rope_interleave"] is True and pub["indexer_rope_interleave"] is True
    assert pub["scoring_func"] == "sigmoid" and pub["n_shared_experts"] == 1 and mc["experts_total"] == 256
    assert mc["mla_head_gate"] is False and mc["layer_types"] == ["mla"] * 5
    assert mc["ffn_types"] == ["dense"] + ["experts"] * 4
    every = [m["name"] for m in cell.per_layer]
    assert sorted(every) == sorted(NEW + SHARED)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    t, e = cell.traffic, cell.workload["engine"]
    assert (t["clients"], t["requests"], t["kind"], t["lead_in_s"]) == (32, 48, "closed-loop-arch", 12.0)
    assert (t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]) == (4096, 8192)
    assert (t["output_tokens"]["min"], t["output_tokens"]["max"]) == (256, 512)
    assert t["temperature"] == 0.0 and abs(t["base_share"] - 1 / 3) < 1e-3 and t["schedule_seed"] == 20261001
    assert t["clients"] == 2 * e["slots"] == 32 and e["kv_blocks"] * e["kv_block_size"] == 16 * e["max_seq_len"]
    assert e["max_seq_len"] == t["prompt_tokens"]["max"] + t["output_tokens"]["max"] == 8704
    assert t["prompt_tokens"]["min"] >= 2 * mc["index_topk"]  # every selection in the window is a real one
    assert (e["decode_chunk"], e["prefill_chunk"], e["kv_overcommit"]) == (8, 256, "off")
    assert cell.workload["adapters"] == {"count": 2, "rank": 8, "alpha": 32.0,
                                         "targets": ["q_b_proj", "o_proj"]}
    assert cell.workload["check"]["requests"] == 8


def test_hand_counts_of_the_published_configuration():
    mc = spec.load_cell(CELL).model_fields
    D = 6144
    # q_a 6144 x 2048, q_b 2048 x 16384, kv_a 6144 x 576, kv_b 512 x 28672, o 16384 x 6144, norms 2048 + 512
    mla = D * 2048 + 2048 * 16384 + D * 576 + 512 * 28672 + 16384 * D + 2048 + 512
    # the indexer: wq_b 2048 x 4096, wk 6144 x 128, weights_proj 6144 x 32, the key norm's scale and bias
    idx = 2048 * 4096 + D * 128 + D * 32 + 2 * 128
    assert flops_glm.indexer_params(mc) == idx == 9371904
    assert flops_glm.mixer_params(mc) == mla + idx == 174394112  # 174.4 M
    assert flops_glm.layer_params(mc, "dense") == mla + idx + 2 * D + 3 * D * 12288 == 400898816
    expert = 3 * D * 2048
    assert flops_glm.layer_params(mc, "experts") == mla + idx + 2 * D + D * 256 + 256 + expert + 16 * expert \
        == 817708032
    from datatunerx_tpu.models import init_params

    cfg = spec.register_preset(spec.load_cell(CELL))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert flops_glm.total_params(mc) == leaves == 3909632768  # 3,909.6 M: 7.82 GB in bf16
    # a token caches 576 + 128 values a layer; the latent row is stored 640 wide (whole lane tiles)
    assert flops_glm.cache_bytes_per_token(mc) == 5 * (576 + 128) * 2 == 7040
    assert flops_glm.stored_bytes_per_token(mc) == 5 * (640 + 128) * 2 == 7680
    from datatunerx_tpu.ops.paged_attention import init_paged_cache, kv_leaf_keys

    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 16, 8704, 16, 544, dtype=jnp.bfloat16))
    assert sorted(kv_leaf_keys(cache)) == ["k_idx", "k_mla"]  # eval_shape sorts the dict
    assert cache["k_mla"].shape == (5, 8704, 16, 640) and cache["k_idx"].shape == (5, 8704, 16, 128)
    assert sum(int(np.prod(cache[k].shape)) * 2 for k in kv_leaf_keys(cache)) == 8704 * 16 * 7680  # 1.07 GB
    work = flops_glm.dsa_decode_step(mc, [5900] * 16 + [1000])
    assert work["bytes"] == 5 * ((16 * 5900 + 1000) * 256 + (16 * 2048 + 1000) * 1152)
    assert work["flops"] == 5 * (2 * 32 * 128 * (16 * 5900 + 1000) + 2 * 64 * 1088 * (16 * 2048 + 1000))
    assert flops_glm.decode_weight_bytes(mc, 16.0) == 2 * (
        flops_glm.total_params(mc) - 5 * 2 * D - D - 19360 * D)  # all but the norms; the embedding is a gather


def test_the_readers_of_what_this_configuration_adds():
    import glm_readers

    cell = spec.load_cell(CELL)
    empty = Observed(cell=cell, engine_info={"chunk": 8, "slots": 16})
    # no trace, or a program from before it had the mechanism: nothing to read, nothing raised
    for read in (glm_readers.dsa_decode_roofline, glm_readers.prefill_dsa_ms, glm_readers.context_over_topk,
                 glm_readers.decode_unscoped_share,
                 lambda o: glm_readers.decode_region_ms(o, glm_readers.DSA_INDEX),
                 lambda o: glm_readers.decode_region_ms(o, glm_readers.ATTN)):
        assert read(empty) is None
    region = glm_readers.moe_readers.region_of
    assert region("jit(f)/dtx.layers/while/body/dtx.dsa_index/dot_general") in glm_readers.DSA_INDEX
    assert region("jit(f)/dtx.layers/while/body/dtx.dsa_select/top_k") in glm_readers.DSA_SELECT
    assert region("jit(f)/dtx.layers/while/body/dtx.dsa_gather/gather") in glm_readers.ATTN
    assert region("jit(f)/dtx.layers/while/body/dtx.moe_shared/dot_general") in glm_readers.WEIGHTS
    assert region("jit(f)/dtx.layers/while/body/dtx.kv_write/scatter") in glm_readers.KV_POOL


def test_the_roofline_share_and_the_counter_from_a_hand_made_decode(monkeypatch):
    """16 live slots at 5,900 tokens of context, 1.2 ms under the selection's
    scopes and ``dtx.attn`` a token step: 5 x 16 x (5,900 x 256 + 2,048 x 1,152) B
    at 819 GB/s is 0.378 ms, 31.5 %. The decode spans' keywords give the counter."""
    import glm_readers

    cell = spec.load_cell(CELL)
    obs = Observed(cell=cell, engine_info={"chunk": 8, "slots": 16})
    obs.peaks = spec.peaks_for("TPU v5 lite")
    obs.trace_clock = (10.0, 14.0)
    monkeypatch.setattr(glm_readers.moe_readers, "decode_region_ms", lambda o, regions: 1.2)
    monkeypatch.setattr(glm_readers.readers, "live_requests", lambda o: [])
    monkeypatch.setattr(glm_readers.readers, "decode_dispatches", lambda o: [11.0, 12.0])
    monkeypatch.setattr(glm_readers.readers, "rows_at", lambda o, live, t: [(None, 5900.0)] * 16)
    least_ms = 5 * 16 * (5900 * 256 + 2048 * 1152) / obs.peaks["hbm_bytes_per_s"] * 1e3
    assert glm_readers.dsa_decode_roofline(obs) == pytest.approx(100 * least_ms / 1.2)
    assert 31.0 < glm_readers.dsa_decode_roofline(obs) < 32.0
    spans = [("dtx_engine_decode", 9.0, 0.1, {"live": 16, "dsa_context": 1, "dsa_selected": 1}),   # before the window
             ("dtx_engine_decode", 10.5, 0.1, {"live": 16, "dsa_context": 1000000, "dsa_selected": 400000}),
             ("dtx_engine_tick", 11.0, 0.5, {"tick": 3}),
             ("dtx_engine_decode", 11.5, 0.1, {"live": 16, "dsa_context": 1755200, "dsa_selected": 662144}),
             ("dtx_engine_decode", 13.0, 0.1, {"live": 16, "dsa_context": 2510400, "dsa_selected": 924288})]
    monkeypatch.setattr(glm_readers.span_stats, "spans", lambda o: spans)
    assert glm_readers.context_over_topk(obs) == pytest.approx(1510400 / 524288)  # 5,900 over 2,048
    monkeypatch.setattr(glm_readers.span_stats, "spans", lambda o: [s[:3] + ({"live": 16},) for s in spans])
    assert glm_readers.context_over_topk(obs) is None  # a program whose spans carry no counter


def test_every_new_metric_has_a_reader_that_finds_nothing_on_an_empty_run():
    cell = spec.load_cell(CELL)
    entries = {m["name"]: m for m in spec.benchmark_json()["per_layer"]}
    assert [m["name"] for m in spec.benchmark_json()["per_layer"]][-14:] == NEW  # appended, in the issue's order
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert m["source"] == ("program_counter" if name == "dsa.context_over_topk" else "device_trace")
    assert {entries[n]["layer"] for n in NEW} == {"Model step, serve", "Token selection",
                                                  "Expert feed-forward", "Device"}
    for name in SHARED:
        assert entries[name]["workloads"][-1] == CELL
    for m in cell.per_layer:
        reader = spec.load_module("metrics", m["name"] + ".py")
        assert reader.read(Observed(cell=cell, engine_info={"chunk": 8, "slots": 16})) is None, m["name"]
