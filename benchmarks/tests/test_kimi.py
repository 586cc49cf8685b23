"""The configuration of dense latent attention under YaRN served through a
prefix cache (``kimi_k2``: Kimi-K2.5), at sizes the CPU holds: the program
against the plain reference on the benchmark's own draws (logits), the session
generator's plan and its check sample, the int8 control, two broken runs that
must come out ``correct: false``, the rehearsal cell, hand counts for
``flops_kimi.py`` and the readers of what this configuration adds."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops_kimi
import run as bench_run
import spec
from common import CompileCounter, Context, Observed

CELL = "kimi-serve-agent"
NEW = ["step.decode_ms.kimi", "step.prefill_chunk_ms.kimi", "step.decode_attn_ms.kimi",
       "step.decode_kv_pool_ms.kimi", "step.decode_weights_ms.kimi", "step.decode_moe_experts_ms.kimi",
       "step.decode_moe_route_ms.kimi", "step.decode_unscoped_share.kimi", "step.prefill_mla_ms",
       "idle_share.serve_kimi", "prefix.shared_token_share", "mla_decode_roofline"]
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
    cell = spec.load_cell("rehearsal-kimi")
    cfg = spec.register_preset(cell)
    weights = spec.load_module("weights_kimi_k2.py")
    reference = spec.load_module("reference", "kimi_k2.py")
    return cell, cfg, weights, reference


def test_the_program_agrees_with_the_reference_on_the_benchmarks_draws(debug):
    """float32 weights from the benchmark's draw, ``models.forward`` against the
    reference's full forward, base and one adapter on ``q_b_proj`` / ``o_proj``,
    as logits, at a context past the 64 positions YaRN scales from: rounding
    order only."""
    from datatunerx_tpu.models import forward

    cell, cfg, weights, reference = debug
    mc = cell.model_fields
    params = weights.draw_params(mc, 3000000019, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 3000000019, count=2, rank=4, targets=["q_b_proj", "o_proj", "q_proj"],
                             b_std=0.05)
    assert sorted(lora["run0"]) == sorted(lora["run1"]) == ["o_proj", "q_b_proj"]  # no q_proj to adapt
    assert lora["run1"]["q_b_proj"]["b"].shape == (2, 4, 4, 4 * (16 + 8))
    assert lora["run1"]["o_proj"]["a"].shape == (2, 4, 4 * 16, 4)  # v heads of 16 beside q/k of 24
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
    # the reference's YaRN is the configuration's: without it, or with the temperature on the
    # tables instead of the scores, it is another model
    for change in ({"rope_scaling_type": None}, {"rope_mscale_all_dim": 0.0}):
        off = reference.sequence_logits(params, dict(mc, **change), tokens, list(range(120)))
        assert float(jnp.abs(off - base).max()) > 1e-3, change


def test_the_drawn_tree_is_the_programs_tree(debug):
    from datatunerx_tpu.models import init_params

    cell, cfg, weights, _ = debug
    drawn = jax.eval_shape(lambda: weights.draw_params(cell.model_fields, 1, dtype=jnp.bfloat16))
    own = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(own)
    assert jax.tree_util.tree_map(lambda a: a.shape, drawn) == \
        jax.tree_util.tree_map(lambda a: a.shape, own)
    run1 = drawn["layers"]["run1"]
    assert "indexer" not in run1 and "g_proj" not in run1 and "q_proj" not in run1


def test_a_sessions_turns_extend_its_history_and_stay_inside_the_context():
    """The plan: 96 sessions whose sizes the schedule_seed fixes and whose ids
    the seed draws; each further turn adds the last answer and a tool result;
    no turn's prompt, laid out in whole buckets as the engine lays it, plus its
    output passes ``max_context``."""
    cell = spec.load_cell(CELL)
    kind = spec.load_module("traffic", "kinds", cell.kind + ".py")
    a = kind.plan(cell, 4100000001, 50.0, ["ad0", "ad1"], 20480)
    b = kind.plan(cell, 2147483999, 50.0, ["ad0", "ad1"], 20480)
    assert a["clients"] == 24 and len(a["sessions"]) == len(b["sessions"]) == 96
    up = lambda n: -(-n // 64) * 64  # noqa: E731
    turns, ended_early = [], 0
    for sa, sb in zip(a["sessions"], b["sessions"]):
        # the same sizes at the same places, other contents
        assert len(sa["first"]) == len(sb["first"]) and sa["adapter"] == sb["adapter"]
        assert [(len(t["tool"]), t["max_new_tokens"]) for t in sa["turns"]] == \
            [(len(t["tool"]), t["max_new_tokens"]) for t in sb["turns"]]
        assert sa["first"] != sb["first"] and 4096 <= len(sa["first"]) <= 8192
        assert 32 <= sa["max_new_tokens"] <= 128 and max(sa["first"]) < 20480 and min(sa["first"]) >= 10
        cursor, last = up(len(sa["first"])), sa["max_new_tokens"]
        for t in sa["turns"]:
            assert 64 <= len(t["tool"]) <= 512 and 32 <= t["max_new_tokens"] <= 128
            cursor, last = cursor + up(last + len(t["tool"])), t["max_new_tokens"]
            assert cursor + last <= 12288
        turns.append(len(sa["turns"]))
        ended_early += len(sa["turns"]) < 3
    assert max(turns) == 11 and sum(turns) > 96 * 5 and ended_early <= 6
    names = [s["adapter"] for s in a["sessions"]]
    assert {n: names.count(n) for n in set(names)} == {"": 32, "ad0": 32, "ad1": 32}


def test_the_check_sample_holds_both_paths_and_the_longest(debug):
    kind = spec.load_module("traffic", "kinds", "closed-loop-sessions.py")

    class Req:
        def __init__(self, mode):
            self.timeline = [(0.0, "admit", {"mode": mode})]

    class Rec:
        def __init__(self, i, n, mode):
            self.spec = {"prompt": [0] * n, "temperature": 0.0, "id": i}
            self.n_tokens, self.error, self.req = 5, None, Req(mode)

    recs = [Rec(i, 100 + 10 * i, "cow_extend") for i in range(10)] + [Rec(10, 50, "chunked"), Rec(11, 60, "chunked")]
    sample = kind.check_sample(recs, 4, 7)
    assert [r.spec["id"] for r in sample[:3]] == [9, 11, 8]  # the longest, the longest cold, the longest shared left
    assert len(sample) == 4 and len({r.spec["id"] for r in sample}) == 4
    assert [r.spec["id"] for r in kind.check_sample(recs[:10], 3, 7)[:2]] == [9, 8]  # no cold turn finished


def test_the_int8_control_fails_the_limits_the_sound_engine_passes():
    ctx = _ctx("test-kimi-serve", 7, 6.0)
    kind = spec.load_module("traffic", "kinds", ctx.cell.kind + ".py")
    r = kind.readings(ctx, True)
    limits = ctx.cell.workload["check"]["limits"]
    assert r["sound"]["served_tokens"] >= 150 and r["failed"] == 0
    assert r["sound"]["paths"] == ["chunked", "cow_extend"]
    assert r["sound"]["gap_mean"] <= limits["gap_mean"] < r["control"]["gap_mean"], r
    assert r["sound"]["gap_max"] <= limits["gap_max"], r


def test_the_rehearsal_cell_is_correct_and_reports_no_device_metric(capsys):
    assert bench_run.main(["--workload", "rehearsal-kimi", "--seed", "3000000007",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu" and out["rehearsal"] is True


@pytest.mark.parametrize("fault,change", [
    ("the temperature on the tables, not the scores", {"rope_mscale_all_dim": 0.0}),
    ("a gate the model has not", {"routed_scaling_factor": 1.0}),
])
def test_a_fault_in_what_this_configuration_adds_makes_a_run_incorrect(capsys, monkeypatch, fault, change):
    """The PROGRAM scales its scores as if the model stated no temperature for
    all lanes, or weighs its experts without the published factor of 2.827; the
    reference as published. Either comes out ``correct: false``."""
    real = spec.register_preset
    monkeypatch.setattr(spec, "register_preset", lambda cell, **kw: real(cell, **dict(kw, **change)))
    assert bench_run.main(["--workload", "test-kimi-serve", "--seed", "21",
                           "--seconds", "4", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False and out["failed"] == 0, fault


def test_the_chip_cell_refuses_the_cpu_and_its_config_is_the_published_one():
    assert bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 3
    cell = spec.load_cell(CELL)
    pub, mc = cell.config, cell.model_fields
    assert sorted(pub["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert pub["source"].endswith("moonshotai/Kimi-K2.5/blob/main/config.json")
    listed = next(c for c in spec.benchmark_json()["configs"] if c["name"] == cell.config_name)
    assert sorted(listed["reduced"]) == sorted(pub["reduced"]) and listed["source"] == pub["source"]
    red = pub["reduced"]
    assert (red["num_hidden_layers"]["published"], red["num_hidden_layers"]["layers"]) == (61, [0, 1, 2, 3, 4])
    assert (red["n_routed_experts"]["published"], red["n_routed_experts"]["chips_sharing_a_layer"]) == (384, 32)
    assert red["n_routed_experts"]["published"] == 32 * pub["n_routed_experts"]
    assert red["vocab_size"]["published"] == 163840 == 8 * pub["vocab_size"]
    for key, field in (("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
                       ("moe_intermediate_size", "expert_intermediate_size"),
                       ("moe_intermediate_size", "shared_expert_intermediate_size"),
                       ("num_attention_heads", "num_heads"), ("kv_lora_rank", "kv_lora_rank"),
                       ("q_lora_rank", "q_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
                       ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
                       ("num_experts_per_tok", "experts_per_token"), ("n_routed_experts", "experts_held"),
                       ("n_group", "n_group"), ("topk_group", "topk_group"),
                       ("routed_scaling_factor", "routed_scaling_factor"), ("norm_topk_prob", "norm_topk_prob"),
                       ("vocab_size", "vocab_size"), ("num_hidden_layers", "num_layers"),
                       ("attention_bias", "attention_bias"), ("rope_theta", "rope_theta"),
                       ("max_position_embeddings", "max_seq_len"), ("rms_norm_eps", "rms_norm_eps")):
        assert pub[key] == mc[field], key
    assert pub["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                                   "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"}
    assert (mc["rope_scaling_type"], mc["rope_scaling_factor"], mc["rope_original_max_len"], mc["rope_beta_fast"],
            mc["rope_beta_slow"], mc["rope_mscale"], mc["rope_mscale_all_dim"]) == ("yarn", 64, 4096, 32, 1, 1, 1)
    assert pub["scoring_func"] == "sigmoid" and pub["n_shared_experts"] == 1 and mc["experts_total"] == 384
    assert pub["first_k_dense_replace"] == 1 and mc["ffn_types"] == ["dense"] + ["experts"] * 4
    assert mc["mla_head_gate"] is False and mc["layer_types"] == ["mla"] * 5 and "index_topk" not in mc
    assert "vision_tower" in pub["left_out"] and "yarn" in pub["assumed"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(NEW + SHARED)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    t, e = cell.traffic, cell.workload["engine"]
    assert (t["clients"], t["sessions"], t["kind"]) == (24, 96, "closed-loop-sessions")
    assert (t["first_prompt_tokens"]["min"], t["first_prompt_tokens"]["max"]) == (4096, 8192)
    assert (t["tool_tokens"]["min"], t["tool_tokens"]["max"]) == (64, 512)
    assert (t["output_tokens"]["min"], t["output_tokens"]["max"]) == (32, 128)
    assert t["further_turns"] == {"min": 3, "max": 11} and t["temperature"] == 0.0
    assert t["max_context"] == e["max_seq_len"] == 12288 and e["slots"] == 16 < t["clients"]
    assert e["kv_blocks"] * e["kv_block_size"] == t["clients"] * e["max_seq_len"]  # 24 sessions at their longest
    assert (e["decode_chunk"], e["prefill_chunk"], e["kv_overcommit"]) == (8, 256, "on")
    assert e["prefix_cache"] >= 2 * t["clients"]
    assert cell.workload["adapters"] == {"count": 2, "rank": 8, "alpha": 32.0,
                                         "targets": ["q_b_proj", "o_proj"]}


def test_hand_counts_of_the_published_configuration():
    mc = spec.load_cell(CELL).model_fields
    D = 7168
    # q_a 7168 x 1536, q_b 1536 x 12288, kv_a 7168 x 576, kv_b 512 x 16384, o 8192 x 7168, norms 1536 + 512
    mla = D * 1536 + 1536 * 12288 + D * 576 + 512 * 16384 + 8192 * D + 1536 + 512
    assert flops_kimi.kv_b_params(mc) == 512 * 64 * 256 == 8388608
    assert flops_kimi.mixer_params(mc) == mla == 101124096  # 101.12 M
    assert flops_kimi.layer_params(mc, "dense") == mla + 2 * D + 3 * D * 18432 == 497500160
    expert = 3 * D * 2048
    assert expert == 44040192
    assert flops_kimi.layer_params(mc, "experts") == mla + 2 * D + D * 384 + 384 + expert + 12 * expert \
        == 676413824
    from datatunerx_tpu.models import init_params

    cfg = spec.register_preset(spec.load_cell(CELL))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert flops_kimi.total_params(mc) == leaves == 3496763904  # 3,496.8 M: 6.99 GB in bf16
    # a token caches 576 values a layer, stored 640 wide (whole lane tiles)
    assert flops_kimi.latent_row_bytes(mc) == 1152 and flops_kimi.stored_bytes_per_token(mc) == 5 * 640 * 2
    from datatunerx_tpu.ops.paged_attention import init_paged_cache, kv_leaf_keys

    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 16, 18432, 16, 768, dtype=jnp.bfloat16))
    assert kv_leaf_keys(cache) == ["k_mla"] and cache["k_mla"].shape == (5, 18432, 16, 640)
    assert int(np.prod(cache["k_mla"].shape)) * 2 == 18432 * 16 * 6400 == 1887436800  # 1.89 GB
    work = flops_kimi.mla_decode_step(mc, [6500] * 16)
    assert work["bytes"] == 5 * ((16 * 6500 + 16) * 1152 + 8388608 * 2)
    assert work["flops"] == 5 * (2 * 64 * 512 * 256 * 16 + 2 * 64 * 1088 * 16 * 6500)
    assert flops_kimi.decode_weight_bytes(mc, 12.0) == 2 * (
        flops_kimi.total_params(mc) - 5 * 2 * D - D - 20480 * D)  # all but the norms; the embedding is a gather


def test_the_readers_of_what_this_configuration_adds():
    import kimi_readers

    cell = spec.load_cell(CELL)
    empty = Observed(cell=cell, engine_info={"chunk": 8, "slots": 16})
    # no trace, or a program from before a model of several kinds took a prefix cache: nothing
    # to read, nothing raised
    for read in (kimi_readers.mla_decode_roofline, kimi_readers.prefill_mla_ms,
                 kimi_readers.shared_token_share, kimi_readers.decode_unscoped_share,
                 lambda o: kimi_readers.decode_region_ms(o, kimi_readers.ATTN)):
        assert read(empty) is None
    region = kimi_readers.moe_readers.region_of
    assert region("jit(f)/dtx.layers/while/body/dtx.attn/dot_general") in kimi_readers.ATTN
    assert region("jit(f)/dtx.layers/while/body/dtx.mla_absorb/dot_general") in kimi_readers.ATTN
    assert region("jit(f)/dtx.layers/while/body/dtx.moe_shared/dot_general") in kimi_readers.WEIGHTS
    assert region("jit(f)/dtx.layers/while/body/dtx.kv_write/scatter") in kimi_readers.KV_POOL
    stats = {"hits": 0, "extensions": 117, "cold": 40, "shared_tokens": 800, "prefilled_tokens": 200}
    obs = Observed(cell=cell, engine_info={"chunk": 8, "slots": 16, "prefix_stats": stats})
    assert kimi_readers.shared_token_share(obs) == pytest.approx(80.0)
    obs.engine_info["prefix_stats"] = dict.fromkeys(stats, 0)
    assert kimi_readers.shared_token_share(obs) is None  # nothing admitted in the window


def test_the_roofline_share_from_a_hand_made_decode(monkeypatch):
    """16 live slots at 6,500 tokens of context, 4.0 ms under ``dtx.attn``,
    ``dtx.mla_absorb`` and ``dtx.kv_write`` a token step: 5 x ((16 x 6,500 + 16)
    x 1,152 + 16.8 M) B at 819 GB/s is 0.834 ms, 20.8 %: memory-bound (the
    operations, 5 x (2.1 + 14.5) GFLOP, would take 0.42 ms)."""
    import kimi_readers

    cell = spec.load_cell(CELL)
    obs = Observed(cell=cell, engine_info={"chunk": 8, "slots": 16})
    obs.peaks = spec.peaks_for("TPU v5 lite")
    obs.trace_clock = (10.0, 14.0)
    monkeypatch.setattr(kimi_readers, "decode_region_ms", lambda o, regions: 4.0)
    monkeypatch.setattr(kimi_readers.readers, "live_requests", lambda o: [])
    monkeypatch.setattr(kimi_readers.readers, "decode_dispatches", lambda o: [11.0, 12.0])
    monkeypatch.setattr(kimi_readers.readers, "rows_at", lambda o, live, t: [(None, 6500.0)] * 16)
    least_ms = 5 * ((16 * 6500 + 16) * 1152 + 8388608 * 2) / obs.peaks["hbm_bytes_per_s"] * 1e3
    assert kimi_readers.mla_decode_roofline(obs) == pytest.approx(100 * least_ms / 4.0)
    assert 20.0 < kimi_readers.mla_decode_roofline(obs) < 21.5
    monkeypatch.setattr(kimi_readers, "decode_region_ms", lambda o, regions: None)
    assert kimi_readers.mla_decode_roofline(obs) is None  # a trace with none of the scopes


def test_every_new_metric_has_a_reader_that_finds_nothing_on_an_empty_run():
    cell = spec.load_cell(CELL)
    bench = spec.benchmark_json()
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW  # appended together, in the issue's order; later PRs append after
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert m["source"] == ("program_counter" if name == "prefix.shared_token_share" else "device_trace")
    assert {entries[n]["layer"] for n in NEW} == {"Model step, serve", "Latent attention", "Prefix cache",
                                                  "Expert feed-forward", "Device"}
    for name in SHARED + ["serve_tok_s"]:
        listed = entries.get(name) or next(m for m in bench["end_to_end"] if m["name"] == name)
        assert CELL in listed["workloads"]
    assert any(w["name"] == CELL and w["chips"] == 1 for w in bench["workloads"])
    for m in cell.per_layer:
        reader = spec.load_module("metrics", m["name"] + ".py")
        assert reader.read(Observed(cell=cell, engine_info={"chunk": 8, "slots": 16})) is None, m["name"]
