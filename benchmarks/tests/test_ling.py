"""The configuration with linear-attention (KDA) and latent (MLA) layers,
group-limited routing and a shared expert (``bailing_hybrid``: Ling-3.0), at
sizes the CPU holds: the program against the plain reference on the
benchmark's own draws, what the decay gate's draw gives, the int8 control, two
broken runs that must come out ``correct: false``, the rehearsal cell, hand
counts for ``flops_ling.py`` and the readers of what this configuration adds."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops_ling
import flops_moe
import run as bench_run
import spec
from common import CompileCounter, Context, Observed

CELL = "ling-serve-decode"


def _ctx(cellname, seed, seconds):
    return Context(cell=spec.load_cell(cellname), seed=seed, seconds=seconds, trace=False,
                   on_cpu=True, device={"platform": "cpu", "kind": "cpu", "count": 1},
                   t_process=time.perf_counter(), trace_dir="", counter=CompileCounter())


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def debug():
    cell = spec.load_cell("rehearsal-ling")
    cfg = spec.register_preset(cell)
    weights = spec.load_module("weights_ling_v3.py")
    reference = spec.load_module("reference", "ling_v3.py")
    return cell, cfg, weights, reference


def test_the_program_agrees_with_the_reference_on_the_benchmarks_draws(debug):
    """float32 weights from the benchmark's draw, ``models.forward`` against the
    reference's full forward, base and one adapter, as logits. 5e-5: the chunk
    form solves a triangular system where the reference adds rank-one updates."""
    from datatunerx_tpu.models import forward

    cell, cfg, weights, reference = debug
    mc = cell.model_fields
    params = weights.draw_params(mc, 3000000019, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 3000000019, count=2, rank=4, targets=["q_proj", "o_proj"],
                             b_std=0.05)
    assert lora["run0"]["q_proj"]["b"].shape == (2, 1, 4, 4 * 16)       # a KDA run: H * d
    assert lora["run2"]["q_proj"]["b"].shape == (2, 1, 4, 4 * (16 + 8))  # the MLA run: H * (nope + rope)
    assert sorted(lora["run2"]) == ["o_proj", "q_proj"]
    tokens = np.random.default_rng(0).integers(10, mc["vocab_size"], size=90).tolist()
    one = jax.tree_util.tree_map(lambda a: a[1], lora)
    for ll, scale in ((None, 0.0), (one, 8.0)):
        want = reference.sequence_logits(params, mc, tokens, list(range(90)), ll, scale)
        got, _ = forward(params, jnp.asarray([tokens], jnp.int32), cfg,
                         lora=(({"layers": ll}, scale) if ll else None))
        np.testing.assert_allclose(got[0], want, atol=5e-5)
    # a tail of padding is inert, and the reference's own precision switch changes its answer
    padded = reference.sequence_logits(params, mc, tokens + [0] * 38, list(range(90)), one, 8.0,
                                       valid_len=90)
    np.testing.assert_allclose(padded, want, atol=1e-6)
    low = reference.sequence_logits(params, mc, tokens, list(range(90)), precision="int8")
    assert float(jnp.abs(low - want).max()) > 1e-3


def test_the_drawn_tree_is_the_programs_tree(debug):
    from datatunerx_tpu.models import init_params

    cell, cfg, weights, _ = debug
    drawn = jax.eval_shape(lambda: weights.draw_params(cell.model_fields, 1, dtype=jnp.float32))
    own = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(own)
    assert jax.tree_util.tree_map(lambda a: a.shape, drawn) == \
        jax.tree_util.tree_map(lambda a: a.shape, own)
    kda = drawn["layers"]["run0"]
    assert kda["A_log"].dtype == kda["dt_bias"].dtype == jnp.float32


@pytest.mark.parametrize("seed", [1, 2900000011])
def test_the_drawn_decay_neither_dies_in_a_token_nor_never_decays(seed):
    """``exp(g)`` over channels and tokens, from the drawn ``A_log``, ``Wf`` and
    ``dt_bias`` on normed inputs: median between 0.5 and 0.95, and a tenth of
    the channels on either side of (0.3, 0.9)."""
    cell = spec.load_cell("test-ling-serve")
    weights = spec.load_module("weights_ling_v3.py")
    mc = cell.model_fields
    p = weights.draw_params(mc, seed, dtype=jnp.float32)["layers"]["run1"]
    H, d, D = mc["num_heads"], mc["head_dim"], mc["hidden_size"]
    h = jax.random.normal(jax.random.PRNGKey(seed % 1000), (200, D)) * p["input_layernorm"]["scale"][0]
    f = (h @ p["f_proj"]["kernel"][0]).reshape(200, H, d)
    a = jnp.exp(p["A_log"][0])[None, :, None]
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    alpha = jnp.exp(-5.0 * jax.nn.sigmoid(a * (f + p["dt_bias"][0].reshape(1, H, d))))
    assert 0.5 < float(jnp.median(alpha)) < 0.95
    assert float(jnp.mean(alpha < 0.3)) > 0.03 and float(jnp.mean(alpha > 0.9)) > 0.03
    assert float(alpha.min()) > np.exp(-5.0)


def test_the_int8_control_fails_the_limit_the_sound_engine_passes():
    ctx = _ctx("test-ling-serve", 7, 6.0)
    kind = spec.load_module("traffic", "kinds", ctx.cell.kind + ".py")
    r = kind.readings(ctx, True)
    limits = ctx.cell.workload["check"]["limits"]
    assert r["sound"]["served_tokens"] >= 150 and r["failed"] == 0  # 350-520 by the machine's load
    assert r["sound"]["gap_mean"] <= limits["gap_mean"] < r["control"]["gap_mean"], r
    assert r["sound"]["gap_max"] <= limits["gap_max"]


def test_the_rehearsal_cell_is_correct_and_reports_no_device_metric(capsys):
    assert bench_run.main(["--workload", "rehearsal-ling", "--seed", "3000000007",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu" and out["rehearsal"] is True


@pytest.mark.parametrize("fault,change", [
    ("one group kept", {"topk_group": 1}),
    ("a decay gate bounded at -1", {"kda_lower_bound": -1.0}),
])
def test_a_fault_in_what_this_configuration_adds_makes_a_run_incorrect(capsys, monkeypatch, fault, change):
    """Two faults: the router keeps one group where the published one keeps
    ``topk_group`` (2 of 4 at this size; seeds 21 and 22 read ``gap_mean``
    2.6e-3 and 3.2e-3 against the limit of 1.8e-3. Keeping EVERY group reads
    1.6e-3 and 1.7e-3 where the sound program reads 1.0e-3: with a quarter of
    the experts held, too few pairs change for this size to show it); the decay
    gate's lower bound is -1, so a channel forgets at most ``e^-1`` a token
    where the published one may forget ``e^-5``.
    (A recurrent state stored in bfloat16 is NOT such a fault at this size: on
    seeds 21 and 22 it reads ``gap_mean`` 9.6e-4 and 7.5e-4 where the float32
    state reads 9.8e-4 and 1.14e-3: the engine's bf16 activations hide it. The
    state's type is held by ``tests/test_ling_model.py``, not by the gaps.)"""
    real = spec.register_preset
    monkeypatch.setattr(spec, "register_preset", lambda cell, **kw: real(cell, **dict(kw, **change)))
    assert bench_run.main(["--workload", "test-ling-serve", "--seed", "21",
                           "--seconds", "4", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False and out["failed"] == 0, fault


def test_the_chip_cell_refuses_the_cpu_and_its_config_is_the_published_one():
    assert bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 3
    cell = spec.load_cell(CELL)
    pub, mc = cell.config, cell.model_fields
    for key, field in (("head_dim", "head_dim"), ("v_head_dim", "v_head_dim"),
                       ("kv_lora_rank", "kv_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
                       ("qk_rope_head_dim", "qk_rope_head_dim"), ("rotary_dim", "qk_rope_head_dim"),
                       ("short_conv_kernel_size", "kda_conv_kernel"), ("kda_lower_bound", "kda_lower_bound"),
                       ("moe_intermediate_size", "expert_intermediate_size"),
                       ("moe_shared_expert_intermediate_size", "shared_expert_intermediate_size"),
                       ("num_experts_per_tok", "experts_per_token"), ("n_group", "n_group"),
                       ("topk_group", "topk_group"), ("routed_scaling_factor", "routed_scaling_factor"),
                       ("norm_topk_prob", "norm_topk_prob")):
        assert pub[key] == mc[field], key
    assert pub["qk_head_dim"] == mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]
    assert pub["num_experts"] == mc["experts_held"] == 64 and mc["experts_total"] == 512
    assert mc["experts_total"] // mc["n_group"] == mc["experts_held"]  # one routing group a chip
    held = pub["reduced"]["num_hidden_layers"]["layers"]
    period, dense = pub["layer_group_size"], pub["first_k_dense_replace"]
    assert ["mla" if (i + 1) % period == 0 else "kda" for i in held] == mc["layer_types"]
    assert ["dense" if i < dense else "experts" for i in held] == mc["ffn_types"]
    # the clamp is not implemented: every held layer's published limit is 0, and the program is told so
    assert [pub["expert_swiglu_limit_list"][i] for i in held] == mc["expert_swiglu_limits"] == [0] * 7
    assert [pub["share_expert_swiglu_limit_list"][i] for i in held] == [0] * 7
    with pytest.raises(NotImplementedError, match="SwiGLU"):
        spec.register_preset(cell, expert_swiglu_limits=[0, 0, 0, 0, 0, 0, 4])
    every = {m["name"] for m in cell.per_layer}
    assert {"kda_state_roofline", "step.decode_kda_state_ms", "moe.rows_routed_here_share",
            "engine.gap_admit_ms.batch", "idle_share.serve_ling"} <= every and len(every) == 20
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    t = cell.traffic
    assert (t["clients"], t["requests"]) == (256, 2048) and cell.workload["engine"]["slots"] == 128
    assert cell.workload["adapters"]["targets"] == ["q_proj", "o_proj"]


def test_hand_counts_of_the_published_configuration():
    mc = spec.load_cell(CELL).model_fields
    D, Hd = 2560, 32 * 128
    # KDA: q, k, v, f, g (D x 4096 each), o, b (D x 32), conv 12288 x 4, A_log 32, dt_bias 4096, head norm 128
    assert flops_ling.kda_params(mc) == 5 * D * Hd + Hd * D + D * 32 + 12288 * 4 + 32 + 4096 + 128
    # MLA: q D x 32*192, kv_a D x 576, its norm 512, kv_b 512 x 32*256, gate D x 32, o
    assert flops_ling.mla_params(mc) == D * 6144 + D * 576 + 512 + 512 * 8192 + D * 32 + Hd * D
    assert flops_moe.expert_params(mc) == 3 * D * 768 == 5898240
    assert flops_ling.shared_expert_params(mc) == 5898240
    assert flops_moe.router_params(mc) == D * 512 + 512
    from datatunerx_tpu.models import init_params

    cfg = spec.register_preset(spec.load_cell(CELL))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert flops_ling.total_params(mc) == leaves
    assert 3.56e9 < leaves < 3.58e9  # 7.14 GB in bf16
    assert flops_ling.latent_bytes_per_token(mc) == 576 * 2 == 1152
    per_slot = 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert flops_ling.state_bytes_per_slot(mc) == per_slot == 2097152 + 73728
    # what the engine's state leaves hold: six KDA layers x 128 slots
    from datatunerx_tpu.ops.paged_attention import init_paged_cache, state_leaf_keys

    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 128, 64, 16, 96, dtype=jnp.bfloat16))
    assert sum(int(np.prod(cache[k].shape)) * cache[k].dtype.itemsize
               for k in state_leaf_keys(cache)) == 6 * 128 * per_slot
    assert cache["k_mla"].shape == (1, 64, 16, 576) and "v_mla" not in cache
    work = flops_ling.kda_state_step(mc, 100.0)
    assert work["bytes"] == 2 * per_slot * 100 and work["flops"] == 2 * 4 * 32 * 128 * 128 * 100
    a = flops_ling.mla_decode_step(mc, [100, 1000])
    assert a["bytes"] == 576 * 1100 * 2 + 2 * 32 * (1024 + 64) * 2
    assert flops_ling.decode_weight_bytes(mc, 64) == 2 * (
        flops_ling.total_params(mc) - D * 157184 - 7 * 2 * D - D)  # all but embedding and layer norms


def test_the_readers_of_what_this_configuration_adds(debug):
    import ling_readers

    cell = spec.load_cell(CELL)
    obs = Observed(cell=cell, engine_info={"chunk": 8, "slots": 128, "moe_stats": {
        "decode_local_rows": 7680, "decode_experts_hit": 3300, "decode_max_rows": 500,
        "decode_layer_steps": 60, "decode_rows_here": 450, "decode_rows": 1000}})
    assert ling_readers.rows_routed_here_share(obs) == 45.0
    assert ling_readers.rows_per_held_expert(obs) == 7680 / (60 * 64)
    assert ling_readers.load_max_over_mean(obs) == 500 * 64 / 7680
    assert ling_readers.kda_state_roofline(obs) is None  # no trace
    # a program from before it had these layers or counters: nothing to read, nothing raised
    old = Observed(cell=cell, engine_info={"chunk": 8, "slots": 128, "moe_stats": {
        "decode_local_rows": 10, "decode_experts_hit": 3, "decode_max_rows": 5, "decode_layer_steps": 6}})
    for read in (ling_readers.rows_routed_here_share, ling_readers.kda_state_roofline,
                 ling_readers.prefill_kda_ms, ling_readers.live_slots,
                 lambda o: ling_readers.kda_region_ms(o, ling_readers.KDA_STATE)):
        assert read(old) is None
    assert ling_readers.moe_readers.region_of(
        "jit(f)/dtx.layers/while/body/dtx.kda_state/jit(_where)/select_n") == "dtx.kda_state"
    assert ling_readers.moe_readers.region_of("jit(f)/dtx.layers/while/body/dtx.moe_shared/dot") in ling_readers.WEIGHTS


def test_the_roofline_share_from_a_hand_made_decode(monkeypatch):
    """128 live slots, 6 KDA layers, 13.9 ms under ``dtx.kda_state`` a token
    step: 2 x 128 x 2,170,880 B x 6 layers at 819 GB/s is 4.07 ms, 29.3 %."""
    import ling_readers

    cell = spec.load_cell(CELL)
    obs = Observed(cell=cell, engine_info={"chunk": 8, "slots": 128})
    obs.peaks = spec.peaks_for("TPU v5 lite")
    monkeypatch.setattr(ling_readers, "decode_region_ms", lambda o, regions: 13.9)
    monkeypatch.setattr(ling_readers.readers, "decode_occupancy", lambda o: 100.0)
    least_ms = 2 * 128 * 2170880 * 6 / obs.peaks["hbm_bytes_per_s"] * 1e3
    assert ling_readers.kda_state_roofline(obs) == pytest.approx(100 * least_ms / 13.9)
    assert 29.0 < ling_readers.kda_state_roofline(obs) < 29.6


def test_every_new_metric_has_a_reader_that_finds_nothing_on_an_empty_run():
    cell = spec.load_cell(CELL)
    assert len(cell.per_layer) == 20
    for m in cell.per_layer:
        reader = spec.load_module("metrics", m["name"] + ".py")
        assert reader.read(Observed(cell=cell, engine_info={"chunk": 8, "slots": 128})) is None, m["name"]
