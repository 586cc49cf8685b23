"""The configuration with state-space (Mamba-2) layers beside attention layers
without positions, Granite's four multipliers, a dense feed-forward and a tied
head (``granitemoehybrid``: granite-4.0-h-micro), at sizes the CPU holds: the
program against the plain reference on the benchmark's own draws, what the
draw of Mamba's decay gives, the int8 control, two broken runs that must come
out ``correct: false``, the rehearsal cell, hand counts for ``flops_granite.py``
and the readers of what this configuration adds."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops_granite
import run as bench_run
import spec
from common import CompileCounter, Context, Observed

CELL = "granite-serve-chat"
NEW = ["step.decode_ms.granite", "step.prefill_chunk_ms.granite", "step.decode_ssm_state_ms",
       "step.decode_ssm_out_ms", "step.prefill_ssm_ms", "step.decode_attn_ms.granite",
       "step.decode_kv_pool_ms.granite", "step.decode_weights_ms.granite",
       "step.decode_unscoped_share.granite", "ssm_state_roofline", "idle_share.serve_granite"]
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
    cell = spec.load_cell("rehearsal-granite")
    cfg = spec.register_preset(cell)
    weights = spec.load_module("weights_granite_v4.py")
    reference = spec.load_module("reference", "granite_v4.py")
    return cell, cfg, weights, reference


def test_the_program_agrees_with_the_reference_on_the_benchmarks_draws(debug):
    """float32 weights from the benchmark's draw, ``models.forward`` against the
    reference's full forward, base and one adapter, as logits. 1e-6 of logits of
    a few hundredths: the chunk form sums one masked product where the
    reference adds rank-one updates token by token."""
    from datatunerx_tpu.models import forward

    cell, cfg, weights, reference = debug
    mc = cell.model_fields
    params = weights.draw_params(mc, 3000000019, dtype=jnp.float32)
    lora = weights.draw_lora(mc, 3000000019, count=2, rank=4, targets=["q_proj", "in_proj", "o_proj"],
                             b_std=0.05)
    assert lora["run0"]["in_proj"]["b"].shape == (2, 2, 4, 2 * 128 + 2 * 32 + 8)  # a Mamba run: [z | x B C | dt]
    assert lora["run0"]["o_proj"]["a"].shape == (2, 2, 128, 4)
    assert sorted(lora["run0"]) == ["in_proj", "o_proj"] and sorted(lora["run1"]) == ["o_proj", "q_proj"]
    tokens = np.random.default_rng(0).integers(10, mc["vocab_size"], size=90).tolist()
    one = jax.tree_util.tree_map(lambda a: a[1], lora)
    for ll, scale in ((None, 0.0), (one, 8.0)):
        want = reference.sequence_logits(params, mc, tokens, list(range(90)), ll, scale)
        got, _ = forward(params, jnp.asarray([tokens], jnp.int32), cfg,
                         lora=(({"layers": ll}, scale) if ll else None))
        assert float(jnp.abs(want).max()) > 0.05
        np.testing.assert_allclose(got[0], want, atol=1e-6)
    base = reference.sequence_logits(params, mc, tokens, list(range(90)))
    assert float(jnp.abs(base - want).max()) > 1e-3  # the adapter carries weight
    # a tail of padding is inert, and the reference's own precision switch changes its answer
    padded = reference.sequence_logits(params, mc, tokens + [0] * 38, list(range(90)), one, 8.0,
                                       valid_len=90)
    np.testing.assert_allclose(padded, want, atol=1e-7)
    low = reference.sequence_logits(params, mc, tokens, list(range(90)), one, 8.0, precision="int8")
    assert float(jnp.abs(low - want).max()) > 1e-3


def test_the_drawn_tree_is_the_programs_tree(debug):
    from datatunerx_tpu.models import init_params

    cell, cfg, weights, _ = debug
    drawn = jax.eval_shape(lambda: weights.draw_params(cell.model_fields, 1, dtype=jnp.bfloat16))
    own = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(own)
    assert jax.tree_util.tree_map(lambda a: a.shape, drawn) == \
        jax.tree_util.tree_map(lambda a: a.shape, own)
    assert "lm_head" not in drawn  # the head is the tied embedding
    ssm = drawn["layers"]["run0"]
    assert ssm["A_log"].dtype == ssm["dt_bias"].dtype == ssm["D"].dtype == jnp.float32
    assert ssm["conv"]["bias"].shape == (2, 8 * 16 + 2 * 32)


@pytest.mark.parametrize("seed", [1, 2900000011])
def test_the_drawn_memory_neither_dies_nor_freezes(seed):
    """A head's decay a token is ``exp(-dt |A|)``, ``dt = softplus(n W_dt +
    dt_bias)``. From the drawn ``A_log``, ``dt_bias`` and ``in_proj`` on normed
    inputs, a head forgets (``a ** n < 1 / e`` at its mean ``dt``) within 10
    tokens in a third to two thirds of the heads, within 100 in four fifths and
    more, within 1,000 in all, and within ONE token in under a sixth."""
    cell = spec.load_cell("test-granite-serve")
    weights = spec.load_module("weights_granite_v4.py")
    mc = cell.model_fields
    p = weights.draw_params(mc, seed, dtype=jnp.float32)["layers"]["run2"]
    H, D = mc["ssm_heads"], mc["hidden_size"]
    A, bias = jnp.exp(p["A_log"]), p["dt_bias"]  # [3 layers, H]
    assert 1.0 <= float(A.min()) and float(A.max()) <= 16.0
    dt0 = jax.nn.softplus(bias)
    assert 0.001 <= float(dt0.min()) and float(dt0.max()) <= 0.1 + 1e-6
    h = jax.random.normal(jax.random.PRNGKey(seed % 1000), (200, D))
    raw = jnp.einsum("td,ldh->tlh", h, p["in_proj"]["kernel"][:, :, -H:])
    horizon = 1.0 / (jnp.mean(jax.nn.softplus(raw + bias[None]), axis=0) * A)  # tokens to 1/e
    assert 0.33 < float(jnp.mean(horizon < 10)) < 0.67
    assert float(jnp.mean(horizon < 100)) > 0.8 and float(horizon.max()) < 1000
    assert float(jnp.mean(horizon < 1)) < 0.17
    assert abs(float(jnp.mean(p["D"])) - 1.0) < 0.02 and float(jnp.std(p["conv"]["kernel"])) > 0.4


def test_the_int8_control_fails_the_limits_the_sound_engine_passes():
    ctx = _ctx("test-granite-serve", 7, 6.0)
    kind = spec.load_module("traffic", "kinds", ctx.cell.kind + ".py")
    r = kind.readings(ctx, True)
    limits = ctx.cell.workload["check"]["limits"]
    assert r["sound"]["served_tokens"] >= 150 and r["failed"] == 0
    assert r["sound"]["gap_mean"] <= limits["gap_mean"] < r["control"]["gap_mean"], r
    assert r["sound"]["gap_max"] <= limits["gap_max"] < r["control"]["gap_max"], r


def test_the_rehearsal_cell_is_correct_and_reports_no_device_metric(capsys):
    assert bench_run.main(["--workload", "rehearsal-granite", "--seed", "3000000007",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu" and out["rehearsal"] is True


@pytest.mark.parametrize("fault,change", [
    ("the embedding unscaled", {"embedding_multiplier": 1.0}),
    ("branches join the stream unscaled", {"residual_multiplier": 1.0}),
])
def test_a_fault_in_what_this_configuration_adds_makes_a_run_incorrect(capsys, monkeypatch, fault, change):
    """Two of Granite's multipliers at their defaults in the PROGRAM, the
    reference as published: the embedding enters the stream whole where this
    size's configuration says 0.5 (seed 21 reads ``gap_mean`` 2.2e-3 against the
    limit of 1.2e-4), and every branch is added whole where it says 0.22.
    (Two faults this size does NOT show: the attention layer's scores times
    ``32 ** -0.5`` for 0.0625, or its heads rotated, read ``gap_mean`` 3.9e-5
    beside the sound 3.7e-5, one attention layer of six adding little at
    weights of normal 0.02; and ``logits_scaling`` moves no choice of a token.
    All three are held on the logits by ``tests/test_granite_model.py``.)"""
    real = spec.register_preset
    monkeypatch.setattr(spec, "register_preset", lambda cell, **kw: real(cell, **dict(kw, **change)))
    assert bench_run.main(["--workload", "test-granite-serve", "--seed", "21",
                           "--seconds", "4", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False and out["failed"] == 0, fault


def test_the_chip_cell_refuses_the_cpu_and_its_config_is_the_published_one():
    assert bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 3
    cell = spec.load_cell(CELL)
    pub, mc = cell.config, cell.model_fields
    assert pub["reduced"] == {} and pub["source"].endswith("granite-4.0-h-micro/blob/main/config.json")
    listed = next(c for c in spec.benchmark_json()["configs"] if c["name"] == cell.config_name)
    assert listed["reduced"] == [] and listed["source"] == pub["source"]
    for key, field in (("mamba_n_heads", "ssm_heads"), ("mamba_d_head", "ssm_head_dim"),
                       ("mamba_d_state", "ssm_state"), ("mamba_n_groups", "ssm_groups"),
                       ("mamba_d_conv", "ssm_conv_kernel"), ("mamba_expand", "ssm_expand"),
                       ("embedding_multiplier", "embedding_multiplier"),
                       ("attention_multiplier", "attention_multiplier"),
                       ("residual_multiplier", "residual_multiplier"),
                       ("logits_scaling", "logits_scaling"),
                       ("shared_intermediate_size", "intermediate_size"),
                       ("attention_bias", "attention_bias")):
        assert pub[key] == mc[field], key
    assert mc["head_dim"] * pub["num_attention_heads"] == pub["hidden_size"]
    assert pub["mamba_n_heads"] * pub["mamba_d_head"] == pub["mamba_expand"] * pub["hidden_size"]
    assert pub["mamba_chunk_size"] == cell.workload["engine"]["prefill_chunk"] == 256
    assert pub["position_embedding_type"] == "nope" and mc["partial_rotary_factor"] == 0.0
    assert pub["num_local_experts"] == pub["num_experts_per_tok"] == 0 and "ffn_types" not in mc
    assert pub["mamba_conv_bias"] is True and pub["mamba_proj_bias"] is False
    assert [{"mamba": "ssm", "attention": "global"}[t] for t in pub["layer_types"]] == mc["layer_types"]
    assert len(mc["layer_types"]) == pub["num_hidden_layers"] == 40 and mc["layer_types"].count("ssm") == 36
    every = [m["name"] for m in cell.per_layer]
    assert sorted(every) == sorted(NEW + SHARED)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s", "setup_s"]
    t, e = cell.traffic, cell.workload["engine"]
    assert (t["clients"], t["requests"], t["kind"]) == (128, 2048, "closed-loop-arch")
    assert (t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]) == (32, 512)
    assert (t["output_tokens"]["min"], t["output_tokens"]["max"]) == (64, 512)
    assert t["temperature"] == 0.0 and abs(t["base_share"] - 1 / 3) < 1e-3
    assert t["clients"] == 2 * e["slots"] and e["kv_blocks"] == 64 * e["slots"]
    assert e["max_seq_len"] == t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
    assert cell.workload["adapters"] == {"count": 2, "rank": 8, "alpha": 32.0,
                                         "targets": ["q_proj", "in_proj", "o_proj"]}


def test_hand_counts_of_the_published_configuration():
    mc = spec.load_cell(CELL).model_fields
    D = 2048
    # Mamba-2: in_proj D x (4096 + 4352 + 64), out_proj 4096 x D, conv 4352 x 4 + 4352, A_log, D, dt_bias 64 each, norm 4096
    assert flops_granite.ssm_params(mc) == D * 8512 + 4096 * D + 4352 * 4 + 4352 + 3 * 64 + 4096 == 25847232
    # attention: q D x 2048, k and v D x 512 each, o
    assert flops_granite.attention_params(mc) == 2 * D * 2048 + 2 * D * 512 == 10485760
    assert flops_granite.dense_ffn_params(mc) == 3 * D * 8192 == 50331648
    from datatunerx_tpu.models import init_params

    cfg = spec.register_preset(spec.load_cell(CELL))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert flops_granite.total_params(mc) == leaves == 3191396096  # 3,192 M: 6.38 GB in bf16
    assert flops_granite.kv_bytes_per_token(mc) == 4 * 2 * 8 * 64 * 2 == 8192
    per_layer = 64 * 64 * 128 * 4 + 3 * 4352 * 2
    assert flops_granite.state_bytes_per_slot_layer(mc) == per_layer == 2097152 + 26112
    assert flops_granite.state_bytes_per_slot(mc) == 36 * per_layer == 76437504  # 76.4 MB a slot
    # what the engine's state leaves hold: 36 Mamba-2 layers x 64 slots
    from datatunerx_tpu.ops.paged_attention import init_paged_cache, state_leaf_keys

    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 64, 4096, 16, 64, dtype=jnp.bfloat16))
    assert state_leaf_keys(cache) == ["state_ssm", "state_ssm_conv"]
    assert cache["state_ssm"].shape == (36, 64, 64, 64, 128) and cache["state_ssm"].dtype == jnp.float32
    assert sum(int(np.prod(cache[k].shape)) * cache[k].dtype.itemsize
               for k in state_leaf_keys(cache)) == 64 * 76437504
    assert cache["k_global"].shape == cache["v_global"].shape == (4, 4096, 16, 512)
    work = flops_granite.ssm_state_step(mc, 50.0)
    assert work["bytes"] == 2 * 76437504 * 50 and work["flops"] == 2 * 3 * 64 * 64 * 128 * 50 * 36
    a = flops_granite.attention_decode_step(mc, [100, 1000])
    assert a["bytes"] == 2 * 512 * 1100 * 2 + 2 * 2 * 2048 * 2
    assert flops_granite.decode_weight_bytes(mc) == 2 * (
        flops_granite.total_params(mc) - 40 * 2 * D - D)  # all but the norms; the embedding once, as the head


def test_the_readers_of_what_this_configuration_adds():
    import granite_readers

    cell = spec.load_cell(CELL)
    empty = Observed(cell=cell, engine_info={"chunk": 8, "slots": 64})
    # no trace, or a program from before it had these layers: nothing to read, nothing raised
    for read in (granite_readers.ssm_state_roofline, granite_readers.prefill_ssm_ms,
                 granite_readers.live_slots, granite_readers.decode_unscoped_share,
                 lambda o: granite_readers.ssm_region_ms(o, granite_readers.SSM_STATE),
                 lambda o: granite_readers.ssm_region_ms(o, granite_readers.SSM_OUT)):
        assert read(empty) is None
    region = granite_readers.moe_readers.region_of
    assert region("jit(f)/dtx.layers/while/body/dtx.ssm_state/jit(_where)/select_n") == "dtx.ssm_state"
    assert region("jit(f)/dtx.layers/while/body/dtx.ssm_conv/add") in granite_readers.SSM_STATE
    assert region("jit(f)/dtx.layers/while/body/dtx.ssm_out/mul") in granite_readers.SSM_OUT
    assert region("jit(f)/dtx.layers/while/body/dtx.qkv/dot_general") in granite_readers.WEIGHTS


def test_the_roofline_share_from_a_hand_made_decode(monkeypatch):
    """64 live slots, 36 Mamba-2 layers, 20.0 ms under ``dtx.ssm_state`` a token
    step: 2 x 64 x 76,437,504 B at 819 GB/s is 11.95 ms, 59.7 %."""
    import granite_readers

    cell = spec.load_cell(CELL)
    obs = Observed(cell=cell, engine_info={"chunk": 8, "slots": 64})
    obs.peaks = spec.peaks_for("TPU v5 lite")
    monkeypatch.setattr(granite_readers, "decode_region_ms", lambda o, regions: 20.0)
    monkeypatch.setattr(granite_readers.readers, "decode_occupancy", lambda o: 100.0)
    least_ms = 2 * 64 * 76437504 / obs.peaks["hbm_bytes_per_s"] * 1e3
    assert granite_readers.ssm_state_roofline(obs) == pytest.approx(100 * least_ms / 20.0)
    assert 59.0 < granite_readers.ssm_state_roofline(obs) < 60.5


def test_every_new_metric_has_a_reader_that_finds_nothing_on_an_empty_run():
    cell = spec.load_cell(CELL)
    entries = {m["name"]: m for m in spec.benchmark_json()["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s" and m["source"] == "device_trace"
    assert {entries[n]["layer"] for n in NEW} == {"Model step, serve", "State-space state", "Device"}
    for name in SHARED:
        assert entries[name]["workloads"][-1] == CELL
    for m in cell.per_layer:
        reader = spec.load_module("metrics", m["name"] + ".py")
        assert reader.read(Observed(cell=cell, engine_info={"chunk": 8, "slots": 64})) is None, m["name"]
