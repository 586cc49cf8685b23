"""Interpret-mode unit tests for the fused sampling epilogue
(ops/pallas_sampling.py): the Pallas kernel must reproduce the blocked-XLA
oracle token for token — greedy bitwise (shared max/compare tile walk),
sampled exactly under a fixed seed (both sides consume the same per-row
uniforms over the identical tile schedule) — and the oracle itself must
agree with the legacy sampler's semantics (``jnp.argmax`` ties, the
``sampling_probs`` distribution, the exact_topp nucleus). Engine-level
epilogue parity lives in test_speculative.py; these tests pin the
primitive."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.ops.pallas_sampling import (
    MODES,
    _kernel_sample,
    _prep,
    _tile_width,
    _xla_sample,
    default_impl,
    fused_sample,
    sample_rows,
)
from datatunerx_tpu.serving.speculative import sampling_probs


def _logits(key, s, v, scale=4.0):
    return jax.random.normal(key, (s, v)) * scale


def _keys(seed, s):
    return jax.vmap(jax.random.PRNGKey)(jnp.arange(seed, seed + s))


# ------------------------------------------------------- kernel vs oracle

@pytest.mark.parametrize("vocab", [256, 2048])
def test_greedy_kernel_matches_oracle_and_argmax(vocab):
    logits = _logits(jax.random.PRNGKey(0), 5, vocab)
    temps = jnp.zeros((5,))
    tp = jnp.ones((5,))
    kern = fused_sample(logits, temps, tp, None, mode="greedy",
                        impl="kernel", interpret=True)
    xla = fused_sample(logits, temps, tp, None, mode="greedy", impl="xla")
    ref = jnp.argmax(logits, axis=-1)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(ref))


def test_greedy_tie_rule_is_first_occurrence():
    # ties across tile boundaries: jnp.argmax takes the FIRST maximum;
    # both impls must agree (strict > across tiles, min-index within)
    v = 512
    logits = jnp.zeros((3, v))
    logits = logits.at[0, 7].set(5.0).at[0, 300].set(5.0)
    logits = logits.at[1, 130].set(2.0).at[1, 131].set(2.0)
    # row 2: all-equal row — argmax is index 0
    temps = jnp.zeros((3,))
    kern = fused_sample(logits, temps, jnp.ones((3,)), None, mode="greedy",
                        impl="kernel", interpret=True)
    xla = fused_sample(logits, temps, jnp.ones((3,)), None, mode="greedy",
                       impl="xla")
    ref = jnp.argmax(logits, axis=-1)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(ref))


@pytest.mark.parametrize("vocab", [256, 1000])
def test_simple_kernel_matches_oracle_fixed_seed(vocab):
    s = 6
    logits = _logits(jax.random.PRNGKey(1), s, vocab)
    temps = jnp.asarray([0.7, 1.0, 1.3, 0.5, 2.0, 0.9])
    tp = jnp.ones((s,))
    for seed in range(2):
        keys = _keys(100 + seed * s, s)
        kern = fused_sample(logits, temps, tp, keys, mode="simple",
                            impl="kernel", interpret=True)
        xla = fused_sample(logits, temps, tp, keys, mode="simple",
                           impl="xla")
        np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))


@pytest.mark.slow
def test_simple_greedy_rows_inside_sampled_batch():
    # slow: CI's kernel parity smoke step runs this file unfiltered.
    # temp <= 0 rows inside a "simple" batch resolve to argmax on both
    # sides regardless of the drawn uniform
    s, v = 4, 384
    logits = _logits(jax.random.PRNGKey(2), s, v)
    temps = jnp.asarray([0.0, 1.0, -1.0, 0.8])
    keys = _keys(7, s)
    kern = fused_sample(logits, temps, jnp.ones((s,)), keys, mode="simple",
                        impl="kernel", interpret=True)
    xla = fused_sample(logits, temps, jnp.ones((s,)), keys, mode="simple",
                       impl="xla")
    ref = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))
    assert int(kern[0]) == ref[0] and int(kern[2]) == ref[2]


def test_non_multiple_of_128_vocab_pads_dead():
    # pad lanes must never win: put the true max at the LAST real lane
    v = 130  # pads to 256
    logits = jnp.full((2, v), -3.0)
    logits = logits.at[:, v - 1].set(9.0)
    temps = jnp.asarray([0.0, 1.0])
    keys = _keys(3, 2)
    for mode, kk in (("greedy", None), ("simple", keys)):
        kern = fused_sample(logits, temps, jnp.ones((2,)), kk, mode=mode,
                            impl="kernel", interpret=True)
        xla = fused_sample(logits, temps, jnp.ones((2,)), kk, mode=mode,
                           impl="xla")
        np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))
        assert int(kern[0]) == v - 1
        assert 0 <= int(kern[1]) < v


# ------------------------------------- the wide walk: tiles, ragged tail

def _walk(logits, temps, us, mode="simple"):
    """Kernel (interpret) and twin over the same prepared rows with the
    uniforms given outright, so a test can place the CDF threshold."""
    temps = jnp.asarray(temps, jnp.float32)
    x, bn = _prep(logits, temps, mode=mode)
    us = jnp.asarray(us, jnp.float32)
    greedy = mode == "greedy"
    kern = _kernel_sample(x, temps, us, bn=bn, greedy=greedy, interpret=True)
    xla = _xla_sample(x, temps, us, bn=bn, greedy=greedy)
    return np.asarray(kern), np.asarray(xla), bn


# (rows, vocab): the cells' vocabulary in several tiles with a ragged last
# one at both slot counts, and vocabularies that are no multiple of 128 and
# wider than one tile
WIDE = [(4, 151936), (16, 151936), (16, 20000), (4, 50257)]


@pytest.mark.parametrize("s,vocab", WIDE)
def test_wide_greedy_kernel_matches_oracle_and_argmax(s, vocab):
    assert -(-vocab // _tile_width(s, -(-vocab // 128) * 128)) > 1
    assert vocab % _tile_width(s, -(-vocab // 128) * 128)
    logits = _logits(jax.random.PRNGKey(20), s, vocab)
    temps, tp = jnp.zeros((s,)), jnp.ones((s,))
    kern = fused_sample(logits, temps, tp, None, mode="greedy",
                        impl="kernel", interpret=True)
    xla = fused_sample(logits, temps, tp, None, mode="greedy", impl="xla")
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))
    np.testing.assert_array_equal(np.asarray(kern),
                                  np.asarray(jnp.argmax(logits, axis=-1)))


@pytest.mark.parametrize("s,vocab", WIDE)
def test_wide_simple_kernel_matches_oracle_fixed_seed(s, vocab):
    logits = _logits(jax.random.PRNGKey(21), s, vocab)
    temps = jnp.asarray(([0.7, 1.0, 1.3, 0.5] * s)[:s])
    tp = jnp.ones((s,))
    for seed in range(2):
        keys = _keys(300 + seed * s, s)
        kern = fused_sample(logits, temps, tp, keys, mode="simple",
                            impl="kernel", interpret=True)
        xla = fused_sample(logits, temps, tp, keys, mode="simple",
                           impl="xla")
        np.testing.assert_array_equal(np.asarray(kern), np.asarray(xla))
        assert np.all((np.asarray(kern) >= 0) & (np.asarray(kern) < vocab))


@pytest.mark.parametrize("mode", ["greedy", "simple"])
def test_max_in_masked_last_tile(mode):
    # the maximum is the last real lane of a ragged last tile: the lanes
    # past it (pad to 128, then whatever the block holds out of bounds)
    # must lose, and a sampled row whose mass sits there must land on it
    s, v = 16, 20000
    # (all negative, so a stray zero out of bounds would win the max)
    logits = jnp.full((s, v), -40.0).at[:, v - 1].set(-1.0)
    temps = jnp.where(jnp.arange(s) % 2 == 0, 0.0, 1.0)
    kern, xla, bn = _walk(logits, temps, jnp.full((s,), 0.5), mode)
    assert (v - 1) // bn == -(-v // bn) - 1 and v % bn
    np.testing.assert_array_equal(kern, xla)
    np.testing.assert_array_equal(kern, np.full((s,), v - 1))


@pytest.mark.parametrize("s", [4, 16])
def test_equal_maxima_across_tile_boundary_first_wins(s):
    v = 151936
    bn = _tile_width(s, v)
    logits = jnp.zeros((s, v))
    # row 0: the two lanes either side of the first boundary; row 1: a
    # later tile's first lane against an earlier tile's last; row 2: the
    # ragged last tile against the first
    logits = logits.at[0, bn - 1].set(4.0).at[0, bn].set(4.0)
    logits = logits.at[1, 3 * bn].set(2.0).at[1, 2 * bn - 1].set(2.0)
    logits = logits.at[2, v - 1].set(1.0).at[2, 17].set(1.0)
    kern, xla, got_bn = _walk(logits, jnp.zeros((s,)), jnp.zeros((s,)),
                              "greedy")
    assert got_bn == bn
    ref = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(kern, ref)
    np.testing.assert_array_equal(xla, ref)
    assert list(kern[:3]) == [bn - 1, 2 * bn - 1, 17]


@pytest.mark.parametrize("u", [1.0, 0.99999994])
def test_no_crossing_falls_back_to_argmax(u):
    # u*Z at (or rounding to) Z: no tile's running total passes it, so the
    # row returns its argmax instead of a lane past the vocabulary
    s, v = 4, 20000
    logits = _logits(jax.random.PRNGKey(22), s, v, scale=1.0)
    kern, xla, _ = _walk(logits, jnp.ones((s,)), jnp.full((s,), u))
    np.testing.assert_array_equal(kern, xla)
    ref = np.asarray(jnp.argmax(logits, axis=-1))
    if u == 1.0:
        np.testing.assert_array_equal(kern, ref)
    else:  # the largest f32 below 1 may still cross, in the last lanes
        assert np.all((kern == ref) | (kern > v - 64))


@pytest.mark.parametrize("s", [4, 16])
def test_greedy_row_in_sampled_batch_crossing_in_other_tile(s):
    # flat rows: the CDF is linear in the index, so u places the crossing.
    # Row 0 is greedy (argmax in the first tile) while its own u, and every
    # sampled row's, crosses in a later tile
    v = 151936
    bn = _tile_width(s, v)
    logits = jnp.zeros((s, v)).at[:, 5].set(1e-3)
    temps = jnp.ones((s,)).at[0].set(0.0)
    us = jnp.linspace(0.3, 0.97, s)
    kern, xla, _ = _walk(logits, temps, us)
    np.testing.assert_array_equal(kern, xla)
    assert kern[0] == 5
    want = np.asarray(us) * v
    assert np.all(np.abs(kern[1:] - want[1:]) < 64), (kern, want)
    assert np.all(kern[1:] // bn >= 1)
    assert len(set((kern[1:] // bn).tolist())) > 1


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, out)
    return out


@pytest.mark.parametrize("s,vocab,mode", [(16, 151936, "simple"),
                                          (16, 32000, "greedy")])
def test_grid_is_a_hundred_steps_and_operand_keeps_its_shape(s, vocab, mode):
    # the benchmark's two serving cells. The walk this replaced took
    # 56,976 and 2,000 grid steps here; and benchmarks/metrics/
    # fused_sample_roofline.py finds the kernel in a device trace as the
    # custom call that takes f32[slots,1,vocab]
    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    jaxpr = jax.make_jaxpr(
        lambda lg, t, p, k: fused_sample(lg, t, p, k, mode=mode,
                                         impl="kernel", interpret=True))(
        sds(s, vocab), sds(s), sds(s), sds(s, 2, dtype=jnp.uint32))
    calls = _pallas_calls(jaxpr.jaxpr, [])
    assert calls
    steps = 0
    for eqn in calls:
        assert eqn.params["name"] == "dtx_fused_sample"
        steps += int(np.prod(eqn.params["grid_mapping"].grid))
        operands = [(v.aval.shape, v.aval.dtype) for v in eqn.invars]
        assert ((s, 1, vocab), jnp.float32) in operands, operands
    assert steps < 200, steps


# -------------------------------------------- distribution-level exactness

@pytest.mark.slow
def test_simple_empirical_matches_sampling_probs():
    # the inverse-CDF draw must follow softmax(logits/t) — the same
    # distribution sampling_probs(top_p=1) describes. Tiny vocab, many
    # fixed-seed draws, loose 4-sigma gate.
    # slow: many-draw empirical sweep — CI's kernel parity smoke step
    # runs this file unfiltered.
    v, n = 8, 3000
    logits = jnp.asarray([[1.0, 2.0, 0.5, -1.0, 0.0, 1.5, -2.0, 0.2]])
    logits = jnp.pad(logits, ((0, 0), (0, 0)))  # [1, 8]
    temp = 0.9
    want = np.asarray(sampling_probs(logits[0], temp, 1.0))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    toks = fused_sample(jnp.tile(logits, (n, 1)), jnp.full((n,), temp),
                        jnp.ones((n,)), keys, mode="simple", impl="xla")
    counts = np.bincount(np.asarray(toks), minlength=v) / n
    for i in range(v):
        sigma = max((want[i] * (1 - want[i]) / n) ** 0.5, 1e-6)
        assert abs(counts[i] - want[i]) <= 4 * sigma + 0.01, (
            i, counts[i], want[i])


def test_topp_tokens_stay_in_nucleus_and_match_probs_support():
    v, n = 8, 800
    logits = jnp.asarray([1.0, 3.0, 0.5, -1.0, 2.0, -0.5, 0.0, -2.0])
    temp, top_p = 1.0, 0.6
    want = np.asarray(sampling_probs(logits, temp, top_p, exact_topp=True))
    support = set(np.nonzero(want > 0)[0].tolist())
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    toks = fused_sample(jnp.tile(logits[None], (n, 1)),
                        jnp.full((n,), temp), jnp.full((n,), top_p), keys,
                        mode="topp", impl="xla")
    got = set(np.asarray(toks).tolist())
    assert got <= support, (got, support)
    # empirical frequencies track the truncated distribution
    counts = np.bincount(np.asarray(toks), minlength=v) / n
    for i in support:
        sigma = max((want[i] * (1 - want[i]) / n) ** 0.5, 1e-6)
        assert abs(counts[i] - want[i]) <= 4 * sigma + 0.02


def test_topp_greedy_rows_and_top_p_one():
    s, v = 3, 320
    logits = _logits(jax.random.PRNGKey(5), s, v)
    temps = jnp.asarray([0.0, 1.0, 1.0])
    tps = jnp.asarray([0.5, 1.0, 0.4])
    keys = _keys(11, s)
    toks = fused_sample(logits, temps, tps, keys, mode="topp", impl="xla")
    assert int(toks[0]) == int(jnp.argmax(logits[0]))
    # top_p == 1 row: nucleus never cuts — token drawn from the full
    # softmax support
    assert 0 <= int(toks[1]) < v


# ------------------------------------------------------------ API contract

def test_sample_rows_preserves_legacy_rng_stream():
    # the migration payload carries per-slot rng: sample_rows must split
    # exactly like the legacy vmap(split) pair (slot 0 kept)
    s, v = 4, 256
    rng = jnp.stack([jax.random.PRNGKey(i) for i in range(s)])
    logits = _logits(jax.random.PRNGKey(9), s, v)
    temps = jnp.full((s,), 0.8)
    toks, new_rng = sample_rows(logits, temps, jnp.ones((s,)), rng,
                                mode="simple", impl="xla")
    split = jax.vmap(jax.random.split)(rng)
    np.testing.assert_array_equal(np.asarray(new_rng),
                                  np.asarray(split[:, 0]))
    want = fused_sample(logits, temps, jnp.ones((s,)), split[:, 1],
                        mode="simple", impl="xla")
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want))


def test_mode_validation_and_default_impl(monkeypatch):
    with pytest.raises(ValueError):
        fused_sample(jnp.zeros((1, 128)), jnp.zeros((1,)), jnp.ones((1,)),
                     None, mode="nope")
    assert set(MODES) == {"greedy", "simple", "topp"}
    monkeypatch.setenv("DTX_SAMPLING_EPILOGUE_KERNEL", "0")
    assert default_impl() == "xla"
    monkeypatch.setenv("DTX_SAMPLING_EPILOGUE_KERNEL", "1")
    assert default_impl() == "kernel"
    monkeypatch.delenv("DTX_SAMPLING_EPILOGUE_KERNEL")
    assert default_impl() == ("kernel" if jax.default_backend() == "tpu"
                              else "xla")
