"""The Mamba-2 token step kernel (ops/pallas_ssm.py) in interpret mode against
``ssm.state_step``: ``y`` and the layer's state to float32 rounding, the rest
of the cache leaf bit for bit, and the static rule that picks between them."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datatunerx_tpu.ops import pallas_ssm, ssm

L, B, H, P, N = 3, 4, 8, 8, 128
FRESH, IDLE = 1, 2  # slot 1 starts from nothing, slot 2's row is idle


def _leaf(seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (L, B, H, P, N), jnp.float32)


def _operands(G, seed=1, T=1):
    """x, B, C, dt, dA as ``ssm_mixer`` holds them (``[B, T, ...]``), D, fresh;
    slot ``IDLE`` carries ``dt`` 0 and ``dA`` 0."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(B, T, H)), jnp.float32)
    dA = -dt * jnp.asarray(rng.uniform(1, 16, size=(H,)), jnp.float32)
    dt, dA = dt.at[IDLE].set(0.0), dA.at[IDLE].set(0.0)
    fresh = jnp.arange(B) == FRESH
    return (f(B, T, H, P), f(B, T, G, N), f(B, T, G, N), dt, dA, f(H)), fresh


@jax.jit
def _xla(leaf, layer, fresh, x, Bm, Cm, dt, dA, D):
    """What ``ssm_mixer`` does without the kernel."""
    state = jnp.where(fresh[:, None, None, None], 0.0, leaf[layer])
    y, state = ssm.state_step(state, x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], dA[:, 0], D)
    return y[:, None], leaf.at[layer].set(state)


# every layer index, one and several groups, one and several head tiles
@pytest.mark.parametrize("layer,G,th", [(0, 1, 8), (1, 1, 8), (2, 1, 8), (1, 2, 8), (0, 4, 4),
                                        (2, 1, 2), (1, 8, 1), (2, 2, 4)])
def test_kernel_equals_state_step_and_touches_one_layer(layer, G, th):
    ops, fresh = _operands(G, seed=layer + G)
    leaf = _leaf()
    # what an earlier request left in the fresh slot must not reach y: not even a NaN
    leaf = leaf.at[:, FRESH, 0, 0, :5].set(jnp.nan).at[:, FRESH, 1].set(1e30)
    got_y, got = jax.jit(lambda leaf, li: pallas_ssm.ssm_step(leaf, li, fresh, *ops, th=th))(
        leaf, jnp.asarray(layer, jnp.int32))
    want_y, want = _xla(leaf, layer, fresh, *ops)
    assert got_y.shape == want_y.shape == (B, 1, H, P) and got_y.dtype == jnp.float32
    assert bool(jnp.isfinite(got_y).all()) and bool(jnp.isfinite(got[layer]).all())
    # the read-out sums 128 products in another order: a few ulp of its largest partial sums (y reaches 40)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-6, atol=5e-5)
    np.testing.assert_allclose(got[layer], want[layer], rtol=1e-6, atol=1e-6)
    # the idle row's state, every other layer and so every byte outside the step: bit for bit
    np.testing.assert_array_equal(got[layer, IDLE], leaf[layer, IDLE])
    for other in set(range(L)) - {layer}:
        np.testing.assert_array_equal(got[other], leaf[other])


def test_a_fresh_slot_reads_as_zero_whatever_the_leaf_holds():
    ops, fresh = _operands(1)
    step = jax.jit(lambda leaf: pallas_ssm.ssm_step(leaf, 1, fresh, *ops, th=4))
    y0, s0 = step(_leaf(0))
    y1, s1 = step(_leaf(0).at[1, FRESH].set(7.0))
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(s0[1], s1[1])
    x, Bm, _, dt, _, _ = ops  # the fresh slot's new state is the rank-one update alone
    np.testing.assert_allclose(
        s0[1, FRESH], (dt[FRESH, 0, :, None] * x[FRESH, 0])[..., None] * Bm[FRESH, 0, 0], rtol=1e-6)


@pytest.mark.parametrize("G", [1, 2])
def test_eight_steps_under_a_scan_equal_the_recurrence(G):
    (x, Bm, Cm, dt, dA, D), _ = _operands(G, seed=5, T=8)
    leaf, layer = _leaf(3), 1
    never = jnp.zeros((B,), bool)

    @jax.jit
    def run(leaf):
        def body(leaf, xs):
            y, leaf = pallas_ssm.ssm_step(leaf, layer, never, *(a[:, None] for a in xs), D, th=4)
            return leaf, y[:, 0]

        leaf, ys = jax.lax.scan(body, leaf, tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt, dA)))
        return jnp.moveaxis(ys, 0, 1), leaf

    got_y, got = run(leaf)
    want_y, want = ssm.recurrence(leaf[layer], x, Bm, Cm, dt, dA, D)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[layer], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[0], leaf[0])
    np.testing.assert_array_equal(got[layer, IDLE], leaf[layer, IDLE])


def _shape(H, P, N, dtype=jnp.float32, layers=L, slots=B):
    return jax.ShapeDtypeStruct((layers, slots, H, P, N), dtype)


@pytest.mark.parametrize("case,leaf,tokens,want", [
    ("granite_cell", _shape(64, 64, 128, layers=36, slots=64), 1, ("dtx_ssm_step", 64)),
    ("small_eligible", _shape(H, P, N), 1, ("dtx_ssm_step", 8)),
    ("block_cap_divides_heads", _shape(96, 64, 128), 1, ("dtx_ssm_step", 48)),
    ("state_of_two_lane_tiles", _shape(24, 64, 256), 1, ("dtx_ssm_step", 24)),
    ("debug_granite_state_32", _shape(8, 16, 32), 1, ("xla", None)),
    ("head_dim_off_the_sublanes", _shape(8, 12, 128), 1, ("xla", None)),
    ("bfloat16_leaf", _shape(H, P, N, jnp.bfloat16), 1, ("xla", None)),
    ("prefill_chunk", _shape(H, P, N), 256, ("xla", None)),
    ("two_tokens", _shape(H, P, N), 2, ("xla", None)),
    ("no_cache", None, 1, ("xla", None)),
])
def test_static_shapes_choose_the_kernel(case, leaf, tokens, want):
    assert pallas_ssm.step_kernel(leaf, tokens) == want


def test_the_gauge_says_which_step_an_engine_runs():
    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats

    reg = Registry()
    export_moe_stats(reg, types.SimpleNamespace(state_kernel={"decode": ("dtx_ssm_step", 64)}))
    assert 'dtx_serving_state_head_tile{kernel="dtx_ssm_step",phase="decode"} 64' in reg.expose()
    export_moe_stats(reg, types.SimpleNamespace(state_kernel={"decode": ("xla", None)}))
    text = reg.expose()
    assert 'dtx_serving_state_head_tile{kernel="xla",phase="decode"} 0' in text
    assert 'kernel="dtx_ssm_step"' not in text
    export_moe_stats(reg, types.SimpleNamespace())  # a model with no state-space layer
    assert "dtx_serving_state_head_tile{" not in reg.expose()
