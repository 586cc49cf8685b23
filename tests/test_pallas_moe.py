"""The expert layer's grouped matmul kernel (ops/pallas_moe.py) in interpret
mode against ``jax.lax.ragged_dot``, and the rule that picks between them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datatunerx_tpu.ops import moe, pallas_moe


def _weights(n, E, D, F, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    draw = lambda k, shape: (jax.random.normal(k, shape, jnp.float32) * 0.05).astype(dtype)  # noqa: E731
    return draw(ks[0], (n, E, D, F)), draw(ks[1], (n, E, D, F)), draw(ks[2], (n, E, F, D))


# sizes over 96 sorted rows (6 groups) unless the case says otherwise
CASES = {
    "empty_front": dict(sizes=[0, 0, 5, 3, 9, 1]),
    "empty_middle": dict(sizes=[4, 0, 0, 7, 0, 2]),
    "empty_end": dict(sizes=[2, 6, 1, 0, 0, 0]),
    "one_large_among_single_rows": dict(sizes=[1, 1, 50, 1, 0, 1]),
    "rows_past_the_sum": dict(sizes=[3, 2, 0, 1, 0, 4]),
    "every_row_on_one_expert": dict(sizes=[0, 0, 0, 96, 0, 0]),
    "every_row_real": dict(sizes=[16, 16, 16, 16, 16, 16]),
    "no_row_at_all": dict(sizes=[0, 0, 0, 0, 0, 0]),
    "group_ends_on_a_tile_edge": dict(sizes=[16, 1, 15, 32, 0, 3]),
    "rows_no_multiple_of_the_tile": dict(sizes=[3, 0, 20, 1, 9, 4], rows=40),
    "stack_layer_0": dict(sizes=[2, 0, 9, 1, 0, 30], layer=0),
    "stack_layer_1": dict(sizes=[2, 0, 9, 1, 0, 30], layer=1),
    "stack_layer_2": dict(sizes=[2, 0, 9, 1, 0, 30], layer=2),
    "tile_32": dict(sizes=[1, 1, 50, 1, 0, 1], tm=32),
    "tile_64": dict(sizes=[1, 1, 50, 1, 0, 1], tm=64, rows=128),
    "float32_operands": dict(sizes=[4, 0, 0, 7, 0, 2], dtype=jnp.float32),
    # the two cells' D : F at an eighth of their width
    "mimo_shape": dict(sizes=[2, 1, 0, 3, 2, 0, 1, 4], D=512, F=256),
    "ling_shape": dict(sizes=[1, 0, 2, 1, 0, 0, 3, 1], D=640, F=128, tm=32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot(case):
    c = dict(dict(rows=96, tm=16, D=256, F=128, layer=None, dtype=jnp.bfloat16),
             **CASES[case])
    sizes = jnp.asarray(c["sizes"], jnp.int32)
    gate, up, down = _weights(3, len(c["sizes"]), c["D"], c["F"], c["dtype"])
    if c["layer"] is None:
        gate, up, down = gate[1], up[1], down[1]
    xs = jax.random.normal(jax.random.PRNGKey(9), (c["rows"], c["D"]),
                           jnp.float32).astype(c["dtype"])
    layer = None if c["layer"] is None else jnp.asarray(c["layer"], jnp.int32)

    got = jax.jit(lambda *a: moe.grouped_swiglu(*a, layer, c["tm"]))(
        xs, sizes, gate, up, down)
    want = jax.jit(lambda *a: moe.grouped_swiglu(*a, layer))(xs, sizes, gate, up, down)
    assert got.shape == want.shape == (c["rows"], c["D"]) and got.dtype == want.dtype
    real = sum(c["sizes"])
    # same operands, f32 accumulation: only the order of a sum differs (and a
    # bf16 rounding of the hidden rows that lands the other way)
    np.testing.assert_allclose(np.asarray(got[:real]), np.asarray(want[:real]),
                               rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("sizes,m,tm", [
    ([0, 3, 0, 40, 1, 0], 96, 16), ([5, 11, 16, 1, 0, 31], 64, 16),
    ([0, 0, 0, 0], 32, 16), ([64, 0, 0, 0], 64, 32), ([1] * 8, 128, 64)])
def test_visits_list_every_tile_a_group_has_rows_in(sizes, m, tm):
    count, group, tile, lo, hi = (np.asarray(a) for a in pallas_moe.visits(
        jnp.asarray(sizes, jnp.int32), m, tm))
    ends = np.cumsum(sizes)
    want = [(g, t) for g, (s, e) in enumerate(zip(ends - sizes, ends))
            for t in range(s // tm, (e - 1) // tm + 1) if e > s]
    assert count == len(want) and len(group) == m // tm + len(sizes) - 1
    assert list(zip(group[:len(want)], tile[:len(want)])) == want
    for v, (g, _) in enumerate(want):
        assert (lo[v], hi[v]) == (ends[g] - sizes[g], ends[g])
    # past the count a visit repeats the last one: no new block to fetch
    last = max(len(want) - 1, 0)
    assert (group[last:] == group[last]).all() and (tile[last:] == tile[last]).all()


@pytest.mark.parametrize("what,rows,top_k,total,d,f,want", [
    ("mimo decode", 64, 8, 256, 4096, 2048, ("dtx_moe_gmm", 16)),
    ("mimo chunk", 256, 8, 256, 4096, 2048, ("dtx_moe_gmm", 64)),
    ("ling decode", 128, 8, 512, 2560, 768, ("dtx_moe_gmm", 16)),
    ("ling chunk", 256, 8, 512, 2560, 768, ("dtx_moe_gmm", 32)),
    ("every expert held, long rows", 1024, 2, 8, 4096, 2048, ("ragged_dot", None)),
    ("a width off the lanes", 64, 8, 256, 4096, 2000, ("ragged_dot", None)),
    ("debug preset", 16, 2, 8, 64, 32, ("ragged_dot", None)),
])
def test_the_grouped_matmul_follows_the_shapes(what, rows, top_k, total, d, f, want):
    assert moe.grouped_matmul(rows, top_k=top_k, experts_total=total, d=d, f=f) == want


def test_expert_layer_on_the_kernel_is_the_layer_on_ragged_dot(monkeypatch):
    """The whole layer at a shape the rule gives the kernel, against the same
    layer with the rule switched off."""
    D, F, E, held, k, N = 256, 128, 32, 8, 4, 24
    gate, up, down = _weights(2, held, D, F, jnp.bfloat16, seed=3)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    p = {"router": {"kernel": jax.random.normal(ks[0], (D, E), jnp.float32)},
         "e_score_correction_bias": jnp.zeros((E,), jnp.float32),
         "experts": {"gate_proj": gate, "up_proj": up, "down_proj": down}}
    x = jax.random.normal(ks[1], (N, D), jnp.float32).astype(jnp.bfloat16)
    valid = jnp.arange(N) < 20
    layer = lambda: jax.jit(lambda x, valid, p: moe.expert_layer(  # noqa: E731
        x, valid, p, experts_total=E, experts_held=held, first_held=8, top_k=k,
        normalize=True, scaling=1.0, layer=jnp.asarray(1, jnp.int32)))(x, valid, p)
    assert moe.grouped_matmul(N, top_k=k, experts_total=E, d=D, f=F) == ("dtx_moe_gmm", 32)
    y, stats = layer()
    monkeypatch.setattr(pallas_moe, "row_tile", lambda *a: None)
    y0, stats0 = layer()
    assert int(stats[0]) > 0  # some pair was routed here
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats0))
    assert np.isfinite(np.asarray(y, np.float32)).all()
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y0, np.float32),
                               rtol=2e-2, atol=2e-3)


def test_the_engagement_record_is_exported_by_phase_and_kernel():
    import types

    from datatunerx_tpu.obs.metrics import Registry, export_moe_stats

    reg = Registry()
    export_moe_stats(reg, types.SimpleNamespace(moe_kernel={
        "decode": moe.grouped_matmul(64, top_k=8, experts_total=256, d=4096, f=2048),
        "prefill": moe.grouped_matmul(256, top_k=2, experts_total=8, d=64, f=32)}))
    text = reg.expose()
    assert 'dtx_serving_moe_row_tile{kernel="dtx_moe_gmm",phase="decode"} 16' in text
    assert 'dtx_serving_moe_row_tile{kernel="ragged_dot",phase="prefill"} 0' in text
    # an engine whose model has no experts states no series
    export_moe_stats(reg, types.SimpleNamespace())
    assert "dtx_serving_moe_row_tile{" not in reg.expose()
