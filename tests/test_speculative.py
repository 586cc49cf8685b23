"""Speculative decoding (serving/speculative.py + BatchedEngine spec tick).

The correctness bar has two layers:

- the ACCEPTANCE MATH: greedy acceptance reproduces sequential argmax decode
  token-for-token by construction, and the sampled rejection/residual scheme
  emits tokens whose marginal distribution is EXACTLY the target's (the
  Leviathan/Chen guarantee) — verified analytically against empirical
  frequencies over many PRNG keys;
- the ENGINE: spec-on greedy output is token-identical to spec-off across
  dense + paged caches, concurrent ragged batches, stop tokens, pooled
  mixed-rank adapters, and the adaptive-k controller's shrink/disable paths —
  while ``--spec_mode off`` leaves the engine byte-identical to before.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from datatunerx_tpu.ops.paged_attention import blocks_for_depth
from datatunerx_tpu.serving.batched_engine import BatchedEngine
from datatunerx_tpu.serving.speculative import (
    AdaptiveK,
    accept_tokens,
    build_draft,
    sampling_probs,
)

MODEL = "preset:debug"


# ------------------------------------------------------------ fixtures

@pytest.fixture(scope="module")
def dense_pair():
    """Spec-off / spec-on twins over a dense per-slot cache. The draft is
    take:2 — ALL of the 2-layer debug model, i.e. a perfect draft — so the
    all-accept path is exercised."""
    off = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=3, decode_chunk=4)
    on = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                       slots=3, decode_chunk=4,
                       spec_draft="take:2", spec_k=3, spec_mode="on")
    yield off, on
    off.close()
    on.close()


@pytest.fixture(scope="module")
def paged_pair():
    """Paged twins with a WEAK draft (take:1 of a random 2-layer model —
    near-zero acceptance), so rejection, residual correction and ragged
    per-row advance over block tables all run for real."""
    off = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=3, decode_chunk=4, kv_block_size=16)
    on = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                       slots=3, decode_chunk=4, kv_block_size=16,
                       spec_draft="take:1", spec_k=3, spec_mode="on")
    yield off, on
    off.close()
    on.close()


# ------------------------------------------------- acceptance-rule units

def test_sampling_probs_matches_sample_jit_semantics():
    logits = jnp.asarray([2.0, 1.0, 0.5, -1.0])
    # greedy: one-hot argmax
    p = sampling_probs(logits, 0.0, 1.0)
    np.testing.assert_array_equal(np.asarray(p), [1.0, 0.0, 0.0, 0.0])
    # top_p = 1: plain softmax of logits/t, fast path == exact path
    t = 0.7
    exact = np.asarray(sampling_probs(logits, t, 1.0))
    fast = np.asarray(sampling_probs(logits, t, 1.0, exact_topp=False))
    want = np.asarray(jax.nn.softmax(logits / t))
    np.testing.assert_allclose(exact, want, rtol=1e-5)
    np.testing.assert_allclose(fast, want, rtol=1e-5)
    # top_p < 1: the tail is cut and the kept mass renormalized. softmax
    # here is [.609, .224, .136, .030]: the nucleus rule keeps a token
    # while the mass BEFORE it is <= top_p, so 0.7 keeps exactly two.
    p = np.asarray(sampling_probs(logits, 1.0, 0.7))
    soft = np.asarray(jax.nn.softmax(logits))
    assert p[3] == 0.0 and p[2] == 0.0  # tail outside the 0.7 nucleus
    np.testing.assert_allclose(p[:2], soft[:2] / soft[:2].sum(), rtol=1e-5)
    assert abs(p.sum() - 1.0) < 1e-5


def test_accept_greedy_is_argmax_comparison():
    V, k = 6, 3
    p = np.zeros((k + 1, V), np.float32)
    p[0, 2] = p[1, 4] = p[2, 1] = p[3, 5] = 1.0  # target argmax: 2,4,1,5
    q = np.zeros((k, V), np.float32)
    q[:, 0] = 1.0
    rng = jax.random.PRNGKey(0)
    # drafts agree at 0 and 1, diverge at 2 → accept 2, correct to argmax
    a, extra, _ = accept_tokens(jnp.asarray(p), jnp.asarray(q),
                                jnp.asarray([2, 4, 0]), 0.0, rng, True)
    assert int(a) == 2 and int(extra) == 1
    # full agreement → accept all, bonus = argmax of the k-th dist
    a, extra, _ = accept_tokens(jnp.asarray(p), jnp.asarray(q),
                                jnp.asarray([2, 4, 1]), 0.0, rng, True)
    assert int(a) == 3 and int(extra) == 5
    # immediate divergence → accept none, correct to argmax of p_0
    a, extra, _ = accept_tokens(jnp.asarray(p), jnp.asarray(q),
                                jnp.asarray([0, 0, 0]), 0.0, rng, True)
    assert int(a) == 0 and int(extra) == 2
    # spec_on=False: forced plain step regardless of agreement
    a, extra, _ = accept_tokens(jnp.asarray(p), jnp.asarray(q),
                                jnp.asarray([2, 4, 1]), 0.0, rng, False)
    assert int(a) == 0 and int(extra) == 2


def test_accept_all_accept_and_all_reject_edges():
    V, k = 4, 2
    rng = jax.random.PRNGKey(1)
    # q == p → ratio 1 → every proposal accepted (sampled mode)
    p = np.asarray([[0.4, 0.3, 0.2, 0.1]] * (k + 1), np.float32)
    q = p[:k]
    for seed in range(8):
        a, _, _ = accept_tokens(
            jnp.asarray(p), jnp.asarray(q), jnp.asarray([0, 1]),
            1.0, jax.random.PRNGKey(seed), True)
        assert int(a) == k
    # draft proposes a token with ZERO target mass → always rejected,
    # and the residual (= p with q's mass removed) never re-emits it
    p0 = np.asarray([[0.0, 0.5, 0.5, 0.0]] * (k + 1), np.float32)
    q0 = np.zeros((k, V), np.float32)
    q0[:, 0] = 1.0
    for seed in range(16):
        a, extra, _ = accept_tokens(
            jnp.asarray(p0), jnp.asarray(q0), jnp.asarray([0, 0]),
            1.0, jax.random.PRNGKey(seed), True)
        assert int(a) == 0 and int(extra) in (1, 2)
    del rng


def test_residual_scheme_is_distribution_exact():
    """The Leviathan guarantee, checked empirically: with draft dist q and
    target dist p over a tiny vocab, the emitted FIRST token's frequency
    over many keys matches p — even though q is badly mismatched."""
    V, k = 4, 1
    p = np.asarray([0.5, 0.25, 0.15, 0.1], np.float32)
    q = np.asarray([0.05, 0.05, 0.45, 0.45], np.float32)
    p_full = jnp.asarray(np.stack([p] * (k + 1)))
    q_full = jnp.asarray(q[None, :])
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(42), n)
    # the draft samples d_0 ~ q with its own keys; acceptance consumes the
    # slot key — exactly the program's split discipline
    dkeys = jax.random.split(jax.random.PRNGKey(7), n)
    d0 = jax.vmap(
        lambda kk: jax.random.categorical(kk, jnp.log(q_full[0])))(dkeys)

    def one(key, d):
        a, extra, _ = accept_tokens(p_full, q_full, d[None], 1.0, key, True)
        return jnp.where(a > 0, d, extra)

    toks = np.asarray(jax.jit(jax.vmap(one))(keys, d0.astype(jnp.int32)))
    freq = np.bincount(toks, minlength=V) / n
    # 4000 samples: generous 4-sigma-ish tolerance, deterministic seeds
    np.testing.assert_allclose(freq, p, atol=0.04)


def test_blocks_for_depth_reserve_math():
    assert blocks_for_depth(32, 16) == 2
    assert blocks_for_depth(33, 16) == 3
    # spec overshoot rides on top…
    assert blocks_for_depth(32, 16, overshoot=5) == 3
    # …but never past the table width (cap = max_seq_len)
    assert blocks_for_depth(250, 16, overshoot=16, cap_depth=256) == 16
    assert blocks_for_depth(256, 16, overshoot=5, cap_depth=256) == 16


# ------------------------------------------------------ controller units

def test_adaptive_k_shrinks_and_disables():
    ctrl = AdaptiveK(k_max=4, mode="auto", floor=0.35, min_obs=2,
                     probe_every=3)
    assert ctrl.current_k() == 4 and ctrl.use_spec()
    # collapse acceptance on slot 0 → slot disabled, k shrinks, auto mode
    # stands down globally
    for _ in range(6):
        ctrl.observe([(0, 0, 4)])
    assert not ctrl.slot_enabled(0)
    assert ctrl.current_k() == 1
    assert not ctrl.use_spec()
    assert ctrl.disabled_events >= 1
    # plain fallback probes periodically so spec can win back
    for _ in range(3):
        ctrl.note_plain_step()
    assert ctrl.use_spec()  # the probe step
    # healthy acceptance restores full k; a released slot starts clean
    ctrl.reset_slot(0)
    assert ctrl.slot_enabled(0)
    for _ in range(30):
        ctrl.observe([(1, 4, 4)])
    assert ctrl.current_k() == 4 and ctrl.use_spec()
    # mode=on never stands down globally (per-slot gating still applies)
    pinned = AdaptiveK(k_max=2, mode="on", floor=0.5, min_obs=1)
    pinned.observe([(0, 0, 2)] * 8)
    assert pinned.use_spec()


def test_build_draft_take_and_validation():
    cfg, params, _ = __import__(
        "datatunerx_tpu.utils.model_loader",
        fromlist=["load_model_and_tokenizer"],
    ).load_model_and_tokenizer(MODEL)
    dcfg, dparams = build_draft("take:1", cfg, params)
    assert dcfg.num_layers == 1
    # early layers + embedding/unembedding are the target's own arrays
    assert dparams["embed_tokens"]["embedding"] is \
        params["embed_tokens"]["embedding"]
    np.testing.assert_array_equal(
        np.asarray(dparams["layers"]["q_proj"]["kernel"][0]),
        np.asarray(params["layers"]["q_proj"]["kernel"][0]))
    with pytest.raises(ValueError, match="out of range"):
        build_draft("take:9", cfg, params)
    # vocab mismatch is refused (acceptance compares one vocabulary)
    with pytest.raises(ValueError, match="vocab"):
        build_draft("preset:tinyllama-1.1b", cfg, params)


# -------------------------------------------------- engine-level parity

def test_spec_greedy_token_exact_dense_all_accept(dense_pair):
    off, on = dense_pair
    tok = off.tokenizer
    for text in ("the quick brown fox", "a completely different prompt"):
        ids = tok.encode(text)
        want = off.generate(ids, max_new_tokens=16)
        got = on.generate(ids, max_new_tokens=16)
        assert got == want, (text, got, want)
    info = on.spec_info()
    assert info["enabled"] and info["proposed"] > 0
    # a perfect (full self) draft must accept everything
    assert info["accept_rate"] == 1.0


def test_spec_greedy_token_exact_paged_rejections(paged_pair):
    off, on = paged_pair
    tok = off.tokenizer
    for text in ("hello world this is serving", "short"):
        ids = tok.encode(text)
        want = off.generate(ids, max_new_tokens=16)
        got = on.generate(ids, max_new_tokens=16)
        assert got == want, (text, got, want)
    info = on.spec_info()
    # the weak draft must have been REJECTED sometimes — the correction
    # path ran, and output still matched exactly
    assert info["accepted"] < info["proposed"]


def test_spec_concurrent_ragged_advance_paged(paged_pair):
    """Concurrent requests of different lengths advance raggedly inside one
    verify program (per-row accepted lengths differ); every stream must
    match its spec-off twin and every block must return to the free list."""
    off, on = paged_pair
    tok = off.tokenizer
    free0 = on.free_kv_blocks
    prompts = [tok.encode("first request about weather"),
               tok.encode("second one"),
               tok.encode("third request that is somewhat longer than both")]
    want = [off.submit(p, max_new_tokens=8 + 4 * i)
            for i, p in enumerate(prompts)]
    got = [on.submit(p, max_new_tokens=8 + 4 * i)
           for i, p in enumerate(prompts)]
    for w, g in zip(want, got):
        assert w.done.wait(120) and g.done.wait(120)
        assert g.tokens == w.tokens, (g.tokens, w.tokens)
    deadline = time.monotonic() + 10
    while on.free_kv_blocks != free0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert on.free_kv_blocks == free0  # ragged release leaked nothing


def test_spec_stop_token_truncates_identically(paged_pair):
    off, on = paged_pair
    tok = off.tokenizer
    ids = tok.encode("the quick brown fox")
    base = off.generate(ids, max_new_tokens=12)
    stop = {base[4]}  # a token greedy decode WILL emit mid-stream
    want = off.generate(ids, max_new_tokens=12, stop_ids=stop)
    got = on.generate(ids, max_new_tokens=12, stop_ids=stop)
    assert want == base[:4]  # sanity: the stop actually truncated
    assert got == want


def test_spec_sampled_runs_and_respects_budget(paged_pair):
    """Sampled spec decode is distribution-exact (proved at the math layer);
    at the engine layer it must run the topp/simple program variants,
    respect max_new_tokens, and differ per seed like any sampler."""
    _, on = paged_pair
    tok = on.tokenizer
    ids = tok.encode("sampling prompt")
    outs = {tuple(on.generate(ids, max_new_tokens=10, temperature=0.9,
                              top_p=0.8, seed=s)) for s in range(3)}
    assert all(len(o) <= 10 for o in outs)
    assert len(outs) > 1  # different seeds explore
    simple = on.generate(ids, max_new_tokens=10, temperature=0.9, seed=0)
    assert len(simple) <= 10


def test_spec_mixed_rank_pooled_adapters_in_verify_batch(tmp_path):
    """Pooled LoRA adapters stay program ARGUMENTS through the verify
    forward: mixed-rank adapters decoding concurrently under spec match
    their spec-off twin token-for-token."""
    from datatunerx_tpu.serving.adapters import make_adapter_sweep

    ckpts = make_adapter_sweep(str(tmp_path), MODEL, 2)  # ranks differ
    kw = dict(template="vanilla", max_seq_len=256, slots=3, decode_chunk=4,
              kv_block_size=16, adapter_pool=2, adapter_rank_max=16)
    off = BatchedEngine(MODEL, adapters=ckpts, **kw)
    on = BatchedEngine(MODEL, adapters=ckpts, spec_draft="take:2",
                       spec_k=3, spec_mode="on", **kw)
    try:
        tok = off.tokenizer
        names = ["", *sorted(ckpts)]
        prompts = [tok.encode(f"adapter request {i}") for i in range(3)]
        want = [off.submit(p, max_new_tokens=10, adapter=a)
                for p, a in zip(prompts, names)]
        got = [on.submit(p, max_new_tokens=10, adapter=a)
               for p, a in zip(prompts, names)]
        for w, g in zip(want, got):
            assert w.done.wait(180) and g.done.wait(180)
            assert g.tokens == w.tokens, (g.tokens, w.tokens)
        info = on.spec_info()
        assert set(info["adapter_accept_rate"]) >= set(names)
    finally:
        off.close()
        on.close()


def test_spec_mode_off_is_byte_identical(paged_pair):
    """--spec_mode off must leave the engine exactly as before: no spec
    structures, no draft load, the pre-spec decode program path."""
    off, _ = paged_pair
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=3, decode_chunk=4, kv_block_size=16,
                        spec_draft="take:1", spec_mode="off")
    try:
        assert eng.spec is None and eng._spec_overshoot == 0
        ids = eng.tokenizer.encode("off mode prompt")
        assert eng.generate(ids, max_new_tokens=8) == \
            off.generate(ids, max_new_tokens=8)
    finally:
        eng.close()
    with pytest.raises(ValueError, match="spec_draft_config"):
        BatchedEngine(MODEL, template="vanilla", max_seq_len=256, slots=2,
                      spec_mode="on")


def test_spec_adaptive_auto_falls_back_and_stays_exact():
    """spec_mode=auto with a hopeless draft: the controller must stand down
    to the plain pending-form program (never-slower contract) and output
    must STILL be token-exact — the fallback is the same decode math."""
    off = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16)
    on = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                       slots=2, decode_chunk=4, kv_block_size=16,
                       spec_draft="take:1", spec_k=4, spec_mode="auto")
    try:
        ids = off.tokenizer.encode("adversarial workload prompt")
        want = off.generate(ids, max_new_tokens=48)
        got = on.generate(ids, max_new_tokens=48)
        assert got == want
        info = on.spec_info()
        assert info["plain_steps"] > 0, info  # the fallback actually ran
        assert info["k"] <= 2  # collapsed acceptance shrank k
    finally:
        off.close()
        on.close()


def test_spec_metrics_and_replica_stats(paged_pair):
    _, on = paged_pair
    from datatunerx_tpu.gateway.replica_pool import InProcessReplica

    # its own speculative steps: under ``--dist load`` the file's earlier tests
    # (which used to leave them on the module's engine) may run on another worker
    on.generate(on.tokenizer.encode("a request of this test's own"), max_new_tokens=8)
    st = InProcessReplica("r0", on).stats()
    assert st["spec_enabled"] is True
    assert st["spec_accept_rate"] is not None
    info = on.spec_info()
    for key in ("proposed", "accepted", "spec_steps", "plain_steps", "k",
                "mode", "draft"):
        assert key in info


def test_router_prefers_spec_replicas():
    """Greedy (spec-friendly) traffic narrows to spec-enabled replicas with
    healthy acceptance; sampled traffic and spec-less fleets are untouched."""
    from datatunerx_tpu.gateway.replica_pool import Replica, ReplicaPool
    from datatunerx_tpu.gateway.router import Router

    class FakeReplica(Replica):
        def __init__(self, name, spec_enabled, rate):
            super().__init__(name)
            self._st = {"slots_busy": 0, "slots_total": 4,
                        "kv_blocks_free": 64, "kv_blocks_total": 64,
                        "adapters": None, "resident_adapters": None,
                        "spec_enabled": spec_enabled,
                        "spec_accept_rate": rate}

        def probe_health(self):
            return True

        def stats(self):
            return self._st

    specful = FakeReplica("spec", True, 0.9)
    specless = FakeReplica("plain", False, None)
    collapsed = FakeReplica("collapsed", True, 0.05)
    pool = ReplicaPool([specful, specless, collapsed])
    for r in (specful, specless, collapsed):
        r.healthy = True
    router = Router(pool, policy="round_robin")
    picks = {router.route(prefer_spec=True).name for _ in range(6)}
    assert picks == {"spec"}  # healthy-acceptance spec replica wins
    picks = {router.route(prefer_spec=False).name for _ in range(6)}
    assert picks == {"spec", "plain", "collapsed"}  # non-spec-friendly: all
    assert router.spec_routes["preferred"] > 0


# ------------------------------------------------------- tree-draft units

import jax.numpy as jnp  # noqa: E402

from datatunerx_tpu.serving.speculative import (  # noqa: E402
    TreeSpec,
    accept_tree_tokens,
    parse_spec_tree,
    tree_draft_mask,
    tree_verify_mask,
)


def test_parse_spec_tree_and_validation():
    t = parse_spec_tree("4x3")
    assert (t.width, t.depth) == (4, 3)
    assert t.step_tokens == 13  # pending + 4*3 nodes
    assert str(t) == "4x3"
    assert parse_spec_tree("1X1") == TreeSpec(1, 1)
    for bad in ("", "4", "4x", "x3", "4x3x2", "axb"):
        with pytest.raises(ValueError, match="WxD"):
            parse_spec_tree(bad)
    for oob in ("0x3", "65x2", "4x0", "4x17"):
        with pytest.raises(ValueError, match="out of range"):
            parse_spec_tree(oob)


def test_tree_verify_mask_ancestry():
    # W=2, D=2 — columns: 0 pending, 1=(d1,b0), 2=(d1,b1), 3=(d2,b0),
    # 4=(d2,b1). Each node sees the root + ITS OWN chain, never a sibling.
    want = np.array([[1, 0, 0, 0, 0],
                     [1, 1, 0, 0, 0],
                     [1, 0, 1, 0, 0],
                     [1, 1, 0, 1, 0],
                     [1, 0, 1, 0, 1]], bool)
    np.testing.assert_array_equal(tree_verify_mask(2, 2), want)
    # degenerate 1-wide tree IS the chain: lower-triangular
    np.testing.assert_array_equal(tree_verify_mask(1, 3),
                                  np.tril(np.ones((4, 4), bool)))


def test_tree_draft_mask_own_path_only():
    np.testing.assert_array_equal(
        tree_draft_mask(2, 1), np.array([[1, 1, 0], [1, 0, 1]], bool))
    np.testing.assert_array_equal(
        tree_draft_mask(2, 2),
        np.array([[1, 1, 0, 1, 0], [1, 0, 1, 0, 1]], bool))


def test_accept_tree_greedy_longest_surviving_path():
    """Greedy tree acceptance = sequential argmax decode by construction:
    a node survives iff its token matches the target argmax at its parent
    column; the deepest surviving branch wins; the extra token is the
    argmax at the divergence point."""
    V, W, D = 8, 2, 2
    # target argmaxes: col0→2, col1→4, col2→5, col3→1, col4→7
    p = np.zeros((1 + W * D, V), np.float32)
    for c, tok in enumerate((2, 4, 5, 1, 7)):
        p[c, tok] = 1.0
    q = jnp.zeros((D, W, V), jnp.float32)
    rng = jax.random.PRNGKey(0)

    def run(d_toks, spec_on=True):
        a, b, extra, _ = accept_tree_tokens(
            jnp.asarray(p), q, jnp.asarray(d_toks, jnp.int32), 0.0, rng,
            spec_on, width=W, depth=D)
        return int(a), int(b), int(extra)

    # branch 0 survives both depths → full path + bonus at its leaf
    assert run([[2, 3], [4, 0]]) == (2, 0, 1)
    # branch 1 is the survivor (branch 0 dies at depth 1)
    assert run([[3, 2], [0, 5]]) == (2, 1, 7)
    # branch 0 survives depth 1 only; extra = argmax at its depth-1 col
    assert run([[2, 3], [0, 0]]) == (1, 0, 4)
    # both branches die at depth 1 → plain step: argmax of the root dist
    a, _, extra = run([[0, 1], [0, 0]])
    assert (a, extra) == (0, 2)
    # spec_on=False forces the plain step regardless of agreement
    a, _, extra = run([[2, 3], [4, 0]], spec_on=False)
    assert (a, extra) == (0, 2)


def test_accept_tree_width1_matches_chain_rule():
    """A 1-wide tree is a chain: greedy acceptance must agree with
    accept_tokens on the same distributions (both count the agreeing
    prefix and correct at the divergence)."""
    V, D = 6, 3
    rng = jax.random.PRNGKey(2)
    p = np.zeros((D + 1, V), np.float32)
    for i, tok in enumerate((2, 4, 1, 5)):
        p[i, tok] = 1.0
    q = np.zeros((D, V), np.float32)
    q[:, 0] = 1.0
    for d in ([2, 4, 0], [2, 4, 1], [0, 0, 0]):
        a_c, extra_c, _ = accept_tokens(
            jnp.asarray(p), jnp.asarray(q), jnp.asarray(d), 0.0, rng, True)
        a_t, _, extra_t, _ = accept_tree_tokens(
            jnp.asarray(p), jnp.asarray(q)[:, None],
            jnp.asarray(d, jnp.int32)[:, None], 0.0, rng, True,
            width=1, depth=D)
        assert int(a_t) == int(a_c), d
        assert int(extra_t) == int(extra_c), d


def test_tree_sibling_rejection_is_distribution_exact():
    """The SpecInfer guarantee, checked empirically: W iid siblings from a
    badly-mismatched draft q, recursive-rejection acceptance against the
    running residual — the emitted FIRST token's marginal over many keys
    is EXACTLY the target p."""
    V, W = 4, 2
    p = np.asarray([0.5, 0.25, 0.15, 0.1], np.float32)
    q0 = np.asarray([0.05, 0.05, 0.45, 0.45], np.float32)
    # D=1: bonus rows never touch the FIRST emitted token
    p_cols = jnp.asarray(np.stack([p] * (1 + W)))
    q_tree = jnp.asarray(np.broadcast_to(q0, (1, W, V)).copy())
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(42), n)
    dkeys = jax.random.split(jax.random.PRNGKey(7), n)

    def draw(kk):
        k1, k2 = jax.random.split(kk)
        return jnp.stack([jax.random.categorical(k1, jnp.log(q0)),
                          jax.random.categorical(k2, jnp.log(q0))])

    d0 = jax.vmap(draw)(dkeys).astype(jnp.int32)[:, None, :]  # [n, 1, W]

    def one(key, d):
        a, b, extra, _ = accept_tree_tokens(
            p_cols, q_tree, d, 1.0, key, True, width=W, depth=1)
        return jnp.where(a > 0, d[0, b], extra)

    toks = np.asarray(jax.jit(jax.vmap(one))(keys, d0))
    freq = np.bincount(toks, minlength=V) / n
    np.testing.assert_allclose(freq, p, atol=0.04)


def test_accept_tree_all_accept_edge():
    """q == p → the FIRST sibling's ratio test always passes (u * q <= r
    with r = p = q), so some branch is always accepted."""
    V, W, D = 4, 3, 2
    p = np.asarray([[0.4, 0.3, 0.2, 0.1]] * (1 + W * D), np.float32)
    q = np.broadcast_to(np.asarray([0.4, 0.3, 0.2, 0.1], np.float32),
                        (D, W, V)).copy()
    for seed in range(8):
        a, _, _, _ = accept_tree_tokens(
            jnp.asarray(p), jnp.asarray(q),
            jnp.zeros((D, W), jnp.int32), 1.0,
            jax.random.PRNGKey(seed), True, width=W, depth=D)
        assert int(a) >= 1


# ------------------------------------------------ tree engine-level parity

@pytest.fixture(scope="module")
def tree_pair(paged_pair):
    """The paged_pair's off twin plus a WEAK-draft 2x2 tree engine
    (mode=on so the controller cannot stand down): rejections, branch
    selection, window compaction and ragged per-row advance all run for
    real against the identically-configured non-spec oracle."""
    off, _ = paged_pair
    on = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                       slots=3, decode_chunk=4, kv_block_size=16,
                       spec_draft="take:1", spec_k=3, spec_mode="on",
                       spec_tree="2x2")
    yield off, on
    on.close()


def test_tree_greedy_token_exact_concurrent_and_no_leak(tree_pair):
    """Greedy tree decode is token-exact vs the non-spec oracle — single
    and concurrent ragged streams — and every block the tree's
    (1 + W*D)-token window reservation took comes back: the
    blocks_for_depth overshoot used the per-step token count, not the
    chain's k+1."""
    off, on = tree_pair
    tok = off.tokenizer
    free0 = on.free_kv_blocks
    ids = tok.encode("hello world this is serving")
    want = off.generate(ids, max_new_tokens=16)
    got = on.generate(ids, max_new_tokens=16)
    assert got == want, (got, want)
    info = on.spec_info()
    assert info["tree_steps"] > 0
    assert info["tree"]["spec"] == "2x2"
    # the weak draft was REJECTED sometimes — branch selection, rollback
    # and window compaction all ran, and output still matched exactly
    assert info["accepted"] < info["proposed"]

    prompts = [tok.encode("first request about weather"),
               tok.encode("second one"),
               tok.encode("third request that is somewhat longer than both")]
    want = [off.submit(p, max_new_tokens=8 + 4 * i)
            for i, p in enumerate(prompts)]
    got = [on.submit(p, max_new_tokens=8 + 4 * i)
           for i, p in enumerate(prompts)]
    for w, g in zip(want, got):
        assert w.done.wait(180) and g.done.wait(180)
        assert g.tokens == w.tokens, (g.tokens, w.tokens)
    deadline = time.monotonic() + 10
    while on.free_kv_blocks != free0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert on.free_kv_blocks == free0


@pytest.mark.slow
def test_tree_sampled_runs_and_respects_budget(tree_pair):
    _, on = tree_pair
    tok = on.tokenizer
    ids = tok.encode("sampling prompt")
    outs = {tuple(on.generate(ids, max_new_tokens=10, temperature=0.9,
                              top_p=0.8, seed=s)) for s in range(2)}
    assert all(len(o) <= 10 for o in outs)
    assert len(outs) > 1


def test_tree_overshoot_is_step_tokens(tree_pair):
    """The satellite fix: reservation math takes the PER-STEP token count.
    A 2x2 tree writes 1 + 2*2 = 5 tokens per verify step — more than the
    chain's spec_k + 1 = 4 — so sizing overshoot by the chain formula
    would overflow the reserved tail and corrupt a neighbor's block."""
    _, on = tree_pair
    assert on.spec_tree.step_tokens == 5
    assert on._spec_overshoot == 5
    assert on._tick_advance == 5  # max(decode_chunk=4, step_tokens)


def test_tree_engine_validation_and_off_modes():
    # tree without a draft is refused
    with pytest.raises(ValueError, match="spec_draft_config"):
        BatchedEngine(MODEL, template="vanilla", max_seq_len=256, slots=2,
                      spec_tree="2x2")
    # malformed WxD is refused with the format named
    with pytest.raises(ValueError, match="WxD"):
        BatchedEngine(MODEL, template="vanilla", max_seq_len=256, slots=2,
                      spec_draft="take:1", spec_tree="nope")
    # a tree that cannot fit the sequence budget is refused
    with pytest.raises(ValueError, match="max_seq_len"):
        BatchedEngine(MODEL, template="vanilla", max_seq_len=16, slots=2,
                      kv_block_size=16, spec_draft="take:1",
                      spec_tree="64x16")
    # spec_mode=off ignores the tree entirely — byte-identical off path
    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        spec_draft="take:1", spec_mode="off",
                        spec_tree="2x2")
    try:
        assert eng.spec is None and eng._spec_overshoot == 0
    finally:
        eng.close()


def test_chain_engine_has_no_tree_surface(paged_pair):
    """--spec_tree unset: spec_info carries no tree document and the
    overshoot stays the chain's spec_k + 1 — the PR 14 engine unchanged."""
    _, on = paged_pair
    info = on.spec_info()
    assert "tree" not in info
    assert on.spec_tree is None
    assert on._spec_overshoot == 4  # spec_k=3 → k+1


# ----------------------------------------- learned ragged tree shapes (units)

def test_ragged_widths_validation_and_masks():
    from datatunerx_tpu.serving.speculative import _widths_tuple

    assert _widths_tuple(2, 2) == (2, 2)
    assert _widths_tuple((3, 2, 1)) == (3, 2, 1)
    with pytest.raises(ValueError, match="non-increasing"):
        _widths_tuple((1, 2))
    with pytest.raises(ValueError, match=">= 1"):
        _widths_tuple((2, 0))
    # ragged ancestry, widths (2, 1): cols 0 root, 1=(d1,b0), 2=(d1,b1),
    # 3=(d2,b0) — branch 1 simply has no depth-2 column
    want = np.array([[1, 0, 0, 0],
                     [1, 1, 0, 0],
                     [1, 0, 1, 0],
                     [1, 1, 0, 1]], bool)
    np.testing.assert_array_equal(tree_verify_mask((2, 1)), want)
    # a widths tuple that IS the rectangle matches the (W, D) form
    np.testing.assert_array_equal(tree_verify_mask((2, 2)),
                                  tree_verify_mask(2, 2))
    # ragged draft mask at depth 2 of (2, 1): one live branch over the
    # 1 + 2 + 1 window — root, own depth-1 ancestor, own write lane
    np.testing.assert_array_equal(
        tree_draft_mask((2, 1), 2), np.array([[1, 1, 0, 1]], bool))


def test_accept_tree_ragged_widths_greedy():
    """Learned (2, 1) shape: branch 1 exists at depth 1 only. Its chain
    stops at its live depth, and dead lanes (d_toks -1, q 0) never win a
    test — acceptance over the ragged flattened window stays exactly the
    sequential-greedy rule."""
    V = 8
    # cols: 0 root→2, 1=(d1,b0)→4, 2=(d1,b1)→5, 3=(d2,b0)→1
    p = np.zeros((4, V), np.float32)
    for c, tok in enumerate((2, 4, 5, 1)):
        p[c, tok] = 1.0
    q = jnp.zeros((2, 2, V), jnp.float32)
    rng = jax.random.PRNGKey(0)

    def run(d_toks):
        a, b, extra, _ = accept_tree_tokens(
            jnp.asarray(p), q, jnp.asarray(d_toks, jnp.int32), 0.0, rng,
            True, widths=(2, 1))
        return int(a), int(b), int(extra)

    # branch 0 survives both depths → full path + bonus at its leaf
    assert run([[2, 3], [4, -1]]) == (2, 0, 1)
    # branch 1 survives depth 1; its chain ENDS there (no depth-2 lane)
    assert run([[3, 2], [0, -1]]) == (1, 1, 5)
    # everything dies at depth 1 → plain step from the root distribution
    a, _, extra = run([[0, 1], [0, -1]])
    assert (a, extra) == (0, 2)


def test_adaptive_tree_buckets_and_monotone_cap():
    from datatunerx_tpu.serving.speculative import AdaptiveTree

    ctrl = AdaptiveTree(3, mode="on", tree=parse_spec_tree("4x3"))
    # no evidence yet: the full rectangle
    assert ctrl.current_plan() == ("tree", (4, 4, 4))
    # first observation seeds the EMAs directly; survival 1.0 / 0.4 / 0.1
    # buckets to W / ceil(W/2) / 1 at the 0.6 / 0.3 thresholds
    ctrl.observe_tree([1.0, 0.4, 0.1], 0.0)
    assert ctrl.current_plan() == ("tree", (4, 2, 1))
    # monotone cap: a depth whose own bucket exceeds the depth above it is
    # clamped (prefix-live branch chains), whatever its own EMA says
    ctrl2 = AdaptiveTree(3, mode="on", tree=parse_spec_tree("4x3"))
    ctrl2.observe_tree([0.4, 0.1, 1.0], 0.0)
    assert ctrl2.current_plan() == ("tree", (2, 1, 1))


def test_adaptive_tree_decisive_margin_caps_root():
    from datatunerx_tpu.serving.speculative import AdaptiveTree

    ctrl = AdaptiveTree(3, mode="on", tree=parse_spec_tree("4x2"))
    # the draft root's top-2 margin is (nearly) always decisive: sibling
    # roots are wasted draft FLOPs, so depth-1 width caps at 1 — and the
    # monotone chain drags every deeper width down with it
    ctrl.observe_tree([1.0, 1.0], 1.0)
    assert ctrl.current_plan() == ("tree", (1, 1))
    # sub-threshold decisiveness leaves the learned widths alone
    ctrl2 = AdaptiveTree(3, mode="on", tree=parse_spec_tree("4x2"))
    ctrl2.observe_tree([1.0, 1.0], 0.5)
    assert ctrl2.current_plan() == ("tree", (4, 4))


def test_adaptive_tree_global_floor_and_migration_state():
    from datatunerx_tpu.serving.speculative import AdaptiveTree

    def mk():
        return AdaptiveTree(3, mode="on", tree=parse_spec_tree("4x2"))

    ctrl = mk()
    ctrl.observe_tree([1.0, 0.4], 0.0)
    ctrl.observe([(0, 2, 4)])  # slot 0 acceptance history (rate 0.5)
    assert ctrl.current_plan() == ("tree", (4, 2))
    # collapsed GLOBAL acceptance overrides the per-depth evidence: the
    # width-1 chain-of-depth-D last resort, same as the fixed controller
    ctrl.global_ema = 0.1
    assert ctrl.current_plan() == ("tree", (1, 1))
    ctrl.global_ema = 0.5

    # the dtx-kv-session "spec" sub-document warms a cold importer: the
    # learned widths survive migration instead of restarting at (W,)*D
    state = ctrl.export_slot_state(0)
    cold = mk()
    cold.import_slot_state(5, state)
    assert cold.current_plan() == ("tree", (4, 2))
    assert cold._slot_ema[5][0] == pytest.approx(0.5)
    assert cold.global_ema == pytest.approx(0.5)
    # a live controller's own evidence is NOT overwritten by an import
    warm = mk()
    warm.observe_tree([0.1, 0.1], 0.0)
    warm.import_slot_state(5, state)
    assert warm.current_plan() == ("tree", (1, 1))


# ------------------------------------------- fused sampling epilogue (engine)

@pytest.fixture(scope="module")
def epilogue_pair():
    """Identical spec engines differing ONLY in --sampling_epilogue: off is
    the legacy per-row vmap sampler, on routes the draw through the fused
    epilogue (resolved to the blocked-XLA oracle impl on CPU — the same
    tile walk the Pallas kernel reproduces bitwise, pinned by
    test_pallas_sampling)."""
    # take:2 (perfect draft) keeps the acceptance EMA — and so the
    # adaptive k — stable across generates: fixed-seed streams only
    # repeat when the k path repeats. Non-spec programs are already
    # memoized by paged_pair (same engine config, off == CPU auto).
    kw = dict(template="vanilla", max_seq_len=256, slots=3, decode_chunk=4,
              kv_block_size=16, spec_draft="take:2", spec_k=3,
              spec_mode="on")
    off = BatchedEngine(MODEL, sampling_epilogue="off", **kw)
    on = BatchedEngine(MODEL, sampling_epilogue="on", **kw)
    yield off, on
    off.close()
    on.close()


@pytest.mark.slow
def test_epilogue_greedy_token_exact_and_counted(epilogue_pair):
    # slow: first user of the epilogue_pair fixture — prices the fused
    # spec program family. CI's spec smoke step runs this file unfiltered.
    off, on = epilogue_pair
    assert on.sampling_epilogue == "on"
    assert on._epilogue_impl in ("xla", "kernel")
    assert off._epilogue_impl == "off"
    tok = off.tokenizer
    ids = tok.encode("fused epilogue request")
    want = off.generate(ids, max_new_tokens=16)
    got = on.generate(ids, max_new_tokens=16)
    assert got == want, (got, want)
    assert on.sampling_stats["fused_steps"] > 0
    assert off.sampling_stats["fused_steps"] == 0
    assert off.sampling_stats["legacy_steps"] > 0
    info = on.spec_info()
    assert info["sampling_epilogue"] == "on"
    assert info["fused_steps"] > 0


@pytest.mark.slow
def test_epilogue_sampled_fixed_seed_deterministic(epilogue_pair):
    """The fused draw is distribution-exact (test_pallas_sampling pins the
    primitive against sampling_probs); at the engine layer a fixed seed
    must reproduce the stream exactly and distinct seeds must explore.
    slow: compiles the whole sampled-mode spec program family — the CI
    spec smoke step runs this file unfiltered, like the tree sampled
    budget test above."""
    _, on = epilogue_pair
    tok = on.tokenizer
    ids = tok.encode("sampled epilogue prompt")
    a = on.generate(ids, max_new_tokens=10, temperature=0.9, seed=3)
    assert a == on.generate(ids, max_new_tokens=10, temperature=0.9, seed=3)
    assert len(a) <= 10
    b = on.generate(ids, max_new_tokens=10, temperature=0.9, seed=4)
    assert a != b  # distinct seeds explore
    # (topp-mode determinism rides the plain-engine test below — one
    # compiled program instead of the whole spec family)


@pytest.mark.slow
def test_epilogue_int8_kv_quant_token_exact():
    # slow: compiles the epilogue-on int8 program family — the CI spec
    # smoke step runs this file unfiltered.
    # dense int8 cache: the off twin's programs are already compiled by
    # test_batched_engine's int8 engine (same memo key), so this pair
    # prices only the epilogue-on int8 program family
    kw = dict(template="vanilla", max_seq_len=256, slots=2, decode_chunk=4,
              kv_quant="int8", spec_draft="take:2",
              spec_k=3, spec_mode="on")
    off = BatchedEngine(MODEL, sampling_epilogue="off", **kw)
    on = BatchedEngine(MODEL, sampling_epilogue="on", **kw)
    try:
        ids = off.tokenizer.encode("quantized cache with fused sampling")
        want = off.generate(ids, max_new_tokens=12)
        got = on.generate(ids, max_new_tokens=12)
        assert got == want, (got, want)
        assert on.sampling_stats["fused_steps"] > 0
    finally:
        off.close()
        on.close()


@pytest.mark.slow
def test_epilogue_mixed_rank_pooled_adapters_token_exact(tmp_path):
    # slow: two pooled-adapter engines — CI spec smoke runs this file
    # unfiltered
    from datatunerx_tpu.serving.adapters import make_adapter_sweep

    ckpts = make_adapter_sweep(str(tmp_path), MODEL, 2)  # ranks differ
    kw = dict(template="vanilla", max_seq_len=256, slots=3, decode_chunk=4,
              kv_block_size=16, adapter_pool=2, adapter_rank_max=16,
              spec_draft="take:2", spec_k=3, spec_mode="on")
    off = BatchedEngine(MODEL, adapters=ckpts, sampling_epilogue="off", **kw)
    on = BatchedEngine(MODEL, adapters=ckpts, sampling_epilogue="on", **kw)
    try:
        tok = off.tokenizer
        names = ["", *sorted(ckpts)]
        prompts = [tok.encode(f"adapter epilogue request {i}")
                   for i in range(3)]
        want = [off.submit(p, max_new_tokens=10, adapter=a)
                for p, a in zip(prompts, names)]
        got = [on.submit(p, max_new_tokens=10, adapter=a)
               for p, a in zip(prompts, names)]
        for w, g in zip(want, got):
            assert w.done.wait(180) and g.done.wait(180)
            assert g.tokens == w.tokens, (g.tokens, w.tokens)
    finally:
        off.close()
        on.close()


def test_epilogue_off_and_cpu_auto_share_programs():
    """--sampling_epilogue off is byte-identical to the pre-epilogue
    engine: on CPU `auto` resolves off, so the explicit-off engine and a
    default engine hit the SAME _PROGRAM_MEMO entry — one compiled program
    set, identical traces, identical output."""
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to on under a TPU backend")
    kw = dict(template="vanilla", max_seq_len=256, slots=2, decode_chunk=4,
              kv_block_size=16)
    auto = BatchedEngine(MODEL, **kw)
    off = BatchedEngine(MODEL, sampling_epilogue="off", **kw)
    try:
        assert auto.sampling_epilogue == "off"
        assert auto._epilogue_impl == off._epilogue_impl == "off"
        assert off._decode is auto._decode  # same memoized _Programs
        assert off._prefill is auto._prefill
        ids = auto.tokenizer.encode("identical path")
        assert off.generate(ids, max_new_tokens=8) == \
            auto.generate(ids, max_new_tokens=8)
    finally:
        auto.close()
        off.close()


@pytest.mark.slow
def test_epilogue_plain_engine_fused_decode():
    """The fused draw also serves the plain (non-spec) decode program —
    the epilogue is not a spec-only surface.
    slow: prices the plain fused greedy + exact-topp programs — the CI
    spec smoke step runs this file unfiltered."""
    kw = dict(template="vanilla", max_seq_len=256, slots=2, decode_chunk=4,
              kv_block_size=16)
    off = BatchedEngine(MODEL, sampling_epilogue="off", **kw)
    on = BatchedEngine(MODEL, sampling_epilogue="on", **kw)
    try:
        ids = off.tokenizer.encode("plain decode fused epilogue")
        assert on.generate(ids, max_new_tokens=10) == \
            off.generate(ids, max_new_tokens=10)
        assert on.sampling_stats["fused_steps"] > 0
        assert on.spec_info() is None  # no spec surface grew
        # topp-mode epilogue: exact-nucleus path, fixed-seed deterministic
        t = on.generate(ids, max_new_tokens=8, temperature=0.9, top_p=0.7,
                        seed=0)
        assert t == on.generate(ids, max_new_tokens=8, temperature=0.9,
                                top_p=0.7, seed=0)
    finally:
        off.close()
        on.close()


@pytest.mark.slow
def test_tree_adaptation_and_epilogue_zero_recompiles():
    """SAN003: the learned controller's width replans and the epilogue's
    per-batch mode switches must land on ALREADY-COMPILED programs — the
    bucketed width set and the static mode set bound the program memo, so
    steady-state serving never lowers a fresh program mid-traffic.
    slow: pre-warms every width bucket's program set (the point of the
    test) — the CI spec smoke step runs this file unfiltered."""
    from datatunerx_tpu.analysis.sanitizers import compile_budget

    eng = BatchedEngine(MODEL, template="vanilla", max_seq_len=256,
                        slots=2, decode_chunk=4, kv_block_size=16,
                        spec_draft="take:1", spec_k=3, spec_mode="on",
                        spec_tree="2x2", sampling_epilogue="on")
    try:
        tok = eng.tokenizer
        ids = tok.encode("prewarm prompt")
        ctrl = eng.spec_ctrl
        # every plan the W=2 bucket set {2, 1} + monotone cap can produce
        plans = {(1.0, 1.0): (2, 2), (1.0, 0.4): (2, 1), (0.1, 0.1): (1, 1)}

        def pin(fr):
            # reset ALL learned signals (the weak take:1 draft's real
            # acceptance would otherwise drag the global EMA under the
            # 0.3 floor and pin every plan at the width-1 chain)
            with ctrl._lock:
                ctrl._depth_ema = [None] * len(ctrl._depth_ema)
                ctrl._decisive_ema = None
                ctrl.global_ema = None
            ctrl.observe_tree(list(fr), 0.0)

        # pre-warm every width bucket (greedy) plus ONE plan's sampled
        # variant outside the window: this is where the bounded program
        # set compiles
        for fr, widths in plans.items():
            pin(fr)
            assert ctrl.current_plan() == ("tree", widths)
            eng.generate(ids, max_new_tokens=6)
        pin((1.0, 1.0))
        eng.generate(ids, max_new_tokens=6, temperature=0.9, seed=1)
        # a 1-token sampled request never drafts (no headroom), so it runs
        # the PLAIN decode program in "simple" mode — compile that variant
        # here, outside the window, since the window replays the same shape
        pin((1.0, 1.0))
        eng.generate(ids, max_new_tokens=1, temperature=0.9, seed=1)
        with compile_budget(0, label="tree replan + epilogue mode switch"):
            for fr in reversed(list(plans)):
                pin(fr)
                eng.generate(ids, max_new_tokens=6)
            # epilogue mode switch (greedy ↔ simple) on a warmed plan.
            # One token = ONE spec tick, which reads the plan exactly
            # once at the pinned state — the weak draft's real acceptance
            # evidence cannot replan onto a sampled variant the pre-warm
            # did not compile.
            pin((1.0, 1.0))
            eng.generate(ids, max_new_tokens=1, temperature=0.9, seed=2)
        assert eng.sampling_stats["fused_steps"] > 0
    finally:
        eng.close()
