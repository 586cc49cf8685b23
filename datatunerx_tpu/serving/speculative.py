"""Speculative decoding for the batched serving engine (ISSUE 14).

PR 13's Pallas kernel made each decode step cheap on HBM; this module makes
each TARGET step emit more than one token. A small draft model proposes ``k``
tokens autoregressively, the target model runs ONE verify-k forward over the
proposed positions, and a batched rejection/residual acceptance rule keeps the
longest agreeing prefix plus one corrected token — so a target forward
amortizes over ``1 + accepted`` emitted tokens while staying
**distribution-exact**:

- greedy (``temperature <= 0``): a proposal is accepted iff it equals the
  target argmax at its position, and the corrected token IS the target
  argmax — the emitted stream is token-identical to vanilla greedy decode;
- sampled: the standard speculative scheme (Leviathan et al. / Chen et al.):
  accept ``d_j`` with prob ``min(1, p_j(d_j)/q_j(d_j))``; on first rejection
  sample from the residual ``norm(max(p_j - q_j, 0))``; on full acceptance
  sample the bonus token from ``p_k``. Every emitted token is marginally
  distributed exactly as a sample from the target distribution, driven by the
  slot's live PRNG key (the same first-class key the KV-migration payload
  carries).

The engine-side state machine (``BatchedEngine._spec_decode_tick``) keeps
slots in **pending-token form**: the most recently emitted token's KV is not
yet written; each step feeds ``[pending, d_0..d_{k-1}]`` through the target so
the bonus/corrected token needs no extra forward. ``SpecPrograms`` below holds
the jitted device programs (process-memoized like the engine's ``_Programs``);
the acceptance math is pure and unit-testable.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from datatunerx_tpu.models.llama import forward, init_cache
from datatunerx_tpu.ops.attention import compact_window
from datatunerx_tpu.ops.pallas_sampling import fused_sample, sample_rows
from datatunerx_tpu.serving import options
from datatunerx_tpu.serving.engine import _sample_jit


# ------------------------------------------------------------- tree topology
@dataclasses.dataclass(frozen=True)
class TreeSpec:
    """``--spec_tree WxD``: W parallel draft chains of depth D sharing the
    pending root. The verify window flattens depth-major: column 0 is the
    pending token, node (depth j, branch b) sits at column
    ``1 + (j-1)*W + b`` with rope position ``pos + j`` — siblings SHARE a
    rope position, which is why tree verification needs the branch
    ancestry mask (``tree_verify_mask``) on top of the causal check."""

    width: int
    depth: int

    @property
    def step_tokens(self) -> int:
        """Tokens one tree step writes per slot (pending + all nodes) —
        the overshoot / window width / verify-column count."""
        return 1 + self.width * self.depth

    def __str__(self) -> str:
        return f"{self.width}x{self.depth}"


def parse_spec_tree(spec: str) -> TreeSpec:
    """Parse ``--spec_tree`` / ``serveConfig.specTree`` ``"WxD"`` strings."""
    return TreeSpec(*options.parse_spec_tree(spec))


def _tree_col(j: int, b: int, width: int) -> int:
    """Verify-window column of tree node (depth ``j`` >= 1, branch ``b``)
    in the RECTANGLE layout (every depth ``width`` wide)."""
    return 1 + (j - 1) * width + b


def _widths_tuple(width, depth=None) -> tuple:
    """Canonical per-depth widths: ``(W, D)`` ints mean the fixed rectangle
    ``(W,) * D``; an explicit sequence is the learned ragged shape. Widths
    must be monotone NON-INCREASING — that makes every branch chain
    prefix-live (branch b exists at depth j ⇒ it exists at every shallower
    depth), which is what keeps ragged ancestry masks, clamped gathers and
    the chain acceptance rule correct."""
    if depth is not None:
        ws = (int(width),) * int(depth)
    else:
        ws = tuple(int(w) for w in width)
    if not ws or any(w < 1 for w in ws):
        raise ValueError(f"tree widths must all be >= 1, got {ws}")
    if any(b > a for a, b in zip(ws, ws[1:])):
        raise ValueError(
            f"tree widths must be non-increasing (branch chains must be "
            f"prefix-live), got {ws}")
    return ws


def _width_offsets(ws: tuple) -> list:
    """Flattened-window column of each depth's first node: depth j
    (1-indexed) occupies columns ``offs[j-1] .. offs[j-1]+ws[j-1]-1``;
    column 0 is the pending root."""
    offs, c = [], 1
    for w in ws:
        offs.append(c)
        c += w
    return offs


def tree_verify_mask(width, depth=None) -> np.ndarray:
    """Static [T, T] branch ancestry mask for the verify forward: query
    column c may attend window column c' iff c' is on c's root-to-self
    path. Combined with the causal check inside ``attention_allow`` (which
    still excludes unwritten sentinel lanes), this is exactly the oracle
    bias a sequential per-branch verify would build.

    Accepts ``(W, D)`` ints (the fixed rectangle) or one per-depth widths
    tuple (the learned ragged shape, ``T = 1 + sum(widths)``)."""
    ws = _widths_tuple(width, depth)
    offs = _width_offsets(ws)
    T = 1 + sum(ws)
    mask = np.zeros((T, T), dtype=bool)
    mask[0, 0] = True
    for j, w in enumerate(ws, start=1):
        for b in range(w):
            c = offs[j - 1] + b
            mask[c, 0] = True
            for i in range(1, j + 1):
                mask[c, offs[i - 1] + b] = True
    return mask


def tree_draft_mask(width, j: int) -> np.ndarray:
    """Static window mask for the draft's depth-``j`` forward: branch b's
    query attends the pending root, its own ancestors, and its own write
    lane — never a sibling chain. ``width`` is an int (rectangle: shape
    ``[W, 1 + j*W]``) or the per-depth widths tuple (ragged: shape
    ``[ws[j-1], 1 + sum(ws[:j])]``)."""
    ws = _widths_tuple(width, j) if isinstance(width, int) else \
        _widths_tuple(width)
    offs = _width_offsets(ws)
    w = ws[j - 1]
    mask = np.zeros((w, 1 + sum(ws[:j])), dtype=bool)
    for b in range(w):
        mask[b, 0] = True
        for i in range(1, j + 1):
            mask[b, offs[i - 1] + b] = True
    return mask


# ------------------------------------------------------------- sampling math
def sampling_probs(logits: jnp.ndarray, temperature, top_p,
                   exact_topp: bool = True) -> jnp.ndarray:
    """The probability vector ``_sample_jit`` samples from ([V] float32).

    Greedy (``temperature <= 0``) is a one-hot argmax; otherwise the top-p
    truncated, renormalized softmax of ``logits / temperature`` — computed in
    the same sorted space as ``_sample_jit`` so the two agree exactly (the
    categorical over ``filtered`` logits IS the renormalized kept mass).
    The acceptance rule must divide/subtract these, so they are materialized
    here instead of re-deriving the filter at every use site.

    ``exact_topp=False`` is a STATIC fast path for batches where no live row
    actually filters (every ``top_p >= 1``): the cut never triggers, so the
    distribution is plain ``softmax(logits/t)`` and the full-vocab sort —
    the single most expensive op in the verify program — never compiles.
    The caller asserts the batch property; passing a filtering row through
    the fast path would be WRONG, not just slow."""
    V = logits.shape[-1]
    greedy = jax.nn.one_hot(jnp.argmax(logits), V, dtype=jnp.float32)

    t = jnp.maximum(temperature, 1e-6)
    scaled = logits / t
    if exact_topp:
        sorted_idx = jnp.argsort(-scaled)
        sorted_logits = scaled[sorted_idx]
        probs = jax.nn.softmax(sorted_logits)
        cum = jnp.cumsum(probs)
        cut = (cum - probs > top_p) & (top_p < 1.0)
        kept = jnp.where(cut, 0.0, probs)
        kept = kept / jnp.maximum(kept.sum(), 1e-30)
        sampled = jnp.zeros((V,), jnp.float32).at[sorted_idx].set(kept)
    else:
        sampled = jax.nn.softmax(scaled)

    return jnp.where(temperature <= 0.0, greedy, sampled)


def accept_tokens(p_probs: jnp.ndarray, q_probs: jnp.ndarray,
                  draft_toks: jnp.ndarray, temperature, rng,
                  spec_on) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One row's rejection/residual acceptance (traceable; vmapped by the
    verify program, unit-tested directly).

    ``p_probs`` [k+1, V]: target distributions at the k proposed positions
    plus the bonus position; ``q_probs`` [k, V]: the draft distributions each
    proposal was sampled from; ``draft_toks`` [k]. Returns ``(n_accept,
    extra_token, new_rng)`` — the row emits ``draft_toks[:n_accept]`` then
    ``extra_token`` (subject to the engine's stop/budget truncation).

    ``spec_on=False`` rows force zero acceptances AND a zero draft
    distribution, so the "residual" degenerates to the plain target
    distribution ``p_0`` — the row takes an ordinary single-token step
    inside the same program."""
    k = draft_toks.shape[0]
    rng, u_key, x_key = jax.random.split(rng, 3)
    us = jax.random.uniform(u_key, (k,))
    idx = jnp.arange(k)
    p_at = p_probs[idx, draft_toks]
    q_at = q_probs[idx, draft_toks]
    greedy = temperature <= 0.0
    tgt_argmax = jnp.argmax(p_probs, axis=-1)  # [k+1]
    # u < min(1, p/q) in the division-free form; the q_at > 0 guard is
    # belt-and-braces (the draft sampled the token FROM q, so q_at > 0 in
    # any real flow) and applies to the ratio test only — greedy acceptance
    # is pure argmax comparison and never consults q
    ok_sampled = (us * q_at <= p_at) & (q_at > 0.0)
    ok_greedy = draft_toks == tgt_argmax[:k]
    ok = jnp.where(greedy, ok_greedy, ok_sampled) & spec_on
    acc_prefix = jnp.cumprod(ok.astype(jnp.int32))
    a = jnp.sum(acc_prefix).astype(jnp.int32)  # 0..k, first rejection stops

    p_a = p_probs[a]
    q_pad = jnp.concatenate([q_probs, jnp.zeros_like(q_probs[:1])], axis=0)
    q_a = jnp.where(spec_on, q_pad[a], jnp.zeros_like(p_a))
    resid = jnp.clip(p_a - q_a, 0.0, None)
    tot = resid.sum()
    # numerically-empty residual (p ≈ q): any sample from p_a is correct
    resid = jnp.where(tot > 0.0, resid / jnp.maximum(tot, 1e-30), p_a)
    extra_sampled = jax.random.categorical(
        x_key, jnp.log(jnp.maximum(resid, 1e-30))).astype(jnp.int32)
    extra = jnp.where(greedy, tgt_argmax[a], extra_sampled).astype(jnp.int32)
    return a, extra, rng


def accept_tree_tokens(p_cols: jnp.ndarray, q_tree: jnp.ndarray,
                       d_toks: jnp.ndarray, temperature, rng, spec_on,
                       *, width: int = 0, depth: int = 0,
                       widths: Optional[tuple] = None):
    """One row's tree acceptance (traceable; vmapped by the tree-verify
    program, unit-tested directly).

    ``p_cols`` [T, V]: target distributions at every verify column (column
    0 = pending, node (j, b) at ``_tree_col``); ``q_tree`` [D, W, V]: the
    draft distribution each node's token was sampled from (``q_tree[0]``
    is the shared root distribution all depth-1 siblings were drawn iid
    from); ``d_toks`` [D, W]. Returns ``(n_accept, branch, extra_token,
    new_rng)`` — the row emits the chosen branch's first ``n_accept``
    tokens then ``extra_token``.

    Exactness:

    - greedy (``temperature <= 0``): a node survives iff its token equals
      the target argmax at its parent column; the deepest surviving branch
      wins and the corrected/bonus token is the argmax at the divergence —
      the emitted stream is token-identical to sequential greedy decode
      (siblings are distinct by top-k, so at most one survives depth 1).
    - sampled: SpecInfer-style recursive rejection across the depth-1
      siblings — test each against the running residual (``r ← norm(max(r
      - q, 0))`` after every rejection), which keeps the emitted marginal
      EXACTLY ``p`` no matter how many siblings are tried — then the
      standard Leviathan/Chen chain rule down the accepted branch, with
      the usual residual at the first chain rejection and the bonus
      distribution at full depth.

    ``spec_on=False`` rows reject every sibling WITHOUT consuming residual
    mass (the update is gated), so the final "residual" is the plain
    target distribution ``p_0`` — the row takes an ordinary single-token
    step inside the same program, exactly like ``accept_tokens``.

    ``widths`` (learned ragged shapes, ISSUE 20): a monotone non-increasing
    per-depth widths tuple. ``p_cols`` is then the ragged flattened window
    ``[1 + sum(widths), V]`` (node (j, b) at ``_width_offsets(widths)[j-1]
    + b``) while ``q_tree``/``d_toks`` STAY the ``[D, W, V]`` / ``[D, W]``
    rectangle with ``W = widths[0]`` — the caller zero-pads dead ``q_tree``
    lanes and sets dead ``d_toks`` lanes to -1. Dead lanes then lose every
    test for free: a -1 token never equals a target argmax, and a zero
    ``q_at`` fails the ratio guard — so a branch's chain stops at its live
    depth, and the residual row at exactly the live depth degenerates to
    ``norm(clip(p - 0, 0)) = p``, which IS the bonus distribution."""
    ws = _widths_tuple(widths) if widths is not None else \
        _widths_tuple(width, depth)
    W, D = ws[0], len(ws)
    offs = _width_offsets(ws)
    rng, u_key, x_key = jax.random.split(rng, 3)
    us = jax.random.uniform(u_key, (W + D - 1,)) if W + D - 1 else \
        jnp.zeros((0,))
    greedy = temperature <= 0.0

    # ---- sampled: W-round sibling rejection at depth 1
    r = p_cols[0]
    q0 = q_tree[0, 0]
    b_star = jnp.asarray(-1, jnp.int32)
    accepted = jnp.asarray(False)
    for b in range(W):
        x = d_toks[0, b]
        q_at = q0[x]
        ok = (~accepted) & spec_on & (q_at > 0.0) & (us[b] * q_at <= r[x])
        b_star = jnp.where(ok, jnp.asarray(b, jnp.int32), b_star)
        accepted = accepted | ok
        r_new = jnp.clip(r - q0, 0.0, None)
        tot = r_new.sum()
        r_new = jnp.where(tot > 0.0, r_new / jnp.maximum(tot, 1e-30), r)
        r = jnp.where((~accepted) & spec_on, r_new, r)

    # ---- chain rule down the accepted branch (depths 2..D)
    bsafe = jnp.maximum(b_star, 0)
    toks_b = d_toks[:, bsafe]                                   # [D]
    # clamped per-depth column gather: a branch past its live depth reads
    # the depth's LAST live column — the value is never consulted (its
    # zero q_at already failed the chain), the clamp only keeps the
    # gather in-bounds for ragged widths
    col_tab = jnp.asarray(
        np.array([[offs[j] + min(b, ws[j] - 1) for b in range(W)]
                  for j in range(D)], np.int32))                # [D, W]
    cols_b = col_tab[:, bsafe]                                  # [D]
    p_b = p_cols[cols_b]                                        # [D, V]
    q_b = q_tree[:, bsafe]                                      # [D, V]
    if D > 1:
        jidx = jnp.arange(D - 1)
        p_at = p_b[jidx, toks_b[1:]]
        q_at = q_b[jidx + 1, toks_b[1:]]
        ok_chain = (us[W + jidx] * q_at <= p_at) & (q_at > 0.0)
        nacc = jnp.sum(jnp.cumprod(ok_chain.astype(jnp.int32)))
    else:
        nacc = jnp.asarray(0, jnp.int32)
    a_sampled = jnp.where(accepted, 1 + nacc, 0).astype(jnp.int32)

    # extra-token distribution table indexed by the acceptance count:
    # row 0 = the post-sibling residual, rows 1..D-1 = the chain-rejection
    # residuals, row D = the full-acceptance bonus distribution
    resid = jnp.clip(p_b[:-1] - q_b[1:], 0.0, None)  # [D-1, V]
    tots = resid.sum(axis=-1, keepdims=True)
    resid = jnp.where(tots > 0.0, resid / jnp.maximum(tots, 1e-30),
                      p_b[:-1])
    table = jnp.concatenate([r[None], resid, p_b[-1:]], axis=0)  # [D+1, V]
    extra_sampled = jax.random.categorical(
        x_key, jnp.log(jnp.maximum(table[a_sampled], 1e-30))
    ).astype(jnp.int32)

    # ---- greedy: pure argmax comparison per node (never consults q)
    tgt = jnp.argmax(p_cols, axis=-1).astype(jnp.int32)          # [T]
    pred = np.zeros((D, W), np.int64)  # parent column of node (j+1, b)
    for j in range(1, D):
        for b in range(W):
            pred[j, b] = offs[j - 1] + min(b, ws[j - 1] - 1)
    live = jnp.asarray(
        np.array([[b < ws[j] for b in range(W)] for j in range(D)]))
    ok_g = (d_toks == tgt[pred]) & live & spec_on
    a_per_b = jnp.sum(jnp.cumprod(ok_g.astype(jnp.int32), axis=0), axis=0)
    b_greedy = jnp.argmax(a_per_b).astype(jnp.int32)  # first max wins
    a_greedy = a_per_b[b_greedy]
    offs_arr = jnp.asarray(np.array(offs, np.int32))
    leaf = jnp.where(a_greedy == 0, 0,
                     offs_arr[jnp.maximum(a_greedy - 1, 0)] + b_greedy)
    extra_greedy = tgt[leaf]

    a = jnp.where(greedy, a_greedy, a_sampled)
    branch = jnp.where(greedy, b_greedy, bsafe)
    extra = jnp.where(greedy, extra_greedy, extra_sampled).astype(jnp.int32)
    return a, branch, extra, rng


# --------------------------------------------------------------- draft model
def build_draft(spec_draft: str, target_cfg, target_params,
                target_vocab: Optional[int] = None):
    """Resolve ``--spec_draft_config`` into ``(draft_cfg, draft_params)``.

    - ``take:N`` — self-speculative layer truncation (Draft & Verify): the
      draft is the target's FIRST N transformer blocks with the target's own
      embedding, final norm and unembedding (shared device buffers — zero
      extra HBM for those leaves). Same tokenizer/vocab by construction.
    - anything else — a model path / ``preset:`` spec loaded via the normal
      model loader; its vocab must match the target's (the acceptance rule
      compares distributions over one vocabulary).
    """
    from datatunerx_tpu.models.config import refuse_recurrent_state

    # a rejected proposal rewinds the cursor; a recurrent state cannot follow
    refuse_recurrent_state(target_cfg, "speculative decoding (--spec_draft)")
    if spec_draft.startswith("take:"):
        n = int(spec_draft.split(":", 1)[1])
        if not 1 <= n <= target_cfg.num_layers:
            raise ValueError(
                f"spec draft take:{n} out of range for a "
                f"{target_cfg.num_layers}-layer target")
        dcfg = dataclasses.replace(
            target_cfg, num_layers=n, name=f"{target_cfg.name}-take{n}",
            paged_kernel=False)
        layers = {
            name: {leaf: arr[:n] for leaf, arr in sub.items()}
            for name, sub in target_params["layers"].items()
        }
        dparams = dict(target_params)
        dparams["layers"] = layers
        return dcfg, dparams
    from datatunerx_tpu.utils.model_loader import load_model_and_tokenizer

    dcfg, dparams, _ = load_model_and_tokenizer(spec_draft,
                                                dtype=jnp.bfloat16)
    want = target_vocab or target_cfg.vocab_size
    if dcfg.vocab_size != want:
        raise ValueError(
            f"spec draft vocab {dcfg.vocab_size} != target vocab {want}; "
            "speculative verification needs one shared vocabulary")
    if getattr(dcfg, "paged_kernel", False):
        dcfg = dataclasses.replace(dcfg, paged_kernel=False)
    return dcfg, dparams


# ---------------------------------------------------------------- controller
class AdaptiveK:
    """Host-side acceptance-rate controller: per-slot EMAs gate individual
    rows out of drafting, the global EMA shrinks ``k`` and (``mode="auto"``)
    falls back to the plain pending-form decode program entirely — spec must
    never be slower than the non-spec path it replaces. Disabled state
    re-probes every ``probe_every`` plain steps so a workload shift can win
    spec back.

    Thread-safety: observed from the scheduler thread only; read (stats,
    /metrics) from HTTP threads — the lock keeps the tiny dicts consistent.
    """

    def __init__(self, k_max: int, mode: str = "auto", floor: float = 0.35,
                 alpha: float = 0.25, min_obs: int = 4,
                 probe_every: int = 64, tree: Optional[TreeSpec] = None):
        if k_max < 1:
            raise ValueError(f"spec_k must be >= 1, got {k_max}")
        self.k_max = int(k_max)
        self.tree = tree
        self.mode = mode
        self.floor = float(floor)
        self.alpha = float(alpha)
        self.min_obs = int(min_obs)
        self.probe_every = int(probe_every)
        self.global_ema: Optional[float] = None
        self._slot_ema: Dict[int, Tuple[float, int]] = {}
        self._slot_off: Dict[int, bool] = {}
        self._plain_streak = 0
        self.disabled_events = 0
        self._lock = threading.Lock()

    # ---- scheduler-side
    def observe(self, rows: List[Tuple[int, int, int]]):
        """``rows`` = [(slot, accepted, k)] for every row that drafted this
        step."""
        with self._lock:
            for slot, accepted, k in rows:
                rate = accepted / k if k else 0.0
                ema, n = self._slot_ema.get(slot, (rate, 0))
                ema = ema + self.alpha * (rate - ema)
                self._slot_ema[slot] = (ema, n + 1)
                if n + 1 >= self.min_obs and ema < self.floor:
                    if not self._slot_off.get(slot):
                        self.disabled_events += 1
                    self._slot_off[slot] = True
                g = self.global_ema if self.global_ema is not None else rate
                self.global_ema = g + self.alpha * (rate - g)
            if rows:
                self._plain_streak = 0

    def note_plain_step(self):
        with self._lock:
            self._plain_streak += 1

    def reset_slot(self, slot: int):
        """A finished request releases its slot; the next tenant starts with
        a clean acceptance history (spec re-enabled)."""
        with self._lock:
            self._slot_ema.pop(slot, None)
            self._slot_off.pop(slot, None)

    def force_off_slot(self, slot: int):
        """Hard per-slot disable (e.g. the draft could not be primed)."""
        with self._lock:
            self._slot_off[slot] = True
            self._slot_ema[slot] = (0.0, self.min_obs)

    # ---- decisions
    def slot_enabled(self, slot: int) -> bool:
        with self._lock:
            return not self._slot_off.get(slot, False)

    def current_k(self) -> int:
        """Shrink the proposal depth as global acceptance collapses: full k
        while acceptance holds, half on mediocre acceptance, 1 near the
        floor. Bounded set of distinct k values = bounded set of compiled
        verify programs."""
        with self._lock:
            return self.current_k_locked()

    def use_spec(self) -> bool:
        """Whether this tick runs the draft/verify program at all. ``on``
        pins it; ``auto`` backs off to the plain pending-form program when
        the global EMA sits under the floor (with periodic probes)."""
        if self.mode == "on":
            return True
        with self._lock:
            g = self.global_ema
            streak = self._plain_streak
        if g is None or g >= self.floor:
            return True
        return streak >= self.probe_every  # probe: one spec step, re-measure

    def current_plan(self) -> tuple:
        """The step shape this tick runs: ``("chain", k)``, or from
        ``AdaptiveTree`` ``("tree", widths)`` where ``widths`` is the
        learned per-depth width tuple."""
        with self._lock:
            return self.current_plan_locked()

    def current_plan_locked(self) -> tuple:
        return ("chain", self.current_k_locked())

    # ---- observability
    def snapshot(self) -> dict:
        with self._lock:
            plan = self.current_plan_locked()
            return {
                "k": self.current_k_locked(),
                "plan": [list(p) if isinstance(p, tuple) else p
                         for p in plan],
                "global_ema": self.global_ema,
                "slots": {s: round(e, 4)
                          for s, (e, _) in self._slot_ema.items()},
                "slots_off": sorted(s for s, off in self._slot_off.items()
                                    if off),
                "disabled_events": self.disabled_events,
            }

    def current_k_locked(self) -> int:
        g = self.global_ema
        if g is None or g >= 0.6:
            return self.k_max
        if g >= 0.3:
            return max(1, self.k_max // 2)
        return 1

    # ---- migration (dtx-kv-session payload "spec" sub-document)
    def export_slot_state(self, slot: int) -> dict:
        """JSON-safe controller state riding the session payload: the
        slot's own acceptance EMA plus the learned global signals, so an
        importer does not restart the controller cold (ISSUE 20)."""
        with self._lock:
            ema = self._slot_ema.get(slot)
            plan = self.current_plan_locked()
            return {
                "slot_ema": list(ema) if ema is not None else None,
                "slot_off": bool(self._slot_off.get(slot, False)),
                "global_ema": self.global_ema,
                "plan": [list(p) if isinstance(p, tuple) else p
                         for p in plan],
            }

    def import_slot_state(self, slot: int, state) -> None:
        """Warm this controller from an imported session's exported state.
        The slot EMA/off flag are restored verbatim (they ARE that
        session's history); the global EMA is adopted only when this
        controller has none — one migrating tenant must not overwrite a
        live fleet member's own evidence."""
        if not isinstance(state, dict):
            return
        with self._lock:
            ema = state.get("slot_ema")
            if isinstance(ema, (list, tuple)) and len(ema) == 2:
                self._slot_ema[slot] = (float(ema[0]), int(ema[1]))
            if state.get("slot_off"):
                self._slot_off[slot] = True
            g = state.get("global_ema")
            if g is not None and self.global_ema is None:
                self.global_ema = float(g)


class AdaptiveTree(AdaptiveK):
    """Learned tree shapes (ISSUE 20): the fixed ``WxD`` rectangle becomes
    a per-depth width VECTOR recomputed from acceptance evidence at tick
    granularity.

    - per-depth survival EMAs (fraction of drafting rows whose accepted
      prefix reached depth j) pick each depth's width from the bounded
      bucket set ``{1, ceil(W/2), W}`` with the same 0.6/0.3 thresholds as
      ``current_k`` — a bounded width set means a bounded compiled-program
      set, so adaptation never fragments the tree-step memo (the SAN003
      compile-budget gate asserts this);
    - widths are forced monotone non-increasing (each depth capped by the
      one above), which keeps every branch chain prefix-live — the
      invariant the ragged masks and clamped gathers rely on;
    - a DECISIVE-margin EMA tracks how often the draft root's top-1 logit
      margin is decisive; when it is nearly always decisive the depth-1
      width is capped at 1 — the draft-side early exit: sibling roots are
      pure draft FLOPs when the top token wins anyway.
    """

    DECISIVE_MARGIN = 4.0   # root top-2 logit gap that settles the branch
    DECISIVE_EMA = 0.9      # "nearly always": cap depth-1 width at 1

    def __init__(self, k_max: int, mode: str = "auto",
                 tree: Optional[TreeSpec] = None, **kw):
        if tree is None:
            raise ValueError("AdaptiveTree requires a TreeSpec")
        super().__init__(k_max, mode=mode, tree=tree, **kw)
        self._depth_ema: List[Optional[float]] = [None] * tree.depth
        self._decisive_ema: Optional[float] = None

    # ---- scheduler-side
    def observe_tree(self, depth_fracs, decisive_frac) -> None:
        """Per-tick tree evidence: ``depth_fracs[j]`` = fraction of
        drafting rows whose accepted prefix reached depth ``j+1``;
        ``decisive_frac`` = fraction whose draft root margin cleared
        ``DECISIVE_MARGIN``."""
        with self._lock:
            for j, f in enumerate(depth_fracs[:len(self._depth_ema)]):
                e = self._depth_ema[j]
                self._depth_ema[j] = float(f) if e is None else \
                    e + self.alpha * (float(f) - e)
            d = self._decisive_ema
            self._decisive_ema = float(decisive_frac) if d is None else \
                d + self.alpha * (float(decisive_frac) - d)

    def _bucket(self, ema: Optional[float]) -> int:
        W = self.tree.width
        if ema is None or ema >= 0.6:
            return W
        if ema >= 0.3:
            return max(1, -(-W // 2))
        return 1

    def current_plan_locked(self) -> tuple:
        g = self.global_ema
        if g is not None and g < 0.3:
            # near-floor global acceptance: width-1 chain-of-depth-D, the
            # same last resort the fixed controller takes
            return ("tree", (1,) * self.tree.depth)
        ws, cap = [], self.tree.width
        for j in range(self.tree.depth):
            w = min(self._bucket(self._depth_ema[j]), cap)
            if j == 0 and self._decisive_ema is not None \
                    and self._decisive_ema >= self.DECISIVE_EMA:
                w = 1  # draft-side early exit
            ws.append(w)
            cap = w
        return ("tree", tuple(ws))

    # ---- observability / migration
    def snapshot(self) -> dict:
        doc = super().snapshot()
        with self._lock:
            doc["depth_ema"] = [None if e is None else round(e, 4)
                                for e in self._depth_ema]
            doc["decisive_ema"] = None if self._decisive_ema is None \
                else round(self._decisive_ema, 4)
        return doc

    def export_slot_state(self, slot: int) -> dict:
        state = super().export_slot_state(slot)
        with self._lock:
            state["depth_ema"] = list(self._depth_ema)
            state["decisive_ema"] = self._decisive_ema
        return state

    def import_slot_state(self, slot: int, state) -> None:
        super().import_slot_state(slot, state)
        if not isinstance(state, dict):
            return
        with self._lock:
            de = state.get("depth_ema")
            if isinstance(de, (list, tuple)):
                for j, e in enumerate(de[:len(self._depth_ema)]):
                    if e is not None and self._depth_ema[j] is None:
                        self._depth_ema[j] = float(e)
            d = state.get("decisive_ema")
            if d is not None and self._decisive_ema is None:
                self._decisive_ema = float(d)


# ------------------------------------------------------------ device programs
# Bounded process-wide memo, the engine _Programs pattern: twin engines
# (bench spec-on/off, parity tests) built from equal (target cfg, draft cfg,
# max_seq_len, kv_quant) share one set of jitted spec programs — draft and
# target params, caches and per-slot state all arrive as ARGUMENTS.
_SPEC_MEMO: "collections.OrderedDict" = collections.OrderedDict()
_SPEC_MEMO_MAX = 8


def spec_programs(tcfg, dcfg, max_seq_len: int, kv_quant,
                  epilogue: str = "off") -> "SpecPrograms":
    from datatunerx_tpu.models.config import refuse_recurrent_state

    for cfg in (tcfg, dcfg):
        refuse_recurrent_state(cfg, "speculative decoding (--spec_draft)")
    try:
        key = (repr(tcfg), repr(dcfg), int(max_seq_len), kv_quant, epilogue)
    except Exception:  # noqa: BLE001 — memoization is best-effort
        key = None
    progs = None if key is None else _SPEC_MEMO.get(key)
    if progs is None:
        progs = SpecPrograms(tcfg, dcfg, max_seq_len, kv_quant,
                             epilogue=epilogue)
        if key is not None:
            _SPEC_MEMO[key] = progs
            while len(_SPEC_MEMO) > _SPEC_MEMO_MAX:
                _SPEC_MEMO.popitem(last=False)
    else:
        _SPEC_MEMO.move_to_end(key)
    return progs


class SpecPrograms:
    """Jitted programs of the speculative state machine. All slots live in
    PENDING-TOKEN form while spec is enabled: the last emitted token's KV is
    not yet written, so a verify forward of ``[pending, d_0..d_{k-1}]``
    yields target distributions for positions ``pos+1..pos+k+1`` in one shot
    and the corrected/bonus token becomes the next pending — no second
    target forward per step.

    Ragged per-row advance: the verify forward writes ``k+1`` tokens for
    every row and rolls each row's cursor back to ``old + 1 + accepted``.
    Rejected-lane KV/positions are stale but sit at cursors strictly beyond
    every live write head, where monotonic rope positions + the causal check
    mask them until the next contiguous write overwrites them — the same
    argument that already covers recycled blocks. Paged rows reserve
    ``spec_k + 1`` tokens of block overshoot at admission
    (``ops.paged_attention.blocks_for_depth``) so verify writes stay
    physical; dense rows rely on the scatter's drop-OOB mode exactly like
    the existing decode program."""

    def __init__(self, tcfg, dcfg, max_seq_len: int, kv_quant,
                 epilogue: str = "off"):
        self.tcfg = tcfg
        self.dcfg = dcfg
        self.max_seq_len = max_seq_len
        self.kv_quant = kv_quant
        # "off" = the legacy argsort sampler everywhere (byte-identical
        # pre-epilogue programs); "kernel" / "xla" = the fused sampling
        # epilogue with that implementation (ops/pallas_sampling.py)
        self.epilogue = epilogue
        self.enter = jax.jit(self._enter_impl, static_argnames=("mode",))
        # as in batched_engine._Programs: a program that returns a cache
        # (the target's ``tcache``, the draft's ``dcache``) consumes the one
        # it is given, so both pools are written in place
        self.prime = jax.jit(self._prime_impl, donate_argnums=(1,))
        self.step = jax.jit(self._step_impl, static_argnames=("k", "mode"),
                            donate_argnums=(3, 4))
        self.tree_step = jax.jit(
            self._tree_step_impl,
            static_argnames=("widths", "mode"), donate_argnums=(3, 4))
        self.decode = jax.jit(self._decode_pending_impl,
                              static_argnames=("K", "mode"),
                              donate_argnums=(2,))
        self.settle = jax.jit(self._settle_impl, donate_argnums=(2,))

    # ---- one batched token draw, epilogue-aware
    def _draw(self, logits, temps, top_ps, rng, mode: str):
        """The legacy split + ``_sample_jit`` pair when the epilogue is off
        (``mode == "off"``) — byte-identical pre-epilogue programs — else
        the fused epilogue with the same key-split order, so the per-slot
        PRNG stream evolves identically either way."""
        with jax.named_scope("dtx.sample"):
            if mode == "off" or self.epilogue == "off":
                split = jax.vmap(jax.random.split)(rng)
                rng2, sub = split[:, 0], split[:, 1]
                return (jax.vmap(_sample_jit)(logits, temps, top_ps, sub),
                        rng2)
            return sample_rows(logits, temps, top_ps, rng, mode=mode,
                               impl=self.epilogue)

    def _draw_keys(self, logits, temps, top_ps, keys, mode: str):
        """One draw from PRE-SPLIT per-row keys (the tree step's W iid
        sibling draws)."""
        with jax.named_scope("dtx.sample"):
            if mode == "off" or self.epilogue == "off":
                return jax.vmap(_sample_jit)(logits, temps, top_ps, keys)
            return fused_sample(logits, temps, top_ps, keys, mode=mode,
                                impl=self.epilogue)

    # ---- logits-form → pending-form transition (first emitted token)
    def _enter_impl(self, logits, pending, remaining, active, rng,
                    temps, top_ps, stops, fresh, *, mode: str = "off"):
        """Sample one token from each fresh row's held logits (the same
        split-then-sample the plain decode step would do), emit it, and make
        it the row's pending token. Cache and cursor untouched — the token's
        KV is written by the row's first verify/pending forward. ``mode``
        is the engine's static batch sampling mode when the fused epilogue
        is on, or the ``"off"`` sentinel (one compiled variant, the legacy
        sampler) when it is not."""
        nxt, rng2 = self._draw(logits, temps, top_ps, rng, mode)
        is_stop = jnp.any(nxt[:, None] == stops, axis=1)
        emit = fresh & active & ~is_stop & (remaining > 0)
        emitted = jnp.where(emit, nxt, -1)
        new_active = jnp.where(fresh, emit & (remaining > 1), active)
        remaining = remaining - emit.astype(jnp.int32)
        pending = jnp.where(emit, nxt, pending)
        rng = jnp.where(fresh[:, None], rng2, rng)
        return emitted, pending, remaining, new_active, rng

    # ---- draft prefill of one slot's context row
    def _prime_impl(self, dparams, dcache, slot, tokens, mask, positions,
                    prime_len):
        """Prefill ``tokens`` (left-pad-bucketed prompt + settled emitted
        tokens) through the DRAFT into a fresh full-width row, then install
        it as ``slot``'s row of the per-slot draft cache. Priming feeds only
        acceptance quality — verification guarantees exactness regardless —
        so an approximate re-primed context after import is correct by
        construction."""
        W = dcache["k"].shape[2]
        row = init_cache(self.dcfg, 1, W, dtype=jnp.bfloat16)
        _, row = forward(
            dparams, tokens, self.dcfg, positions=positions,
            attention_mask=mask, cache=row, compute_dtype=jnp.bfloat16,
        )
        out = dict(dcache)
        out["k"] = jax.lax.dynamic_update_slice(
            dcache["k"], row["k"], (0, slot, 0, 0))
        out["v"] = jax.lax.dynamic_update_slice(
            dcache["v"], row["v"], (0, slot, 0, 0))
        out["pos"] = jax.lax.dynamic_update_slice(
            dcache["pos"], row["pos"], (slot, 0))
        out["len"] = dcache["len"].at[slot].set(prime_len)
        return out

    # ---- the speculative super-step: propose k, verify once, accept
    def _step_impl(self, tparams, dparams, lora, tcache, dcache,
                   pending, pos, remaining, active, rng, temps, top_ps,
                   stops, adapter_idx, spec_on, *, k: int,
                   mode: str = "topp"):
        """``mode`` is a STATIC batch property the engine derives from its
        live requests each tick (bounded set of compiled variants):

        - ``"greedy"`` — every drafting row has ``temperature <= 0``:
          acceptance is pure argmax comparison, so no distribution (and no
          full-vocab sort) is ever materialized;
        - ``"simple"`` — sampled rows exist but none filters
          (``top_p >= 1``): distributions are plain softmax;
        - ``"topp"`` — the fully general sorted top-p path.
        Each is exact for the batches it is selected for; greedy rows
        inside a sampled batch still resolve exactly via the traced
        ``temperature <= 0`` selects."""
        S = pending.shape[0]
        participate = active
        drow = participate & spec_on

        # draft propose: k+1 single-token forwards in one scan. Iteration i
        # feeds the previous token (pending at i=0) at rope position pos+i
        # and samples proposal d_i from the draft's distribution q_i. The
        # (k+1)-th iteration's sample is discarded — it runs only to write
        # d_{k-1}'s KV so a fully-accepted row's draft cache stays complete.
        d_len0 = dcache["len"]

        def dstep(carry, i):
            cur, dc, r = carry
            dlogits, dc = forward(
                dparams, cur[:, None], self.dcfg,
                positions=(pos + i)[:, None],
                attention_mask=drow[:, None].astype(jnp.int32),
                cache=dc, compute_dtype=jnp.bfloat16,
            )
            last = dlogits[:, -1]
            if mode == "greedy":
                nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
                q = jnp.zeros((S, 1), jnp.float32)  # placeholder, unused
            else:
                nxt, r = self._draw(last, temps, top_ps, r, mode)
                q = jax.vmap(
                    lambda lg, t, tp: sampling_probs(
                        lg, t, tp, exact_topp=(mode == "topp"))
                )(last, temps, top_ps)
            return (nxt, dc, r), (nxt, q)

        (_, dcache, rng), (d_all, q_all) = jax.lax.scan(
            dstep, (pending, dcache, rng),
            jnp.arange(k + 1, dtype=jnp.int32))
        d_toks = jnp.transpose(d_all[:k])              # [S, k]

        # verify: ONE target forward over [pending, d_0..d_{k-1}] — the
        # chunked-prefill/extend machinery's multi-token path, so the paged
        # cache, pooled LoRA adapters and int8 kv_quant all keep working.
        # Rows not drafting mask out the proposal columns and take a plain
        # single-token step on column 0.
        t_len0 = tcache["len"]
        vtoks = jnp.concatenate([pending[:, None], d_toks], axis=1)
        vpos = pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        vmask = jnp.concatenate(
            [participate[:, None],
             jnp.broadcast_to(drow[:, None], (S, k))], axis=1)
        vlogits, tcache = forward(
            tparams, vtoks, self.tcfg, positions=vpos,
            attention_mask=vmask.astype(jnp.int32), cache=tcache, lora=lora,
            lora_adapter_idx=(adapter_idx if lora is not None else None),
            compute_dtype=jnp.bfloat16,
        )
        if mode == "greedy":
            # acceptance without distributions: a proposal survives iff it
            # IS the target argmax at its position, and the corrected/bonus
            # token is the argmax at the first divergence — token-identical
            # to sequential greedy decode by construction
            tgt_argmax = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            ok = (d_toks == tgt_argmax[:, :k]) & drow[:, None]
            acc_prefix = jnp.cumprod(ok.astype(jnp.int32), axis=1)
            a = jnp.sum(acc_prefix, axis=1).astype(jnp.int32)
            extra = jnp.take_along_axis(
                tgt_argmax, a[:, None], axis=1)[:, 0]
        else:
            q_dists = jnp.transpose(q_all[:k], (1, 0, 2))  # [S, k, V]
            p_dists = jax.vmap(
                lambda row_logits, t, tp: jax.vmap(
                    lambda lg: sampling_probs(
                        lg, t, tp, exact_topp=(mode == "topp")))(row_logits)
            )(vlogits, temps, top_ps)  # [S, k+1, V]
            a, extra, rng = jax.vmap(accept_tokens)(
                p_dists, q_dists, d_toks, temps, rng, drow)
        a = jnp.where(participate, a, 0)

        # emission: accepted prefix + corrected/bonus token, truncated by
        # the row's stop set and token budget exactly as the sequential
        # decode loop would have (a stop token is never emitted; the budget
        # bounds emitted count; either truncation deactivates the row)
        idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        d_ext = jnp.concatenate(
            [d_toks, jnp.full((S, 1), -1, jnp.int32)], axis=1)
        cand = jnp.where(idx < a[:, None], d_ext,
                         jnp.where(idx == a[:, None], extra[:, None], -1))
        is_stop = jnp.any(cand[:, :, None] == stops[:, None, :], axis=2) \
            & (cand >= 0)
        navail = a + 1
        stop_idx = jnp.min(jnp.where(is_stop, idx, k + 2), axis=1)
        n_emit = jnp.minimum(jnp.minimum(navail, stop_idx), remaining)
        n_emit = jnp.where(participate, n_emit, 0)
        emitted = jnp.where(idx < n_emit[:, None], cand, -1)
        new_remaining = remaining - n_emit
        new_active = participate & (n_emit == navail) & (new_remaining > 0)
        pending = jnp.where(new_active, extra, pending)

        # ragged advance: each row's cursor moves by 1 + accepted (the old
        # pending plus the kept proposals); rejected-lane writes beyond the
        # new cursor are dead — masked by causal position until overwritten
        adv = jnp.where(participate, 1 + a, 0)
        pos = pos + adv
        tcache = dict(tcache)
        tcache["len"] = t_len0 + adv
        dcache = dict(dcache)
        dcache["len"] = d_len0 + jnp.where(drow, adv, 0)
        return (emitted, a, tcache, dcache, pending, pos, new_remaining,
                new_active, rng)

    # ---- the tree super-step: draft a widths-shaped tree, verify once
    def _tree_step_impl(self, tparams, dparams, lora, tcache, dcache,
                       pending, pos, remaining, active, rng, temps, top_ps,
                       stops, adapter_idx, spec_on, *, widths: tuple,
                       mode: str = "topp"):
        """The ``_step_impl`` shape with a TREE of drafts per slot:
        ``widths[j-1]`` parallel branches at depth j sharing the pending
        root, flattened into ``1 + sum(widths)`` verify columns under the
        branch ancestry mask, ONE target forward, longest-surviving-path
        acceptance (``accept_tree_tokens``). ``widths`` is the monotone
        non-increasing per-depth width tuple — the fixed ``WxD`` rectangle
        is ``(W,) * D``, and ``AdaptiveTree`` shrinks individual depths
        from acceptance evidence. Each depth's draft forward runs only its
        OWN ``widths[j-1]`` live lanes (the learned-shape FLOP saving);
        dead rectangle lanes exist only in the acceptance inputs, as -1
        tokens with zero draft mass, and lose every test by construction.

        Also returns the draft root's top-2 logit margin per row — the
        decisiveness signal ``AdaptiveTree`` turns into the draft-side
        early exit.

        Tree windows BREAK the chain's stale-lane safety argument (a
        rejected sibling shares its rope position with an accepted one, so
        causal masking alone would admit it on a later read); after
        acceptance the chosen path is compacted into the contiguous cursor
        lanes and every other window lane's position is scrubbed to the
        sentinel (``compact_window``), restoring the chain invariant the
        settle / export / migration paths assume."""
        ws = _widths_tuple(widths)
        W, D = ws[0], len(ws)
        offs = _width_offsets(ws)
        T = 1 + sum(ws)
        S = pending.shape[0]
        participate = active
        drow = participate & spec_on
        d_len0 = dcache["len"]
        t_len0 = tcache["len"]
        exact = mode == "topp"

        # ---- draft: the pending root, then D ragged tree forwards (the
        # last one exists only to write the leaves' KV — samples discarded)
        dlogits, dcache = forward(
            dparams, pending[:, None], self.dcfg, positions=pos[:, None],
            attention_mask=drow[:, None].astype(jnp.int32),
            cache=dcache, compute_dtype=jnp.bfloat16,
        )
        l0 = dlogits[:, -1]
        top2, _ = jax.lax.top_k(l0, 2)
        margin = top2[:, 0] - top2[:, 1]  # root decisiveness, host EMA'd
        if mode == "greedy":
            # distinct top-W roots: at most one can match the target
            # argmax, and the verify walks every branch anyway
            _, topw = jax.lax.top_k(l0, W)
            cur = topw.astype(jnp.int32)                        # [S, W]
            q0 = jnp.zeros((S, 1), jnp.float32)  # placeholder, unused
        else:
            split = jax.vmap(lambda r: jax.random.split(r, W + 1))(rng)
            rng = split[:, 0]
            cur = jnp.stack(
                [self._draw_keys(l0, temps, top_ps, split[:, 1 + b],
                                 "off" if self.epilogue == "off" else mode)
                 for b in range(W)], axis=1)                    # iid from q0
            q0 = jax.vmap(
                lambda lg, t, tp: sampling_probs(lg, t, tp,
                                                 exact_topp=exact)
            )(l0, temps, top_ps)
        d_depth, q_depth = [cur], [q0]
        for j in range(1, D + 1):
            wj = ws[j - 1]
            wmask = jnp.asarray(tree_draft_mask(ws, j))
            dlogits, dcache = forward(
                dparams, cur[:, :wj], self.dcfg,
                positions=jnp.broadcast_to((pos + j)[:, None], (S, wj)),
                attention_mask=jnp.broadcast_to(
                    drow[:, None], (S, wj)).astype(jnp.int32),
                cache=dcache, compute_dtype=jnp.bfloat16,
                window_mask=jnp.broadcast_to(
                    wmask[None], (S, wj, 1 + sum(ws[:j]))),
                window_start=d_len0,
            )
            if j == D:
                break
            wn = ws[j]  # next depth's width (<= wj: prefix-live chains)
            if mode == "greedy":
                nxt = jnp.argmax(dlogits[:, :wn], axis=-1).astype(jnp.int32)
                qj = jnp.zeros((S, W, 1), jnp.float32)
            else:
                split = jax.vmap(lambda r: jax.random.split(r, wn + 1))(rng)
                rng = split[:, 0]
                nxt = jnp.stack(
                    [self._draw_keys(
                        dlogits[:, b], temps, top_ps, split[:, 1 + b],
                        "off" if self.epilogue == "off" else mode)
                     for b in range(wn)], axis=1)
                qn = jax.vmap(
                    lambda row, t, tp: jax.vmap(
                        lambda lg: sampling_probs(lg, t, tp,
                                                  exact_topp=exact))(row)
                )(dlogits[:, :wn], temps, top_ps)              # [S, wn, V]
                # dead rectangle lanes carry ZERO draft mass — the
                # acceptance rule's q_at > 0 guard retires them for free
                qj = jnp.pad(qn, ((0, 0), (0, W - wn), (0, 0)))
            # dead-lane tokens are -1: never equal to any target argmax
            cur = jnp.pad(nxt, ((0, 0), (0, W - wn)), constant_values=-1)
            d_depth.append(cur)
            q_depth.append(qj)
        d_toks = jnp.stack(d_depth, axis=1)                     # [S, D, W]

        # ---- verify: ONE target forward over the ragged flattened tree
        vtoks = jnp.concatenate(
            [pending[:, None]]
            + [d_toks[:, j, :ws[j]] for j in range(D)], axis=1)  # [S, T]
        depth_of = np.concatenate(
            [[0]] + [[j] * ws[j - 1]
                     for j in range(1, D + 1)]).astype(np.int32)
        vpos = pos[:, None] + jnp.asarray(depth_of)[None, :]
        vmask = jnp.concatenate(
            [participate[:, None],
             jnp.broadcast_to(drow[:, None], (S, T - 1))], axis=1)
        wmask_v = jnp.asarray(tree_verify_mask(ws))
        vlogits, tcache = forward(
            tparams, vtoks, self.tcfg, positions=vpos,
            attention_mask=vmask.astype(jnp.int32), cache=tcache, lora=lora,
            lora_adapter_idx=(adapter_idx if lora is not None else None),
            compute_dtype=jnp.bfloat16,
            window_mask=jnp.broadcast_to(wmask_v[None], (S, T, T)),
            window_start=t_len0,
        )
        if mode == "greedy":
            tgt = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # [S, T]
            pred = np.zeros((D, W), np.int64)  # parent column per node
            for j in range(1, D):
                for b in range(W):
                    pred[j, b] = offs[j - 1] + min(b, ws[j - 1] - 1)
            live = jnp.asarray(
                np.array([[b < ws[j] for b in range(W)]
                          for j in range(D)]))
            ok = (d_toks == tgt[:, pred]) & live[None] & drow[:, None, None]
            a_per_b = jnp.sum(
                jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)  # [S, W]
            b_sel = jnp.argmax(a_per_b, axis=1).astype(jnp.int32)
            a = jnp.take_along_axis(a_per_b, b_sel[:, None], axis=1)[:, 0]
            offs_arr = jnp.asarray(np.array(offs, np.int32))
            leaf = jnp.where(
                a == 0, 0, offs_arr[jnp.maximum(a - 1, 0)] + b_sel)
            extra = jnp.take_along_axis(tgt, leaf[:, None], axis=1)[:, 0]
        else:
            p_cols = jax.vmap(
                lambda row_logits, t, tp: jax.vmap(
                    lambda lg: sampling_probs(lg, t, tp,
                                              exact_topp=exact))(row_logits)
            )(vlogits, temps, top_ps)                          # [S, T, V]
            V = p_cols.shape[-1]
            q_tree = jnp.stack(
                [jnp.broadcast_to(q_depth[0][:, None], (S, W, V))]
                + q_depth[1:], axis=1)                         # [S, D, W, V]
            a, b_sel, extra, rng = jax.vmap(
                lambda p, q, d, t, r, s: accept_tree_tokens(
                    p, q, d, t, r, s, widths=ws)
            )(p_cols, q_tree, d_toks, temps, rng, drow)
        a = jnp.where(participate, a, 0)
        b_sel = jnp.where(drow, b_sel, 0)

        # ---- emission: the chosen branch's accepted prefix + extra token
        path = jnp.take_along_axis(
            d_toks, b_sel[:, None, None], axis=2)[:, :, 0]      # [S, D]
        idx = jnp.arange(D + 1, dtype=jnp.int32)[None, :]
        p_ext = jnp.concatenate(
            [path, jnp.full((S, 1), -1, jnp.int32)], axis=1)
        cand = jnp.where(idx < a[:, None], p_ext,
                         jnp.where(idx == a[:, None], extra[:, None], -1))
        is_stop = jnp.any(cand[:, :, None] == stops[:, None, :], axis=2) \
            & (cand >= 0)
        navail = a + 1
        stop_idx = jnp.min(jnp.where(is_stop, idx, D + 2), axis=1)
        n_emit = jnp.minimum(jnp.minimum(navail, stop_idx), remaining)
        n_emit = jnp.where(participate, n_emit, 0)
        emitted = jnp.where(idx < n_emit[:, None], cand, -1)
        new_remaining = remaining - n_emit
        new_active = participate & (n_emit == navail) & (new_remaining > 0)
        pending = jnp.where(new_active, extra, pending)

        # ---- compact the window: accepted path → contiguous cursor lanes,
        # everything else scrubbed to the sentinel (both caches share the
        # window column layout). The per-depth clamp keeps the gather
        # in-bounds for ragged widths — clamped entries sit at depths
        # beyond the accepted length, where compact_window never reads.
        col_tab = jnp.asarray(
            np.array([[offs[j] + min(b, ws[j] - 1) for j in range(D)]
                      for b in range(W)], np.int32))            # [W, D]
        src_cols = col_tab[b_sel]                               # [S, D]
        tcache = compact_window(tcache, participate, t_len0, src_cols, a,
                                pos, T)
        dcache = compact_window(dcache, drow, d_len0, src_cols, a, pos, T)
        adv = jnp.where(participate, 1 + a, 0)
        pos = pos + adv
        tcache = dict(tcache)
        tcache["len"] = t_len0 + adv
        dcache = dict(dcache)
        dcache["len"] = d_len0 + jnp.where(drow, adv, 0)
        return (emitted, a, tcache, dcache, pending, pos, new_remaining,
                new_active, rng, margin)

    # ---- plain decode in pending form (the never-slower fallback)
    def _decode_pending_impl(self, tparams, lora, tcache, pending, pos,
                             remaining, active, rng, temps, top_ps, stops,
                             adapter_idx, *, K: int, mode: str = "off"):
        """K-token chunked decode over pending-form slots: forward the
        pending token, sample its successor from the resulting logits, make
        that the new pending. Per-token cost identical to the non-spec
        ``_decode_impl`` (one forward + one sample), so the adaptive
        controller's fallback never costs more than spec-off decode.
        ``mode`` as in ``_enter_impl``."""
        def step(carry, _):
            pending, tcache, pos, remaining, active, rng = carry
            prev_len = tcache["len"]
            logits, tcache = forward(
                tparams, pending[:, None], self.tcfg,
                positions=pos[:, None],
                attention_mask=active[:, None].astype(jnp.int32),
                cache=tcache, lora=lora,
                lora_adapter_idx=(adapter_idx if lora is not None else None),
                compute_dtype=jnp.bfloat16,
            )
            tcache = dict(tcache)
            tcache["len"] = prev_len + active.astype(jnp.int32)
            pos = pos + active.astype(jnp.int32)
            nxt, rng = self._draw(logits[:, -1], temps, top_ps, rng, mode)
            is_stop = jnp.any(nxt[:, None] == stops, axis=1)
            emit = active & ~is_stop & (remaining > 0)
            emitted = jnp.where(emit, nxt, -1)
            new_active = emit & (remaining > 1)
            remaining = remaining - emit.astype(jnp.int32)
            pending = jnp.where(emit, nxt, pending)
            return (pending, tcache, pos, remaining, new_active, rng), emitted

        (pending, tcache, pos, remaining, active, rng), emitted = \
            jax.lax.scan(step, (pending, tcache, pos, remaining, active, rng),
                         None, length=K)
        return emitted, tcache, pending, pos, remaining, active, rng

    # ---- pending-form → logits-form (export/migration)
    def _settle_impl(self, tparams, lora, tcache, pending, pos, adapter_idx,
                     onehot):
        """Write ONE slot's pending token through the target (mask one-hot;
        every other row's cursor restored) and return the resulting
        next-token logits — the slot is then in the standard logits-form
        state the KV-migration wire format expects."""
        prev_len = tcache["len"]
        logits, tcache = forward(
            tparams, pending[:, None], self.tcfg, positions=pos[:, None],
            attention_mask=onehot[:, None].astype(jnp.int32), cache=tcache,
            lora=lora,
            lora_adapter_idx=(adapter_idx if lora is not None else None),
            compute_dtype=jnp.bfloat16,
        )
        tcache = dict(tcache)
        tcache["len"] = prev_len + onehot.astype(jnp.int32)
        pos = pos + onehot.astype(jnp.int32)
        return logits[:, -1], tcache, pos
