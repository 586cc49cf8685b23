"""Kimi Delta Attention (KDA): a linear-attention mixer whose memory is a
matrix of constant size per head, not rows that grow with the context.

Per head the state ``S [d_k, d_v]`` (float32) follows the gated delta rule

    S' = diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

with a decay ``alpha_t = exp(g_t)`` per key channel (``g_t <= 0``) and a write
strength ``beta_t`` per head. q, k and v first pass a short depthwise causal
convolution (``short_conv``) whose own state is the last ``kernel - 1``
pre-convolution rows.

Two forms of the same recurrence:

- ``state_step``: one token (decode). S is read twice and written once: one
  pass gives ``S'^T k`` and ``S'^T q`` together, the second writes ``S_t``;
  ``o_t`` follows from the first (``S_t^T q = S'^T q + beta (k.q)(v - S'^T k)``).
- ``chunk_states``: ``T`` tokens in sub-chunks of ``chunk`` rows (prefill).
  Inside a sub-chunk with incoming state ``S0`` and ``G_t = sum_{i<=t} g_i``:

      A[t,s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      (s < t)
      U      = (I + A)^-1 diag(beta) (V - (K * exp(G)) S0)
      O      = (Q * exp(G)) S0 + tril(QK) U,   QK[t,s] like A with q_t, s <= t
      S_C    = diag(exp(G_C)) S0 + (K * exp(G_C - G))^T U

  Only differences ``G_t - G_s <= 0`` and ``G`` itself are exponentiated:
  ``exp(-G)`` overflows float32 after a few dozen rows at the lower bound.

A row that is a pad (``valid`` false) must leave both states as they were:
its ``beta`` and ``g`` are 0 and its pre-convolution rows are zeroed. Pads lie
at a row's LEFT (the engine left-pads prompts to a bucket) or are a whole idle
row of a decode step; ``short_conv`` places the stored rows right before the
first real one. Everything here is float32 at HIGHEST matmul precision: the
state is the model's memory, and a bf16 pass over it is a lower precision than
the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64


def short_conv(x, w, state, valid, bias=None):
    """Depthwise causal convolution then SiLU. x [B, T, C] pre-convolution
    rows; w [C, K]; state [B, K-1, C] or None (no history); valid [B, T] bool
    or None; bias [C] or None. Returns (y [B, T, C] float32, new state [B,
    K-1, C] in x's dtype): ``y_t = silu(bias + sum_i w[:, i] z_{t-(K-1)+i})``
    over the history followed by the real rows."""
    B, T, C = x.shape
    K = w.shape[-1]
    if valid is not None:
        x = jnp.where(valid[:, :, None], x, jnp.zeros((), x.dtype))
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    state = state.astype(x.dtype)
    if T == 1:
        ext = jnp.concatenate([state, x], axis=1)  # [B, K, C]
        new_state = ext[:, 1:]
        if valid is not None:  # an idle row shifts nothing
            new_state = jnp.where(valid[:, :, None], new_state, state)
    else:
        ext = jnp.concatenate([jnp.zeros_like(state), x], axis=1)  # [B, T+K-1, C]
        if valid is None:
            ext = ext.at[:, :K - 1].set(state)
        else:
            # pads lie at the left: the history goes right before the first
            # real row, over pad rows that are zero
            n_pad = T - jnp.sum(valid.astype(jnp.int32), axis=1)
            ext = jax.vmap(lambda e, s, n: jax.lax.dynamic_update_slice(
                e, s, (n, 0)))(ext, state, n_pad)
        new_state = ext[:, T:]
    wf = w.astype(jnp.float32)
    y = sum(ext[:, i:i + T].astype(jnp.float32) * wf[None, None, :, i]
            for i in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y), new_state


def gate(f, a_log, dt_bias, lower_bound: float):
    """The lower-bounded decay gate. f [..., H, d] (the gate projection),
    a_log [H], dt_bias [H, d]. Returns ``g`` float32 in ``(lower_bound, 0)``:
    ``lower_bound * sigmoid(exp(A_log) * (f + dt_bias))`` for a negative bound."""
    a = jnp.exp(a_log.astype(jnp.float32))[:, None]
    return lower_bound * jax.nn.sigmoid(
        a * (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)))


def l2_normalize(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(norm, eps)


def state_step(S, q, k, v, g, beta):
    """One token of the recurrence. S [B, H, dk, dv] float32; q, k, g [B, H,
    dk]; v [B, H, dv]; beta [B, H]. Returns (o [B, H, dv], new S). A row with
    ``g`` 0 and ``beta`` 0 leaves S as it was."""
    alpha = jnp.exp(g)
    # S'^T k and S'^T q from one pass over S (S' = diag(alpha) S)
    ak, aq = alpha * k, alpha * q
    pred = jnp.sum(S * ak[..., None], axis=-2)
    read = jnp.sum(S * aq[..., None], axis=-2)
    delta = beta[..., None] * (v - pred)  # [B, H, dv]
    S = alpha[..., None] * S + k[..., None] * delta[..., None, :]
    o = read + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return o, S


def _chunk(S0, q, k, v, g, beta):
    """One sub-chunk. S0 [B, H, dk, dv]; q, k, g [B, H, C, dk]; v [B, H, C,
    dv]; beta [B, H, C]. Returns (O [B, H, C, dv], S_C)."""
    C = q.shape[2]
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)  # noqa: E731
    G = jnp.cumsum(g, axis=2)
    eG = jnp.exp(G)
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    # decay[t, s, c] = exp(G_t[c] - G_s[c]) for s <= t, 0 elsewhere
    diff = G[:, :, :, None, :] - G[:, :, None, :, :]
    decay = jnp.exp(jnp.where((s <= t)[None, None, :, :, None], diff, -jnp.inf))
    kd = k[:, :, None, :, :] * decay  # k_s[c] * decay[t, s, c]
    kk = jnp.sum(k[:, :, :, None, :] * kd, axis=-1)  # [B, H, C, C], s <= t
    qk = jnp.sum(q[:, :, :, None, :] * kd, axis=-1)
    A = jnp.where(s < t, beta[..., None] * kk, 0.0)
    rhs = beta[..., None] * (v - mm("bhck,bhkv->bhcv", k * eG, S0))
    U = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    O = mm("bhck,bhkv->bhcv", q * eG, S0) + mm("bhts,bhsv->bhtv", qk, U)
    last = G[:, :, -1:, :]
    S = (jnp.exp(last[:, :, 0, :, None]) * S0
         + mm("bhck,bhcv->bhkv", k * jnp.exp(last - G), U))
    return O, S


def chunk_states(S0, q, k, v, g, beta, chunk: int = CHUNK):
    """``T`` tokens of the recurrence in sub-chunks of ``chunk`` rows. S0 [B,
    H, dk, dv] float32; q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta [B, T,
    H]; pads carry ``g`` 0, ``beta`` 0. Returns (o [B, T, H, dv], S_T)."""
    B, T, H, dk = q.shape
    C = min(chunk, T)
    pad = -T % C
    n = (T + pad) // C

    def split(x):  # [B, T, H, ...] -> [n, B, H, C, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, n, C) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    xs = tuple(split(x.astype(jnp.float32)) for x in (q, k, v, g, beta))

    def body(S, x):
        O, S = _chunk(S, *x)
        return S, O

    S, O = jax.lax.scan(body, S0.astype(jnp.float32), xs)
    O = jnp.moveaxis(jnp.moveaxis(O, 0, 1), 2, 3)  # [B, n, C, H, dv]
    return O.reshape(B, n * C, H, O.shape[-1])[:, :T], S


def recurrence(S0, q, k, v, g, beta):
    """The recurrence token by token (``state_step`` under a scan): what the
    chunk form must equal. Same shapes as ``chunk_states``."""
    def body(S, x):
        o, S = state_step(S, *x)
        return S, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    S, o = jax.lax.scan(body, S0, xs)
    return jnp.moveaxis(o, 0, 1), S
