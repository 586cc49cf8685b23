"""Mamba-2 (state-space duality): a mixer whose memory is a matrix of constant
size per head, decayed by ONE scalar a head and a token.

Per head the state ``S [P, N]`` (float32; ``P`` channels of the head, ``N`` the
state size) follows

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

with ``dt_t = softplus(dt~_t + dt_bias)`` per head, ``a_t = exp(dt_t A)``, ``A =
-exp(A_log) < 0``, and ``B_t``, ``C_t [N]`` shared by the heads of a group. x, B
and C first pass one short depthwise causal convolution with bias
(``ops/kda.py:short_conv``, whose own state is the last ``kernel - 1``
pre-convolution rows).

Two forms of the same recurrence:

- ``state_step``: one token (decode). The recurrence needs S read once and
  written once; XLA compiles this form to three passes (one fusion updates S
  in place, a second reads it again for the read-out). Over a float32 cache
  leaf ``ops/pallas_ssm.py``'s kernel does the step in the two passes, and
  this form is what it must equal and what runs wherever the kernel's tiles
  do not exist.
- ``chunk_states``: ``T`` tokens in sub-chunks of ``chunk`` rows (prefill).
  Inside a sub-chunk with incoming state ``S0`` and ``L_t = sum_{s<=t} dt_s A``:

      y_t = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s + exp(L_t) S0 C_t + D x_t
      S_T = exp(L_T) S0 + sum_s exp(L_T - L_s) dt_s x_s (x) B_s

  The decay is a scalar a head, so a sub-chunk is one masked ``[T, T]``
  product a head. Only differences ``L_t - L_s <= 0`` (``s <= t``) and ``L``
  itself are exponentiated.

A row that is a pad (``valid`` false) must leave both states as they were: its
``dt`` is 0 (so ``a`` is 1 and the input term 0) and its pre-convolution rows
are zeroed. Everything here is float32 at HIGHEST matmul precision, as
``ops/kda.py`` says why.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 256


def discretize(dt, dt_bias, a_log):
    """dt [..., H] (the projection's last columns), dt_bias, a_log [H].
    Returns float32 (``dt`` = softplus(dt + dt_bias), no clamp; ``dt * A`` <=
    0, the log of the step's decay)."""
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return dt, -dt * jnp.exp(a_log.astype(jnp.float32))


def _per_head(m, heads: int):
    """B or C [..., G, N] as each head sees it, [..., H, N]."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def state_step(S, x, Bm, Cm, dt, dA, D):
    """One token of the recurrence. S [B, H, P, N] float32; x [B, H, P]; Bm,
    Cm [B, G, N]; dt, dA [B, H]; D [H]. Returns (y [B, H, P], new S). A row
    with ``dt`` 0 and ``dA`` 0 leaves S as it was."""
    H = x.shape[1]
    x = x.astype(jnp.float32)
    Bh, Ch = _per_head(Bm.astype(jnp.float32), H), _per_head(Cm.astype(jnp.float32), H)
    S = (jnp.exp(dA)[..., None, None] * S
         + (dt[..., None] * x)[..., None] * Bh[..., None, :])
    y = jnp.sum(S * Ch[..., None, :], axis=-1) + D.astype(jnp.float32)[:, None] * x
    return y, S


def _chunk(S0, x, Bm, Cm, dt, dA):
    """One sub-chunk without the ``D x`` term. S0 [B, H, P, N]; x [B, C, H, P];
    Bm, Cm [B, C, G, N]; dt, dA [B, C, H]. Returns (y [B, C, H, P], S_C)."""
    C, H = x.shape[1], x.shape[2]
    mm = lambda eq, *ops: jnp.einsum(eq, *ops, precision=HIGHEST)  # noqa: E731
    L = jnp.cumsum(dA, axis=1)  # [B, C, H]
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    diff = L[:, :, None, :] - L[:, None, :, :]  # [B, t, s, H]
    decay = jnp.exp(jnp.where((s <= t)[None, :, :, None], diff, -jnp.inf))
    cb = mm("btgn,bsgn->bgts", Cm, Bm)  # C_t . B_s, once a group
    w = jnp.repeat(cb, H // cb.shape[1], axis=1) * jnp.moveaxis(decay, -1, 1)  # [B, H, t, s]
    dx = dt[..., None] * x  # [B, C, H, P]
    Ch, Bh = _per_head(Cm, H), _per_head(Bm, H)
    y = (mm("bhts,bshp->bthp", w, dx)
         + jnp.exp(L)[..., None] * mm("bthn,bhpn->bthp", Ch, S0))
    last = L[:, -1:, :]
    S = (jnp.exp(last[:, 0])[..., None, None] * S0
         + mm("bshp,bshn->bhpn", jnp.exp(last - L)[..., None] * dx, Bh))
    return y, S


def chunk_states(S0, x, Bm, Cm, dt, dA, D, chunk: int = CHUNK):
    """``T`` tokens of the recurrence in sub-chunks of ``chunk`` rows. S0 [B,
    H, P, N] float32; x [B, T, H, P]; Bm, Cm [B, T, G, N]; dt, dA [B, T, H];
    pads carry ``dt`` 0, ``dA`` 0. Returns (y [B, T, H, P], S_T)."""
    B, T = x.shape[:2]
    C = min(chunk, T)
    pad = -T % C
    n = (T + pad) // C

    def split(a):  # [B, T, ...] -> [n, B, C, ...]
        a = jnp.pad(a.astype(jnp.float32), [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, C) + a.shape[2:]), 1, 0)

    def body(S, xs):
        y, S = _chunk(S, *xs)
        return S, y

    S, y = jax.lax.scan(body, S0.astype(jnp.float32),
                        tuple(split(a) for a in (x, Bm, Cm, dt, dA)))
    y = jnp.moveaxis(y, 0, 1).reshape((B, n * C) + y.shape[3:])[:, :T]
    return y + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32), S


def recurrence(S0, x, Bm, Cm, dt, dA, D):
    """The recurrence token by token (``state_step`` under a scan): what the
    chunk form must equal. Same shapes as ``chunk_states``."""
    def body(S, xs):
        y, S = state_step(S, *xs, D)
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt, dA))
    S, y = jax.lax.scan(body, S0, xs)
    return jnp.moveaxis(y, 0, 1), S
